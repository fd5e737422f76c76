#!/usr/bin/env python
"""Input-pipeline benchmark: can ImageIter's decode feed the TPU train rate?

The reference decodes JPEG with multi-threaded C++ workers
(src/io/iter_image_recordio.cc:31-343); here decode is cv2 (GIL-releasing)
under a Python ThreadPool (image.py preprocess_threads).  This benchmark
measures end-to-end iterator throughput — RecordIO read + JPEG decode +
augment + batch assembly — against the measured ResNet-50 train rate, so
the "is the real-data path input-bound?" question has a number.

Run: python benchmarks/bench_input_pipeline.py [--images N] [--batch B]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

TRAIN_RATE_IMG_S = 2464   # bench.py, this repo's round-4 chip measurement


def make_dataset(path_rec, path_idx, n, hw=256):
    import cv2

    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(path_idx, path_rec, "w")
    for i in range(n):
        # realistic JPEG entropy: smoothed noise, quality 90 (im2rec default)
        img = rng.randint(0, 255, (hw, hw, 3), np.uint8)
        img = cv2.blur(img, (4, 4))
        ok, buf = cv2.imencode(".jpg", img,
                               [int(cv2.IMWRITE_JPEG_QUALITY), 90])
        assert ok
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0), buf.tobytes()))
    w.close()


def bench_iter(path_rec, path_idx, batch, threads, epochs=3):
    import mxnet_tpu as mx

    it = mx.image.ImageIter(
        batch_size=batch, data_shape=(3, 224, 224),
        path_imgrec=path_rec, path_imgidx=path_idx,
        shuffle=True, rand_crop=True, rand_mirror=True, seed=0,
        preprocess_threads=threads)
    n = 0
    # warm epoch (thread pool spin-up, page cache); don't count pad slots
    for b in it:
        n += b.data[0].shape[0] - b.pad
    per_epoch = n
    t0 = time.perf_counter()
    for _ in range(epochs):
        it.reset()
        for b in it:
            pass
    dt = time.perf_counter() - t0
    return per_epoch * epochs / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--threads", default="1,2,4,8,16")
    args = ap.parse_args()

    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        rec = os.path.join(tmp, "bench.rec")
        idx = os.path.join(tmp, "bench.idx")
        make_dataset(rec, idx, args.images)
        size_mb = os.path.getsize(rec) / 2 ** 20
        print("dataset: %d jpegs, %.1f MB" % (args.images, size_mb),
              flush=True)
        best = 0.0
        for t in [int(x) for x in args.threads.split(",")]:
            rate = bench_iter(rec, idx, args.batch, t)
            best = max(best, rate)
            print("preprocess_threads=%-2d : %7.0f img/s  (%.2fx the "
                  "%d img/s train rate)"
                  % (t, rate, rate / TRAIN_RATE_IMG_S, TRAIN_RATE_IMG_S),
                  flush=True)
        verdict = "input-bound" if best < TRAIN_RATE_IMG_S else "compute-bound"
        print("best decode rate %.0f img/s -> real-data training is %s "
              "on this host" % (best, verdict), flush=True)


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
