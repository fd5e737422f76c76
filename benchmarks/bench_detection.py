#!/usr/bin/env python
"""Detection-scale NMS benchmark: MultiBoxDetection at SSD300 size.

The reference hand-kernels this op (multibox_detection.cu); here it is a
dense-IoU + masked-scan formulation.  This benchmark records what that
costs at the reference's real scale — 8732 anchors, 21 classes (VOC SSD300)
— so the number is on the table instead of unmeasured (round-3 Weak #7).

Run: python benchmarks/bench_detection.py [--anchors 8732] [--classes 21]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--anchors", type=int, default=8732)
    ap.add_argument("--classes", type=int, default=21)  # incl background
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--topk", type=int, default=400)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.registry import get_op, invoke

    rng = np.random.RandomState(0)
    a = args.anchors
    # plausible SSD head output: most anchors background
    logits = rng.randn(args.batch, args.classes, a).astype(np.float32)
    logits[:, 0] += 3.0
    cls_prob = np.exp(logits) / np.exp(logits).sum(1, keepdims=True)
    loc_pred = (rng.randn(args.batch, a * 4) * 0.1).astype(np.float32)
    centers = rng.rand(1, a, 4).astype(np.float32)
    anchors = np.concatenate([centers[..., :2] - 0.05 * centers[..., 2:],
                              centers[..., :2] + 0.05 * centers[..., 2:]],
                             axis=-1).astype(np.float32)

    def run(**attrs):
        outs, _ = invoke(get_op("MultiBoxDetection"),
                         [jnp.asarray(cls_prob), jnp.asarray(loc_pred),
                          jnp.asarray(anchors)],
                         dict({"nms_threshold": 0.45, "threshold": 0.01},
                              **attrs))
        return outs[0]

    for name, attrs in [
            ("full NMS (all candidates)", {}),
            ("nms_topk=%d (reference's SSD eval setting)" % args.topk,
             {"nms_topk": args.topk})]:
        # invoke() is already jit-cached per (op, attrs)
        out = run(**attrs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            out = run(**attrs)
        float(jnp.sum(out))                  # host-readback sync
        dt = (time.perf_counter() - t0) / reps
        kept = int(jnp.sum(out[..., 0] >= 0))
        print("%s: %7.1f ms/batch%d (%.1f ms/img), %d detections kept"
              % (name, dt * 1e3, args.batch, dt * 1e3 / args.batch, kept),
              flush=True)


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
