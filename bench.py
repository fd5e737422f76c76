#!/usr/bin/env python
"""Benchmark: ResNet-50 ImageNet-shape training throughput on one TPU chip.

Mirrors the reference's headline benchmark
(`example/image-classification/train_imagenet.py --benchmark 1` —
BASELINE.md: 181.53 img/s on P100).  Synthetic data (as --benchmark 1 uses),
full training step: forward + backward + SGD-momentum update, compiled as
ONE donated XLA program (bf16 compute, fp32 master weights) — see
mxnet_tpu/train_step.py.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"} plus the
async-loop accounting fields {"input_stall_fraction", "host_syncs_per_step"}
(profiler.step_stats) and the device it ran on {"platform", "device_kind",
"device_count"}; sustained TFLOP/s and MFU go to stderr.  The measurement
needs a TPU: on any other platform it exits non-zero before building the
model, and a ``device_kind`` the peak table does not name is an error.

``--smoke``: tiny-MLP fit through the FULL async training loop (device-side
metrics + device prefetch + bounded in-flight dispatch) on the CPU harness —
the tier-1 hook that keeps the loop-accounting contract honest
(tests/test_bench_contract.py).
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 181.53  # ResNet-50 train bs32, P100 (docs/how_to/perf.md:188)

# fwd-pass FLOPs for ResNet-50 at 224x224 (2 * MACs); backward ~= 2x forward
RESNET50_FWD_FLOPS = 4.1e9
TRAIN_FLOPS_PER_IMG = 3 * RESNET50_FWD_FLOPS

def contract_line(metric, value, unit, vs_baseline, **extra):
    """The one-line stdout JSON contract every bench emits — and now the
    analysis CLI too (tools/mxlint.py), so CI consumes one schema:
    {"metric", "value", "unit", "vs_baseline", ...extras}."""
    row = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": vs_baseline}
    row.update(extra)
    return json.dumps(row)


def _peak_for(device):
    """(peak_flops_or_None, device_kind) — the spec-sheet table now lives
    with the telemetry subsystem (obs.roofline.PEAK_FLOPS) so the bench
    and the per-program MFU table share one map."""
    from mxnet_tpu.obs.roofline import peak_flops_for

    return peak_flops_for(device)


def _make_recordio_dataset(n_images, tmpdir):
    """Synthetic JPEG .rec (cached): the real-data input path."""
    import cv2

    from mxnet_tpu import recordio

    rec = os.path.join(tmpdir, "bench_%d.rec" % n_images)
    idx = os.path.join(tmpdir, "bench_%d.idx" % n_images)
    if os.path.exists(rec) and os.path.exists(idx):
        return rec, idx
    # write under per-process temp names and publish atomically: neither an
    # interrupted nor a concurrent generation may leave a pair the
    # existence check accepts
    rng = np.random.RandomState(0)
    tmp_rec = "%s.%d.tmp" % (rec, os.getpid())
    tmp_idx = "%s.%d.tmp" % (idx, os.getpid())
    w = recordio.MXIndexedRecordIO(tmp_idx, tmp_rec, "w")
    for i in range(n_images):
        img = cv2.blur(rng.randint(0, 255, (256, 256, 3), np.uint8), (4, 4))
        ok, buf = cv2.imencode(".jpg", img,
                               [int(cv2.IMWRITE_JPEG_QUALITY), 90])
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0), buf.tobytes()))
    w.close()
    os.replace(tmp_rec, rec)
    os.replace(tmp_idx, idx)
    return rec, idx


def main():
    from mxnet_tpu.cache_dirs import arm_compile_cache

    cache_dir = arm_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    print(json.dumps(dict(device, compile_cache=cache_dir)),
          file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        # the metric is a chip number; a CPU run under its name would be a
        # different experiment (--smoke is the CPU test of the loop)
        sys.exit("bench.py measures on a TPU; jax found platform=%r (%s)"
                 % (dev.platform, dev.device_kind))
    from mxnet_tpu.obs.roofline import require_peak_flops

    # an unknown device_kind is an error here, not a null MFU
    peak, kind = require_peak_flops(dev)

    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu import ndarray as nd

    batch_size = int(os.environ.get("BENCH_BATCH", "256"))
    n_iters = int(os.environ.get("BENCH_ITERS", "20"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    warmup = 5
    # --recordio / BENCH_RECORDIO=1: feed real decoded JPEG batches through
    # ImageRecordIter (RecordIO read + cv2 decode + augment + prefetch)
    # instead of a resident synthetic batch — measures the end-to-end
    # real-data rate, which benchmarks/bench_input_pipeline.py showed is
    # input-bound on few-core hosts (the reference's C++ decode threads
    # have the same per-core ceiling; they scale with cores, as does
    # preprocess_threads here since cv2 releases the GIL)
    use_recordio = "--recordio" in sys.argv or \
        os.environ.get("BENCH_RECORDIO", "0") == "1"

    from mxnet_tpu.io import DataDesc

    ctx = mx.tpu()

    net = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    mod = mx.mod.Module(net, context=ctx, compute_dtype=dtype)
    # recordio mode binds uint8 data: batches ship compact and the
    # compiled step casts to the compute dtype on device
    data_desc = DataDesc("data", (batch_size, 3, 224, 224),
                         dtype=np.uint8 if use_recordio else np.float32)
    mod.bind(data_shapes=[data_desc],
             label_shapes=[("softmax_label", (batch_size,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                         "wd": 1e-4})
    if mod._fused_step is None:
        print("WARNING: fused train step not active", file=sys.stderr)

    rng = np.random.RandomState(0)
    if use_recordio:
        import tempfile

        from mxnet_tpu import image as img_mod

        import getpass

        cache = os.path.join(tempfile.gettempdir(),
                             "mxtpu_bench_rec_" + getpass.getuser())
        os.makedirs(cache, exist_ok=True)
        rec, idx = _make_recordio_dataset(
            max(batch_size * 4, 512), cache)
        rec_iter = img_mod.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 224, 224),
            batch_size=batch_size, shuffle=True, rand_crop=True,
            rand_mirror=True, seed=0, dtype="uint8",
            preprocess_threads=max(os.cpu_count() or 1, 1))

        def batches():
            while True:
                try:
                    yield next(rec_iter)
                except StopIteration:
                    rec_iter.reset()

        batch_stream = batches()
    else:
        x = nd.array(rng.uniform(-1, 1, (batch_size, 3, 224, 224))
                     .astype(np.float32), ctx=ctx)
        y = nd.array(rng.randint(0, 1000, (batch_size,)).astype(np.float32),
                     ctx=ctx)
        resident = DataBatch([x], [y])

        def batches():
            while True:
                yield resident

        batch_stream = batches()

    def sync():
        # steps chain through the donated params, so the newest params
        # cover every outstanding step
        if mod._fused_step is not None:
            jax.block_until_ready(mod._fused_step.params)
        else:
            mod._exec_group.param_arrays[-1].wait_to_read()

    from mxnet_tpu import profiler

    for _ in range(warmup):
        mod.forward_backward(next(batch_stream))
        mod.update()
    sync()

    profiler.reset_step_stats()
    tic = time.time()
    for _ in range(n_iters):
        t0 = time.perf_counter()
        batch = next(batch_stream)
        profiler.record_input_wait(time.perf_counter() - t0)
        mod.forward_backward(batch)
        mod.update()
        profiler.record_step()
    t0 = time.perf_counter()
    sync()
    profiler.record_host_wait(time.perf_counter() - t0)
    toc = time.time()
    stats = profiler.step_stats()

    img_s = batch_size * n_iters / (toc - tic)
    tflops = img_s * TRAIN_FLOPS_PER_IMG / 1e12
    print(json.dumps({
        "device": kind, "dtype": dtype, "batch": batch_size,
        "sustained_tflops": round(tflops, 2),
        "mfu": round(tflops * 1e12 / peak, 4),
    }), file=sys.stderr)
    # the per-program roofline join (obs.mfu_table): measured dispatch
    # wall over the timed window vs static dot FLOPs / traffic bytes —
    # the per-kernel view of the aggregate MFU above (tools/mxstat.py
    # renders it; statically-counted FLOPs, not the analytic estimate)
    from mxnet_tpu import obs

    mfu_rows = obs.mfu_table()
    print(obs.render_mfu_table(mfu_rows), file=sys.stderr)
    metric = "resnet50_train_imgs_per_sec_bs%d" % batch_size
    if use_recordio:
        metric = "resnet50_recordio_train_imgs_per_sec_bs%d" % batch_size
    print(contract_line(
        metric, round(img_s, 2), "img/s",
        round(img_s / BASELINE_IMG_S, 3),
        input_stall_fraction=round(stats["input_stall_fraction"], 4),
        host_syncs_per_step=round(stats["host_syncs_per_step"], 4),
        mfu_table=mfu_rows, **device))


def smoke():
    """Tier-1 smoke: a small MLP fit on the CPU harness through the full
    async loop (device metrics, device prefetch, bounded in-flight
    dispatch) UNDER async fenced checkpointing, reporting the
    loop-accounting contract fields — including the elastic trio
    (checkpoint_stall_fraction / last_ckpt_ms / recoveries, whose
    deterministic halves tests/test_bench_contract.py pins: writes
    happened, no recovery on a clean run) — plus the per-program
    ``mfu_table`` roofline rows: the fit drives train_step, a score()
    pass drives eval_step, and a tiny KV-cached generate drives
    prefill + decode_step, so every canonical program the smoke touches
    gets a row joining measured dispatch wall against static
    FLOPs/bytes (flops, bytes, wall_s, mfu — mfu is null on the CPU
    harness, where no spec peak exists)."""
    import shutil
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")

    import mxnet_tpu as mx
    from mxnet_tpu import elastic, obs, profiler

    batch, steps_per_epoch, epochs = 32, 25, 2
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (batch * steps_per_epoch, 64)).astype(np.float32)
    y = rng.randint(0, 8, (batch * steps_per_epoch,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=128, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())

    ckpt_dir = tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
    ctl = elastic.ElasticController(checkpointer=elastic.Checkpointer(
        ckpt_dir, period=max(steps_per_epoch // 2, 1), async_write=True))
    profiler.reset_step_stats()
    tic = time.time()
    try:
        mod.fit(it, eval_metric="acc", num_epoch=epochs, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.initializer.Xavier(), elastic=ctl)
        toc = time.time()
        # loop-accounting snapshot AT the fit boundary: the contract's
        # stall fractions / host_syncs_per_step describe the fit, not
        # the extra program drives below
        stats = profiler.step_stats()
        ckpt_writes = ctl.checkpointer.writes
        steps_during_write = ctl.checkpointer.steps_during_write
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    if mod._fused_step is None:
        print("WARNING: fused train step not active", file=sys.stderr)

    # eval_step row: one device-metric score() pass over the same data
    mod.score(it, "acc")
    # prefill/decode_step rows: a tiny KV-cached generate (the canonical
    # attention-LM dims the analysis programs use)
    from mxnet_tpu.analysis.programs import _lm_params, _lm_symbol
    from mxnet_tpu.decode import DecodePredictor

    sym = _lm_symbol()
    pred = DecodePredictor(sym, _lm_params(sym, 2, 16), cache_len=16,
                           temperature=0.0, kv_dtype="", paged=False)
    pred.generate(rng.randint(0, 32, (2, 8)).astype(np.float32),
                  prompt_len=8, max_new_tokens=5)
    mfu_rows = obs.mfu_table()
    print(obs.render_mfu_table(mfu_rows), file=sys.stderr)
    print(json.dumps({"loop_stats": {k: stats[k] for k in
                                     ("steps", "host_wait_s", "input_wait_s",
                                      "metric_d2h", "metric_syncs",
                                      "ckpt_stall_s", "ckpt_writes",
                                      "recoveries")}}),
          file=sys.stderr)
    n = max(stats["steps"], 1)
    print(contract_line(
        "async_fit_mlp_imgs_per_sec_bs%d" % batch,
        round(batch * n / (toc - tic), 2), "img/s", 1.0,
        input_stall_fraction=round(stats["input_stall_fraction"], 4),
        host_syncs_per_step=round(stats["host_syncs_per_step"], 4),
        checkpoint_stall_fraction=round(stats["checkpoint_stall_fraction"],
                                        4),
        last_ckpt_ms=round(stats["last_ckpt_ms"], 2),
        ckpt_writes=ckpt_writes,
        ckpt_steps_during_write=steps_during_write,
        recoveries=stats["recoveries"],
        mfu_table=mfu_rows))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()
