#!/usr/bin/env python
"""Benchmark: the LM training step's Pallas kernels, off/on x
default/autotuned blocks.

The ISSUE-16 acceptance surface for the 0.15-MFU plateau: one attention
LM trained fwd+bwd+update under a 2x2 grid —

* ``kernels=off``  — the stock einsum/XLA graph (the baseline row);
* ``kernels=on``   — ``MXNET_PALLAS_FUSED`` (LN->linear epilogue
  segments) and ``MXNET_PALLAS_UPDATE`` (fused multi-tensor optimizer)
  armed (flash attention is no switch: ``dot_product_attention`` takes
  it by shape in every row, ``ops.attention.flash_selected``);
* ``blocks=default``   — each kernel's module-constant block shapes;
* ``blocks=autotuned`` — ``MXNET_PALLAS_TUNE`` armed against a fresh
  tuning-cache directory, so every kernel's block shape resolves
  through an on-device sweep (:mod:`mxnet_tpu.ops.tuning`) and the
  winners persist for the timed window.

Mirrors bench.py's contract: ONE json line on stdout —
``{"metric": "lm_train_kernels_tokens_per_sec", "value", "unit",
"vs_baseline", ...}`` — where ``vs_baseline`` is the armed+autotuned
config's tokens/s over the all-off default config on the same chips.
Extras carry the full grid (per-config tokens/s, wall, dispatch paths,
sweep probe counts) and the per-program ``mfu_table`` rows, including
each config's ``lm_fused`` row so the kernel-vs-einsum HBM pricing
travels with the measurement.  Per-config detail goes to stderr, one
json per run.

Env knobs: BENCH_T, BENCH_BATCH, BENCH_EMBED, BENCH_FFN, BENCH_HEADS,
BENCH_VOCAB, BENCH_LAYERS, BENCH_ITERS, BENCH_DTYPE.

``--smoke``: the tier-1 CI entry — tiny dims on CPU with
``MXNET_PALLAS_INTERPRET``, deterministic assertions only (the
dispatch tripwires and the priced-bytes ordering; interpret-mode wall
clock is not a measurement).
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE = "--smoke" in sys.argv

if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

import bench as _bench


def main():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import config as _config
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import obs
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.models import attention_lm
    from mxnet_tpu.ops import tuning
    from mxnet_tpu.ops.fused_lm import FUSED_PATH

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    interp = not on_tpu  # CPU/GPU harness: kernels run in interpret mode

    t = int(os.environ.get("BENCH_T",
                           "128" if SMOKE else "2048" if on_tpu else "128"))
    b = int(os.environ.get("BENCH_BATCH", "2" if SMOKE else "8"))
    e = int(os.environ.get("BENCH_EMBED",
                           "64" if SMOKE else "1024" if on_tpu else "64"))
    ffn = int(os.environ.get("BENCH_FFN",
                             "128" if SMOKE else "4096" if on_tpu else "128"))
    heads = int(os.environ.get("BENCH_HEADS", "2" if SMOKE else "8"))
    vocab = int(os.environ.get("BENCH_VOCAB",
                               "32" if SMOKE else
                               "8192" if on_tpu else "64"))
    layers = int(os.environ.get("BENCH_LAYERS", "1" if SMOKE else "4"))
    n_iters = int(os.environ.get("BENCH_ITERS",
                                 "1" if SMOKE else "10" if on_tpu else "2"))
    dtype = os.environ.get("BENCH_DTYPE",
                           "bfloat16" if on_tpu else "float32")
    warmup = 3 if on_tpu else 1

    # m = B*T must satisfy pallas_fused.supported's m % 256 gate or the
    # whole grid degenerates to einsum-gated
    assert (b * t) % 256 == 0, (b, t)

    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, size=(b, t)).astype(np.float32)
    y = np.concatenate([x[:, 1:], np.zeros((b, 1), np.float32)], axis=1)

    ctx = mx.tpu(0) if on_tpu else mx.cpu()
    peak, kind = _bench._peak_for(jax.devices()[0])

    # separate cache dirs per blocks-mode: default runs must never read
    # the autotuned runs' persisted winners (tuning.resolve consults the
    # disk cache even when the sweep is not armed)
    cache_default = tempfile.mkdtemp(prefix="lmk_default_")
    cache_tuned = tempfile.mkdtemp(prefix="lmk_tuned_")

    def measure(kernels_on, autotuned):
        name = "lmk_%s_%s" % ("on" if kernels_on else "off",
                              "tuned" if autotuned else "default")
        overrides = {
            "MXNET_PALLAS_FUSED": kernels_on,
            "MXNET_PALLAS_UPDATE": kernels_on,
            "MXNET_PALLAS_INTERPRET": kernels_on and interp,
            "MXNET_PALLAS_TUNE": autotuned,
            "MXNET_PROGRAM_CACHE": cache_tuned if autotuned
            else cache_default,
        }
        tuning.reset_memo()
        probes_before = tuning.PROBE_COUNT["n"]
        with _config.overrides(**overrides):
            net = attention_lm.get_symbol(
                vocab_size=vocab, seq_len=t, num_layers=layers, embed=e,
                heads=heads, ffn_hidden=ffn)
            mod = mx.mod.Module(net, context=ctx, compute_dtype=dtype)
            data_desc = DataDesc("data", (b, t), layout="NT")
            label_desc = DataDesc("softmax_label", (b, t), layout="NT")
            mod.bind(data_shapes=[data_desc], label_shapes=[label_desc])
            mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.01})
            batch = DataBatch([nd.array(x)], [nd.array(y)],
                              provide_data=[data_desc],
                              provide_label=[label_desc])

            def sync():
                import jax.numpy as jnp

                if mod._fused_step is not None:
                    src = next(iter(mod._fused_step.params.values()))
                else:
                    src = mod._exec_group.param_arrays[-1].data
                return float(jnp.sum(src.astype(jnp.float32)))

            FUSED_PATH["last"] = None
            for _ in range(warmup):
                mod.forward_backward(batch)
                mod.update()
            sync()
            if mod._fused_step is not None:
                # rename the roofline rows so each grid config keeps its
                # own train_step / opt_update / lm_fused join
                mod._fused_step.telemetry_name = name
                mod._fused_step._static_registered = False
            tic = time.time()
            for _ in range(n_iters):
                mod.forward_backward(batch)
                mod.update()
            sync()
            dt = time.time() - tic
            rows = [r for r in obs.mfu_table(peak)
                    if r["program"].startswith(name)]

        return {"config": name,
                "tokens_per_sec": round(b * t * n_iters / dt, 1),
                "wall_s": round(dt, 4),
                "fused_path": FUSED_PATH["last"],
                "tune_probes": tuning.PROBE_COUNT["n"] - probes_before,
                "mfu_table": rows}

    grid = [measure(kernels_on, autotuned)
            for kernels_on in (False, True)
            for autotuned in (False, True)]
    for row in grid:
        print(json.dumps(row), file=sys.stderr, flush=True)

    by_name = {r["config"]: r for r in grid}
    base = by_name["lmk_off_default"]
    best = by_name["lmk_on_tuned"]

    # deterministic halves: dispatch tripwires and priced-bytes ordering
    assert base["fused_path"] == "einsum", base
    assert best["fused_path"] == "pallas", best
    assert best["tune_probes"] > 0, best
    assert by_name["lmk_on_default"]["tune_probes"] == 0, by_name
    fused_rows = [r for r in best["mfu_table"]
                  if r["program"].endswith("lm_fused")]
    assert fused_rows and fused_rows[0]["fused_path"] == "pallas", fused_rows
    assert fused_rows[0]["fused_kernel_bytes"] \
        < fused_rows[0]["fused_einsum_bytes"], fused_rows

    ratio = best["tokens_per_sec"] / base["tokens_per_sec"]
    print(_bench.contract_line(
        "lm_train_kernels_tokens_per_sec",
        best["tokens_per_sec"], "tok/s", round(ratio, 3),
        vs_einsum_default=round(ratio, 3),
        device_kind=kind, smoke=SMOKE, interpret=interp,
        dims={"b": b, "t": t, "embed": e, "ffn": ffn, "heads": heads,
              "vocab": vocab, "layers": layers, "iters": n_iters,
              "dtype": dtype},
        grid={r["config"]: {"tokens_per_sec": r["tokens_per_sec"],
                            "wall_s": r["wall_s"],
                            "fused_path": r["fused_path"],
                            "tune_probes": r["tune_probes"]}
              for r in grid},
        lm_fused=fused_rows[0],
        mfu_table=[r for g in grid for r in g["mfu_table"]]))
    return 0


if __name__ == "__main__":
    if not SMOKE:
        from mxnet_tpu.cache_dirs import arm_compile_cache

        arm_compile_cache()
    sys.exit(main())
