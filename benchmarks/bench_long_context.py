#!/usr/bin/env python
"""Benchmark: long-context attention-LM training throughput across meshes.

A causal attention LM at T=8192, full training step (forward + backward +
fused optimizer update, ONE donated XLA program), measured on the three
canonical mesh shapes of the ring×TP composition story:

* ``seq``     — sequence-only ring: (data=1, seq=n); ring attention with
                K/V rotating over all n devices.
* ``tp``      — Megatron tensor parallel only: (data=1, model=n); the
                GSPMD einsum path (the partitioner all-gathers K/V — the
                O(T) memory/comms plan ring exists to beat).
* ``ring_tp`` — the composed (data, seq, model) mesh: head groups shard
                over 'model' INSIDE the ring's shard_map region, each
                model shard rotating only its own K/V slice.

Ring meshes are measured under BOTH communication schedules —
``serial`` (each hop's ppermute issued after the hop's kernel,
``MXNET_RING_DOUBLE_BUFFER=0``) and ``overlapped`` (the double-buffered
default: the K/V fetch for hop r+1, and the backward ring's traveling
dK/dV rotation, issued before hop r's kernel) — so the overlap win is a
measured row, not a claim.  Each run also reports the train step's
collective traffic from compiled HLO (``parallel.hlo_stats``): total
bytes plus the async-pair "overlappable" bytes (nonzero on backends
that split collectives into start/done, i.e. TPU).

The benches' contract: ONE json line on stdout —
``{"metric": "attention_lm_tokens_per_sec_t<T>", "value", "unit",
"vs_baseline", "vs_serial"}`` — where the value is the ring×TP
mesh rate under the overlapped schedule, ``vs_baseline`` is its speedup
over the TP-only GSPMD einsum plan on the same chips, and ``vs_serial``
its speedup over its own serial schedule.  Per-(mesh, schedule) detail
(tokens/s, sustained TFLOP/s, traced attention path, collective
bytes) goes to stderr, one json per run.

Env knobs: BENCH_T, BENCH_BATCH, BENCH_EMBED, BENCH_HEADS, BENCH_VOCAB,
BENCH_ITERS, BENCH_DTYPE, BENCH_MESHES (comma-filter, e.g. "seq,ring_tp"),
BENCH_SCHEDULES (comma-filter, "serial,overlapped"), BENCH_HLO (force
collective accounting on/off; default on except TPU, where the extra
fwd+bwd lowering would recompile a T=8192 program just for byte counts).
CPU runs shrink all dims and force an 8-virtual-device host platform so
the meshes exist (same trick as tests/conftest.py).

``--smoke``: the tier-1 CI entry — forces the 8-virtual-device CPU
platform and tiny dims (T=64) so the JSON contract and both schedules
stay runnable on every PR (tests/test_bench_contract.py invokes it).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE = "--smoke" in sys.argv

# the virtual-device mesh must exist BEFORE jax initializes its backend
if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"
if os.environ.get("JAX_PLATFORMS", "") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import numpy as np


def _flops_per_token(t, e, vocab, causal=True):
    """Forward FLOPs per token of the attention LM (2 * MACs).

    qkv projections (3 matmuls E->E) + attention scores/values against
    T keys (halved by causal masking) + out-projection E->E + vocab head.
    Embedding lookups are gathers, not FLOPs.  Training ~= 3x forward.
    """
    proj = 3 * 2 * e * e + 2 * e * e
    attn = 4 * e * t * (0.5 if causal else 1.0)
    head = 2 * e * vocab
    return proj + attn + head


def _mesh_configs(n):
    """The three measured mesh shapes over n devices (insertion order =
    report order; ring_tp last so its rate is the headline)."""
    from mxnet_tpu.parallel import MeshConfig

    cfgs = {
        "seq": MeshConfig(data=1, seq=n),
        "tp": MeshConfig(data=1, model=n),
    }
    if n >= 8:
        cfgs["ring_tp"] = MeshConfig(data=2, seq=n // 4, model=2)
    elif n >= 4:
        cfgs["ring_tp"] = MeshConfig(data=1, seq=n // 2, model=2)
    return cfgs


def main():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import config as _config
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.ops.attention import PATH_TAKEN
    from mxnet_tpu.parallel.hlo_stats import collective_stats

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    on_tpu = platform == "tpu"

    t = int(os.environ.get("BENCH_T",
                           "64" if SMOKE else "8192" if on_tpu else "256"))
    b = int(os.environ.get("BENCH_BATCH", "2"))
    e = int(os.environ.get("BENCH_EMBED",
                           "32" if SMOKE else "2048" if on_tpu else "64"))
    heads = int(os.environ.get("BENCH_HEADS", "16" if on_tpu else "4"))
    vocab = int(os.environ.get("BENCH_VOCAB",
                               "32" if SMOKE else
                               "8192" if on_tpu else "64"))
    n_iters = int(os.environ.get("BENCH_ITERS",
                                 "1" if SMOKE else "10" if on_tpu else "2"))
    dtype = os.environ.get("BENCH_DTYPE",
                           "bfloat16" if on_tpu else "float32")
    warmup = 3 if on_tpu else 1
    # collective accounting lowers the fwd+bwd program once more — cheap
    # on the CPU harness, a full recompile at TPU bench shapes, so it is
    # on by default off-TPU only
    want_hlo = _config._parse_bool(os.environ.get("BENCH_HLO",
                                                  "0" if on_tpu else "1"))

    mesh_filter = [m for m in
                   os.environ.get("BENCH_MESHES", "").split(",") if m]
    sched_filter = [s for s in
                    os.environ.get("BENCH_SCHEDULES", "").split(",") if s]

    def build_lm():
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        emb = sym.Embedding(data, input_dim=vocab, output_dim=e,
                            name="embed")
        q = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="q")
        k = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="k")
        v = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="v")
        att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                        causal=True)
        out = sym.FullyConnected(att, num_hidden=e, flatten=False,
                                 name="proj")
        head = sym.FullyConnected(sym.Reshape(out, shape=(-1, e)),
                                  num_hidden=vocab, name="head")
        return sym.SoftmaxOutput(head, sym.Reshape(label, shape=(-1,)),
                                 name="softmax")

    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, size=(b, t)).astype(np.float32)
    y = np.concatenate([x[:, 1:], np.zeros((b, 1), np.float32)], axis=1)

    ctx_fn = mx.tpu if on_tpu else mx.cpu
    contexts = [ctx_fn(i) for i in range(n_dev)]
    train_flops_per_token = 3 * _flops_per_token(t, e, vocab)
    kind = jax.devices()[0].device_kind

    def measure(cfg):
        mod = mx.mod.Module(build_lm(), context=contexts, mesh_config=cfg,
                            compute_dtype=dtype)
        data_desc = DataDesc("data", (b, t), layout="NT")
        label_desc = DataDesc("softmax_label", (b, t), layout="NT")
        mod.bind(data_shapes=[data_desc], label_shapes=[label_desc])
        mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01,
                                             "momentum": 0.9})
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

        PATH_TAKEN["last"] = None
        for _ in range(warmup):
            mod.forward_backward(batch)
            mod.update()
        sync()
        tic = time.time()
        for _ in range(n_iters):
            mod.forward_backward(batch)
            mod.update()
        sync()
        dt = time.time() - tic

        tok_s = b * t * n_iters / dt
        tflops = tok_s * train_flops_per_token / 1e12
        row = {"tokens_per_sec": round(tok_s, 1),
               "sustained_tflops": round(tflops, 2),
               "attention_path": PATH_TAKEN["last"]}
        if want_hlo:
            # collective accounting of the program that actually trained
            # (same counting surface as the test-suite tripwires:
            # parallel/hlo_stats)
            if mod._fused_step is not None:
                hlo = mod._fused_step.compiled_hlo(mod._exec_group)
            else:
                hlo = mod._exec_group.exec_.compiled_hlo()
            if hlo is not None:
                st = collective_stats(hlo)
                row["collective_count"] = st["total"]["count"]
                row["collective_bytes"] = st["total"]["bytes"]
                row["overlappable_bytes"] = st["overlappable"]["bytes"]
        return row

    # the ring's communication schedule is env-selected at trace time:
    # serial = MXNET_RING_DOUBLE_BUFFER=0, overlapped = 1 (the default).
    # Meshes without a seq axis (tp) never trace a ring — one run.
    results, results_serial = {}, {}
    for name, cfg in _mesh_configs(n_dev).items():
        if mesh_filter and name not in mesh_filter:
            continue
        schedules = ["overlapped", "serial"] if cfg.seq > 1 else [None]
        for schedule in schedules:
            if schedule and sched_filter and schedule not in sched_filter:
                continue
            prior = os.environ.get("MXNET_RING_DOUBLE_BUFFER")
            if schedule:
                os.environ["MXNET_RING_DOUBLE_BUFFER"] = \
                    "1" if schedule == "overlapped" else "0"
                _config.refresh("MXNET_RING_DOUBLE_BUFFER")
            try:
                row = measure(cfg)
            finally:
                if schedule:
                    if prior is None:
                        os.environ.pop("MXNET_RING_DOUBLE_BUFFER", None)
                    else:
                        os.environ["MXNET_RING_DOUBLE_BUFFER"] = prior
                    _config.refresh("MXNET_RING_DOUBLE_BUFFER")
            if schedule == "serial":
                results_serial[name] = row
            else:
                results[name] = row
            print(json.dumps({"mesh": name, "mesh_shape": {
                "data": cfg.data, "seq": cfg.seq, "model": cfg.model},
                "schedule": schedule or "n/a",
                "device": kind, "dtype": dtype, "T": t, "batch": b,
                **row}), file=sys.stderr, flush=True)

    # a BENCH_SCHEDULES=serial run measures ring meshes into
    # results_serial only — those are real measurements, so the headline
    # pool merges them in (overlapped rows win for a mesh measured both
    # ways) rather than erroring or letting a schedule-less mesh like tp
    # shadow the ring rows the run was made to measure
    pool = {**results_serial, **results}
    if not pool:
        sys.exit("no mesh measured: BENCH_MESHES=%r / BENCH_SCHEDULES=%r "
                 "matched none of %s (ring_tp needs >= 4 devices; %d "
                 "present)"
                 % (os.environ.get("BENCH_MESHES", ""),
                    os.environ.get("BENCH_SCHEDULES", ""),
                    sorted(_mesh_configs(n_dev)), n_dev))
    head_name = "ring_tp" if "ring_tp" in pool else next(iter(pool))
    headline = pool[head_name]
    base = results.get("tp")
    # vs_serial only when the headline row itself is NOT the serial
    # measurement (else it would read 1.0 by construction)
    serial = (results_serial.get(head_name)
              if head_name in results else None)
    print(json.dumps({
        "metric": "attention_lm_tokens_per_sec_t%d" % t,
        "value": headline["tokens_per_sec"],
        "unit": "tok/s",
        "vs_baseline": (round(headline["tokens_per_sec"]
                              / base["tokens_per_sec"], 3)
                        if base else None),
        "vs_serial": (round(headline["tokens_per_sec"]
                            / serial["tokens_per_sec"], 3)
                      if serial else None),
    }))


if __name__ == "__main__":
    if not SMOKE:
        from mxnet_tpu.cache_dirs import arm_compile_cache

        arm_compile_cache()
    main()
