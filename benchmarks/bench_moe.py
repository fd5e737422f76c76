#!/usr/bin/env python
"""Benchmark: expert-parallel MoE LM training throughput.

The ROADMAP's MoE headline: tokens/s of a capacity-routed
mixture-of-experts attention LM whose ``capacity_factor > 0`` dispatch
is the explicit all-to-all ``shard_map`` program (``ops/moe.py``) over
the 'expert' mesh axis, versus the **dense one-hot-dispatch oracle** —
the same model with ``capacity_factor = 0``, where every expert
multiplies against every token behind a 0/1 mask and the per-step FFN
FLOPs scale with E.  At E=8 the oracle pays 8× the expert compute the
capacity path pays (cf·k ≈ 2.5× one dense FFN), so the capacity path
must win by construction; the bench measures by how much and pins the
program shape while at it:

* the sparse run must actually take the shard_map path (``MOE_PATH ==
  'sparse_a2a'`` — a silent fallback to GSPMD hints is a bench error);
* its compiled fused step must contain all-to-all collectives (counted
  from HLO, the same surface the mxlint collective-budget pass
  ceilings in benchmarks/budgets.json);
* at full (non-smoke) dims the capacity path must be >= 2x the dense
  oracle's tokens/s — the acceptance line.  ``--smoke`` only REPORTS
  the ratio (this harness's wall clock is shared-machine noise; the
  deterministic halves above are what tier-1 asserts).

The benches' contract: ONE json line on stdout —
``{"metric": "moe_lm_tokens_per_sec_e<E>", "value", "unit",
"vs_baseline", ...}`` — where ``vs_baseline`` (also spelled out as
``vs_dense_dispatch``) is the capacity path's speedup over the dense
oracle on the same chips, plus the all-to-all count/byte accounting.
Per-config detail goes to stderr, one json per run; its ``cost`` is the
step's static price (``analysis.cost.program_cost``: the
expert-parallel step's carries ``collective_bytes``, its exchanges).

Env knobs: BENCH_T, BENCH_BATCH, BENCH_EMBED, BENCH_FFN, BENCH_HEADS,
BENCH_VOCAB, BENCH_EXPERTS, BENCH_CF (capacity factor), BENCH_TOPK,
BENCH_ITERS, BENCH_DTYPE.  CPU runs force an 8-virtual-device host
platform so the 'expert' mesh exists (same trick as tests/conftest.py).

``--smoke``: the tier-1 CI entry — tiny dims, deterministic assertions
only (tests/test_bench_contract.py invokes it).
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

from tools.mxlint import contract_line


def main():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.analysis.cost import program_cost
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.models import attention_lm
    from mxnet_tpu.ops.moe import MOE_PATH
    from mxnet_tpu.parallel import MeshConfig
    from mxnet_tpu.parallel.hlo_stats import collective_stats
    from mxnet_tpu.programs.spec import probing

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    on_tpu = platform == "tpu"

    t = int(os.environ.get("BENCH_T",
                           "16" if SMOKE else "2048" if on_tpu else "64"))
    b = int(os.environ.get("BENCH_BATCH", "8"))
    e = int(os.environ.get("BENCH_EMBED",
                           "16" if SMOKE else "1024" if on_tpu else "32"))
    ffn = int(os.environ.get("BENCH_FFN",
                             "32" if SMOKE else "4096" if on_tpu else "64"))
    heads = int(os.environ.get("BENCH_HEADS", "8" if on_tpu else "4"))
    vocab = int(os.environ.get("BENCH_VOCAB",
                               "32" if SMOKE else
                               "8192" if on_tpu else "64"))
    experts = int(os.environ.get("BENCH_EXPERTS", "8"))
    cf = float(os.environ.get("BENCH_CF", "1.25"))
    top_k = int(os.environ.get("BENCH_TOPK", "2"))
    n_iters = int(os.environ.get("BENCH_ITERS",
                                 "1" if SMOKE else "10" if on_tpu else "3"))
    dtype = os.environ.get("BENCH_DTYPE",
                           "bfloat16" if on_tpu else "float32")
    warmup = 3 if on_tpu else 1

    ep = experts if n_dev % experts == 0 and n_dev >= experts else n_dev
    cfg = MeshConfig(data=max(1, n_dev // ep), expert=ep)

    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, size=(b, t)).astype(np.float32)
    y = np.concatenate([x[:, 1:], np.zeros((b, 1), np.float32)], axis=1)

    ctx_fn = mx.tpu if on_tpu else mx.cpu
    contexts = [ctx_fn(i) for i in range(n_dev)]
    kind = jax.devices()[0].device_kind

    def measure(capacity_factor):
        net = attention_lm.get_symbol(
            vocab_size=vocab, seq_len=t, num_layers=1, embed=e,
            heads=heads, ffn_hidden=ffn, moe_experts=experts,
            moe_capacity_factor=capacity_factor, moe_top_k=top_k)
        mod = mx.mod.Module(net, context=contexts, mesh_config=cfg,
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

        MOE_PATH["last"] = None
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

        row = {"tokens_per_sec": round(b * t * n_iters / dt, 1),
               "moe_path": MOE_PATH["last"]}
        if mod._fused_step is not None:
            hlo = mod._fused_step.compiled_hlo(mod._exec_group)
            if hlo is not None:
                st = collective_stats(hlo)
                a2a = st.get("all-to-all", {"count": 0, "bytes": 0})
                row["all_to_all_count"] = a2a["count"]
                row["all_to_all_bytes"] = a2a["bytes"]
                row["collective_bytes"] = st["total"]["bytes"]
            # the step's static price: the dense oracle's shows the E x
            # FLOP bill the capacity path avoids
            step, group = mod._fused_step, mod._exec_group
            with probing(step):
                cost = program_cost(step._entry_for(group),
                                    step._abstract_args(group))
            row["cost"] = {k: cost.get(k, 0) for k in
                           ("flops", "bytes", "collective_bytes")}
        return row

    sparse = measure(cf)
    dense = measure(0.0)

    # ---- dispatch algorithm accounting (MXNET_MOE_DISPATCH) ----------
    # price the capacity-slot assignment under BOTH algorithms at this
    # config's per-group token count: the sort path's argsort/scatter
    # intermediates vs the one-hot cumsum pack (sort_scatter_bytes is
    # the column the two modes differ in)
    from mxnet_tpu import config as _config
    from mxnet_tpu.ops import moe as _moe

    def _price_dispatch(algo):
        import jax.numpy as jnp

        # the sparse path's per-group token count shards over BOTH the
        # data and expert axes (moe.py: n_loc = n // (dp * ep))
        n_loc = b * t // max(1, cfg.data * ep)
        cap = _moe._capacity(cf, top_k, n_loc, experts, False)
        choice = jax.ShapeDtypeStruct((n_loc, top_k), jnp.int32)
        with _config.overrides(MXNET_MOE_DISPATCH=algo):
            # fresh closure per mode: jax's trace cache keys on function
            # identity, and the knob is read at trace time
            fn = jax.jit(lambda c: _moe._slot_assign(c, experts, cap))
            return program_cost(fn, (choice,))

    dispatch_cost = {algo: _price_dispatch(algo)
                     for algo in ("sort", "onehot")}
    dispatch_mode = str(_config.get("MXNET_MOE_DISPATCH")).lower()

    # ---- sort-vs-onehot token identity (the dispatch contract) -------
    # one training step of the SAME sparse model under each algorithm on
    # the composed (data=2, expert=2, model=2) mesh when 8 devices
    # exist (else this bench's data×expert mesh): outputs AND the
    # post-update params (≡ grads) must be BIT-identical — the two
    # algorithms may only differ in what they materialize, never in
    # which token lands in which slot (drop set included)
    def _one_step(algo, mesh_cfg, n_ctx):
        with _config.overrides(MXNET_MOE_DISPATCH=algo):
            net = attention_lm.get_symbol(
                vocab_size=vocab, seq_len=t, num_layers=1, embed=e,
                heads=heads, ffn_hidden=ffn, moe_experts=experts,
                moe_capacity_factor=cf, moe_top_k=top_k)
            mod = mx.mod.Module(net, context=[ctx_fn(i)
                                              for i in range(n_ctx)],
                                mesh_config=mesh_cfg, compute_dtype=dtype)
            mod.bind(data_shapes=[DataDesc("data", (b, t), layout="NT")],
                     label_shapes=[DataDesc("softmax_label", (b, t),
                                            layout="NT")])
            mx.random.seed(11)
            mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.01,
                                                 "momentum": 0.9})
            batch = DataBatch(
                [nd.array(x)], [nd.array(y)],
                provide_data=[DataDesc("data", (b, t), layout="NT")],
                provide_label=[DataDesc("softmax_label", (b, t),
                                        layout="NT")])
            mod.forward_backward(batch)
            outs = [o.asnumpy() for o in mod.get_outputs()]
            mod.update()
            params, _ = mod.get_params()
            return outs, {n_: v.asnumpy() for n_, v in params.items()}

    if n_dev >= 8 and experts % 2 == 0:
        id_cfg, id_ctx = MeshConfig(data=2, expert=2, model=2), 8
    else:
        id_cfg, id_ctx = cfg, n_dev
    s_outs, s_params = _one_step("sort", id_cfg, id_ctx)
    o_outs, o_params = _one_step("onehot", id_cfg, id_ctx)
    for a, c in zip(s_outs, o_outs):
        assert np.array_equal(a, c), \
            "sort dispatch outputs diverge from one-hot"
    for n_ in s_params:
        assert np.array_equal(s_params[n_], o_params[n_]), \
            "sort dispatch grads diverge from one-hot at %s" % n_
    for name, row in (("moe_a2a", sparse), ("dense_dispatch", dense)):
        print(json.dumps({"config": name, "device": kind, "dtype": dtype,
                          "experts": experts, "mesh_expert": ep, "T": t,
                          "batch": b, "capacity_factor":
                          cf if name == "moe_a2a" else 0.0,
                          "num_experts_per_tok": top_k, **row}),
              file=sys.stderr, flush=True)

    # deterministic halves: the capacity path must BE the explicit
    # all-to-all program, with the exchange visible in compiled HLO
    if ep > 1:
        assert sparse["moe_path"] == "sparse_a2a", sparse
        assert sparse.get("all_to_all_count", 0) > 0, sparse
        assert dense["moe_path"] == "dense", dense

    ratio = sparse["tokens_per_sec"] / dense["tokens_per_sec"]
    print(contract_line(
        "moe_lm_tokens_per_sec_e%d" % experts,
        sparse["tokens_per_sec"], "tok/s", round(ratio, 3),
        vs_dense_dispatch=round(ratio, 3),
        dense_tokens_per_sec=dense["tokens_per_sec"],
        all_to_all_count=sparse.get("all_to_all_count", 0),
        all_to_all_bytes=sparse.get("all_to_all_bytes", 0),
        capacity_factor=cf, num_experts_per_tok=top_k,
        experts=experts, mesh_expert=ep,
        moe_dispatch=dispatch_mode,
        dispatch_bytes={algo: {"bytes": c["bytes"],
                               "sort_scatter_bytes":
                               c["sort_scatter_bytes"]}
                        for algo, c in dispatch_cost.items()},
        dispatch_identical=True))

    if not SMOKE and ep > 1 and ratio < 2.0:
        # the acceptance line: at full dims the capacity path's E/(cf*k)
        # compute advantage must survive its exchange overhead
        print("FAIL: capacity path %.2fx dense one-hot dispatch "
              "(>= 2x required at E=%d)" % (ratio, experts),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if not SMOKE:
        from mxnet_tpu.cache_dirs import arm_compile_cache

        arm_compile_cache()
    sys.exit(main())
