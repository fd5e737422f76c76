"""Builders for the canonical programs the lint audits.

``tools/mxlint.py`` (and the tier-1 smoke) checks thirteen programs —
the compiled surfaces behind every headline number so far:

* ``train_step``  — the fused forward+backward+optimizer program
  (bfloat16 compute, donated params/slots/aux);
* ``eval_step``   — the forward+device-metric-accumulate program
  ``score()`` arms (donated accumulator state);
* ``prefill``     — the KV-cache prefill program;
* ``decode_step`` — the donated one-token decode program;
* ``decode_step_q`` — the same decode step over int8-quantized KV caches
  (per-head scale planes; the cache-bytes pass checks the data planes
  really are narrow);
* ``draft_step``  — the DRAFT model's donated decode step inside the
  speculative serving loop (a second, smaller DecodePredictor);
* ``verify_step`` — the speculative verify program: k+1 positions scored
  in one pass against the quantized caches, acceptance-rejection inside;
* ``paged_decode_step`` / ``paged_verify_step`` — the same decode and
  verify programs over SHARED page pools: per-slot page tables and
  active masks ride in as data (zero retraces across admissions, COW
  forks and retirements), appends scatter through the tables, and
  attention takes the path ``ops.attention.paged_attend`` chooses from
  the call's shapes (``meta['attn_paths']``; at these toy sizes a view is
  one block and is gathered whole: the decode row's Pallas kernel and its
  ``pallas-fallback`` tripwire are driven at a size the kernel tiles in
  tests/test_pallas_decode.py); their cache-bytes meta is the POOL total
  (the paged serving HBM bill the cache-bytes pass budgets);
* ``gqa_decode_step`` — the paged decode program under a grouped-query
  layout (num_kv_heads < num_heads): pools allocate H_kv head slices,
  and the cache-bytes pass's ``mha-under-gqa`` tripwire proves the G×
  pool shrink actually happened;
* ``ring_tp_step`` — the attention-LM fused step on the composed
  (data, seq, model) mesh: ring attention with head groups sharded on
  'model' (needs >= 4 devices; the smoke forces the 8-virtual-device
  CPU platform, same trick as tests/conftest.py);
* ``moe_train_step`` — the MoE attention-LM fused step on the composed
  (data, expert, model) mesh: top-2 capacity-slot routing dispatched
  through the explicit all-to-all ``shard_map`` program
  (``ops/moe.py``), expert stacks sharded on 'expert', the FFN hidden
  dim Megatron-split on 'model' — the collective-budget pass pins the
  dispatch/combine all-to-all count and bytes (forward AND the
  custom-VJP backward's reversed exchanges) so a sharding regression
  that silently degrades the exchange to all-gathers of the full slot
  table fails CI (needs >= 4 devices, like ``ring_tp_step``);
* ``ckpt_train_step`` — the fused step of a ``fit()`` run UNDER async
  fenced checkpointing (``mxnet_tpu.elastic``): fences snapshot the
  donated chain and a writer thread lands committed orbax steps while
  the loop keeps dispatching, and the host-sync pass then proves the
  checkpoint machinery added no callback primitives or host-transfer
  ops to the compiled program — the fence d2h lives on the writer
  thread, OUTSIDE the program (the sanctioned-transfer story in
  docs/static_analysis.md).

Every program is driven at least twice at identical shapes before its
artifact is snapshotted, so the retrace pass checks a real "second call
hit the jit cache" fact, not a vacuous first-trace count.  The three
speculative/quantized programs are driven by an actual MIXED-LENGTH
:class:`~mxnet_tpu.decode.DecodeServer` run (draft-model proposer,
prompts of different lengths, slot reuse), so their one-trace-each
retrace audit covers the real serving schedule, not a synthetic drive.
The two paged programs are likewise driven by a real SHARED-PREFIX paged
serve — chunked prefill, prefix-cache hits, copy-on-write forks and
immediate retirement all exercised before the trace counters snapshot.
Dims are tiny: the point is the *program structure* (collectives,
aliasing, callbacks, dot dtypes, cache bytes), which does not depend on
size.

The same artifacts feed the three history/placement passes: the meshed
programs (``ring_tp_step``, ``moe_train_step``) stamp per-leaf
``sharding_coverage`` meta at placement time for the sharding-coverage
audit, the drift gate (``mxlint --record/--check``) snapshots every
program's priced quantities against ``benchmarks/mxlint_snapshot.json``,
and the schedule pass reads each compiled text — a ``sync-backend`` info
on this CPU harness, with the async-overlap contract pinned on the
canned TPU corpus under ``tests/data/hlo/``.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..programs import registry as _registry

__all__ = ["CANONICAL_PROGRAMS", "build_canonical_artifacts"]

# tiny-but-structured dims shared by every builder
_MLP = dict(batch=8, features=32, hidden=32, classes=8)
_LM = dict(vocab=32, seq_len=16, embed=16, heads=4, ffn=32, layers=1,
           batch=2)
# the draft model: same vocabulary, narrower/shallower stack
_DRAFT = dict(embed=8, heads=2, ffn=16, layers=1)
_SPEC_K = 3


def _mlp_module(compute_dtype="bfloat16"):
    """A classifier Module with the fused train step armed (bfloat16
    compute so the dtype lint audits a mixed-precision program)."""
    import mxnet_tpu as mx
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.io import DataBatch

    d = _MLP
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=d["hidden"], name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=d["classes"], name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(), compute_dtype=compute_dtype)
    mod.bind(data_shapes=[("data", (d["batch"], d["features"]))],
             label_shapes=[("softmax_label", (d["batch"],))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (d["batch"], d["features"]))
                 .astype(np.float32))
    y = nd.array(rng.randint(0, d["classes"], (d["batch"],))
                 .astype(np.float32))
    return mod, DataBatch([x], [y])


def _lm_symbol(**moe_kwargs):
    from mxnet_tpu.models import attention_lm

    d = _LM
    return attention_lm.get_symbol(
        vocab_size=d["vocab"], seq_len=d["seq_len"],
        num_layers=d["layers"], embed=d["embed"], heads=d["heads"],
        ffn_hidden=d["ffn"], **moe_kwargs)


def _lm_mesh_module(mesh_cfg, symbol=None):
    """The attention LM bound on a mesh — the ring×TP composition's
    training program (or, with a MoE ``symbol``, the expert-parallel
    one)."""
    import mxnet_tpu as mx
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.io import DataBatch, DataDesc

    import jax

    d = _LM
    contexts = [mx.cpu(i) for i in range(len(jax.devices()))]
    mod = mx.mod.Module(symbol if symbol is not None else _lm_symbol(),
                        context=contexts, mesh_config=mesh_cfg)
    data_desc = DataDesc("data", (d["batch"], d["seq_len"]), layout="NT")
    label_desc = DataDesc("softmax_label", (d["batch"], d["seq_len"]),
                          layout="NT")
    mod.bind(data_shapes=[data_desc], label_shapes=[label_desc])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)
    x = rng.randint(0, d["vocab"], size=(d["batch"], d["seq_len"])) \
        .astype(np.float32)
    y = np.concatenate([x[:, 1:], np.zeros((d["batch"], 1), np.float32)],
                       axis=1)
    batch = DataBatch([nd.array(x)], [nd.array(y)],
                      provide_data=[data_desc],
                      provide_label=[label_desc])
    return mod, batch


def _drive_fused(mod, batch, steps=2):
    """Run the fused step twice at one shape (retrace ground truth)."""
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
    if mod._fused_step is None:
        raise MXNetError("fused train step did not arm; cannot build its "
                         "artifact (check MXNET_FUSED_TRAIN_STEP)")
    return mod._fused_step


def _eval_artifact(mod, batch):
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu.train_step import CompiledEvalStep

    m = metric_mod.create("acc")
    step = CompiledEvalStep(mod._exec_group, m)
    try:
        step.run(batch)
        step.run(batch)
        return step.artifact(name="eval_step")
    finally:
        step.finish()


def _lm_params(sym, batch, seq_len, seed=0, scale=0.02):
    rng = np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(batch, seq_len), softmax_label=(batch, seq_len))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = rng.normal(0, scale, shape).astype(np.float32)
    for name, shape in zip(sym.list_auxiliary_states(), aux_shapes):
        params["aux:" + name] = np.zeros(shape, np.float32)
    return params


def _decode_artifacts():
    from mxnet_tpu.decode import DecodePredictor

    import jax

    d = _LM
    rng = np.random.RandomState(0)
    sym = _lm_symbol()
    params = _lm_params(sym, d["batch"], d["seq_len"])
    pred = DecodePredictor(sym, params, cache_len=d["seq_len"],
                           temperature=0.0, kv_dtype="")
    prompt_len = d["seq_len"] // 2
    prompts = rng.randint(0, d["vocab"],
                          size=(d["batch"], d["seq_len"])) \
        .astype(np.float32)
    prompts[:, prompt_len:] = 0.0
    key = jax.random.PRNGKey(0)
    state, _ = pred.prefill(prompts, prompt_len, key)
    state, _ = pred.prefill(prompts, prompt_len, key)
    state, _ = pred.step(state, key)
    state, _ = pred.step(state, key)
    return (pred.prefill_artifact(d["batch"], d["seq_len"]),
            pred.decode_artifact(state))


def _speculative_artifacts():
    """decode_step_q / draft_step / verify_step, driven by a real
    mixed-length speculative serve.

    An int8-quantized target and a smaller draft model run a
    :class:`~mxnet_tpu.decode.DecodeServer` queue of different-length
    prompts (slot reuse included) — the mixed-length serve run the
    retrace acceptance criterion names; each program's trace counter must
    then read exactly one when its artifact snapshots.
    """
    from mxnet_tpu.decode import DecodePredictor, DecodeServer, DraftProposer
    from mxnet_tpu.models import attention_lm

    import jax

    d = _LM
    dd = _DRAFT
    rng = np.random.RandomState(1)
    target = DecodePredictor(_lm_symbol(), _lm_params(
        _lm_symbol(), d["batch"], d["seq_len"]), cache_len=d["seq_len"],
        temperature=0.0, kv_dtype="int8")
    draft_sym = attention_lm.get_symbol(
        vocab_size=d["vocab"], seq_len=d["seq_len"],
        num_layers=dd["layers"], embed=dd["embed"], heads=dd["heads"],
        ffn_hidden=dd["ffn"])
    draft = DecodePredictor(
        draft_sym, _lm_params(draft_sym, d["batch"], d["seq_len"], seed=2),
        cache_len=d["seq_len"], temperature=0.0, kv_dtype="")
    proposer = DraftProposer(draft, _SPEC_K)
    server = DecodeServer(target, max_prefill=d["seq_len"] // 2,
                          slots=d["batch"], max_new_tokens=4,
                          proposer=proposer)
    for n in (3, 5, 7, 4):          # mixed-length trace, 2x slot reuse
        server.submit(rng.randint(0, d["vocab"], size=(n,)))
    results = server.run()
    if len(results) != 4 or server.spec_steps == 0:
        raise MXNetError("speculative serve drive did not exercise the "
                         "verify program (results=%d, spec_steps=%d)"
                         % (len(results), server.spec_steps))

    # the plain quantized decode step is the serve loop's near-wrap
    # fallback; drive it twice at the serve batch shape for its artifact
    key = jax.random.PRNGKey(0)
    prompts = rng.randint(0, d["vocab"],
                          size=(d["batch"], d["seq_len"] // 2)) \
        .astype(np.float32)
    state, _ = target.prefill(prompts, d["seq_len"] // 2, key)
    state, _ = target.step(state, key)
    state, _ = target.step(state, key)
    return (target.decode_artifact(state, name="decode_step_q"),
            proposer.predictor.decode_artifact(proposer._state,
                                               name="draft_step"),
            target.verify_artifact(state, _SPEC_K, name="verify_step"))


def _paged_artifacts():
    """paged_decode_step / paged_verify_step, driven by a real
    shared-prefix paged serve.

    Four requests sharing a 6-token prefix drain through a
    :class:`~mxnet_tpu.decode.DecodeServer` over a paged predictor
    (chunked prefill, n-gram speculation): chunk admissions, prefix-cache
    hits, a COW-relevant partial-page publish, speculative verify over
    page tables and immediate retirement all run before the artifacts
    snapshot — each program's trace counter must then read exactly one.

    The programs are audited as they SERVE: which path their attention
    took is the dispatch's own choice from the shapes
    (``ops.attention.decode_kernel_selected``), recorded in
    ``meta['attn_paths']``.
    """
    from mxnet_tpu.decode import DecodePredictor, DecodeServer

    d = _LM
    rng = np.random.RandomState(3)
    pred = DecodePredictor(
        _lm_symbol(), _lm_params(_lm_symbol(), d["batch"],
                                 d["seq_len"]),
        cache_len=d["seq_len"], temperature=0.0, kv_dtype="",
        paged=True, page_tokens=4, prefill_chunk=4)
    server = DecodeServer(pred, max_prefill=12, slots=d["batch"],
                          max_new_tokens=3, spec_k=_SPEC_K)
    prefix = rng.randint(0, d["vocab"], size=(6,))
    for n in (3, 5, 2, 4):          # shared prefix, mixed tails
        server.submit(np.concatenate(
            [prefix, rng.randint(0, d["vocab"], size=(n,))]))
    results = server.run()
    stats = server.stats()
    if len(results) != 4 or server.spec_steps == 0 \
            or stats.get("prefix_cache_hit_rate", 0) <= 0:
        raise MXNetError(
            "paged serve drive did not exercise the paged programs "
            "(results=%d, spec_steps=%d, hit_rate=%s)"
            % (len(results), server.spec_steps,
               stats.get("prefix_cache_hit_rate")))
    # a fresh batch state at the same sizing lowers the SAME traces
    state = pred.paged_batch_state(d["batch"])
    return (pred.decode_artifact(state, name="paged_decode_step"),
            pred.verify_artifact(state, _SPEC_K,
                                 name="paged_verify_step"))


def _gqa_artifacts():
    """gqa_decode_step: the paged decode program under a GROUPED-QUERY
    layout (num_kv_heads < num_heads), driven by a real grouped paged
    serve.

    The grouped config (G = heads/kv_heads = 4 here) allocates pools
    H_kv heads wide — the cache-bytes meta carries the grouped promise
    (``num_kv_heads``/``attn_dims``/``cache_kv_dims``), so the
    cache-bytes pass's ``mha-under-gqa`` tripwire proves the pool really
    shrank by G and a dropped num_kv_heads is a red lint run."""
    from mxnet_tpu.decode import DecodePredictor, DecodeServer
    from mxnet_tpu.models import attention_lm

    d = _LM
    rng = np.random.RandomState(5)
    sym = attention_lm.get_symbol(
        vocab_size=d["vocab"], seq_len=d["seq_len"],
        num_layers=d["layers"], embed=d["embed"], heads=d["heads"],
        ffn_hidden=d["ffn"], num_kv_heads=1)
    pred = DecodePredictor(
        sym, _lm_params(sym, d["batch"], d["seq_len"]),
        cache_len=d["seq_len"], temperature=0.0, kv_dtype="",
        paged=True, page_tokens=4, prefill_chunk=4)
    server = DecodeServer(pred, max_prefill=12, slots=d["batch"],
                          max_new_tokens=3)
    prefix = rng.randint(0, d["vocab"], size=(6,))
    for n in (3, 5, 2, 4):          # shared prefix, mixed tails
        server.submit(np.concatenate(
            [prefix, rng.randint(0, d["vocab"], size=(n,))]))
    results = server.run()
    if len(results) != 4:
        raise MXNetError(
            "grouped paged serve drive did not complete "
            "(results=%d)" % (len(results),))
    state = pred.paged_batch_state(d["batch"])
    art = pred.decode_artifact(state, name="gqa_decode_step")
    if not art.meta.get("num_kv_heads"):
        raise MXNetError(
            "gqa_decode_step artifact carries no grouped-K/V meta; "
            "the mha-under-gqa tripwire would be vacuous")
    return (art,)


def _ckpt_train_step_artifact():
    """The fused step of a real ``fit()`` under async fenced
    checkpointing.

    A small MLP fit runs with an :class:`~mxnet_tpu.elastic.Checkpointer`
    armed (period 3, async writer): fence snapshots dispatch device
    copies and a background thread commits orbax step directories while
    the loop keeps stepping.  The artifact snapshots AFTER at least one
    commit, so the host-sync pass audits a program that demonstrably
    coexisted with live checkpointing — any callback primitive or
    host-transfer op the checkpoint path leaked into the step would land
    here as an error."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import elastic
    from mxnet_tpu.io import NDArrayIter

    d = _MLP
    rng = np.random.RandomState(4)
    X = rng.uniform(-1, 1, (d["batch"] * 6, d["features"])) \
        .astype(np.float32)
    y = rng.randint(0, d["classes"], (d["batch"] * 6,)).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=d["batch"])

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=d["hidden"], name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=d["classes"], name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(), compute_dtype="bfloat16")

    tmp = tempfile.mkdtemp(prefix="mxlint_ckpt_")
    try:
        ctl = elastic.ElasticController(checkpointer=elastic.Checkpointer(
            tmp, period=3, async_write=True))
        mod.fit(it, num_epoch=2, eval_metric="acc", optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.initializer.Xavier(), elastic=ctl)
        if ctl.checkpointer.writes < 1:
            raise MXNetError("fit-under-checkpoint drive committed no "
                             "fence checkpoint; the ckpt_train_step "
                             "artifact would not cover live checkpointing")
        if mod._fused_step is None:
            raise MXNetError("fused train step did not arm under "
                             "checkpointing")
        return mod._fused_step.artifact(name="ckpt_train_step")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _ring_mesh_config(n_dev):
    from mxnet_tpu.parallel import MeshConfig

    if n_dev >= 8:
        return MeshConfig(data=2, seq=2, model=2)
    if n_dev >= 4:
        return MeshConfig(data=1, seq=2, model=2)
    return None


def _moe_mesh_config(n_dev):
    from mxnet_tpu.parallel import MeshConfig

    if n_dev >= 8:
        return MeshConfig(data=2, expert=2, model=2)
    if n_dev >= 4:
        return MeshConfig(data=1, expert=2, model=2)
    return None


def _moe_train_step_artifact():
    """The expert-parallel MoE LM fused step on the composed
    (data, expert, model) mesh.

    A 4-expert top-2 capacity-routed attention LM trains two steps at
    one shape; the explicit all-to-all dispatch (``ops/moe.py``
    shard_map path) must actually have been taken — a silent fallback
    to the GSPMD-hint path would let the collective budget drift
    meaninglessly — so the MOE_PATH tripwire is checked before the
    artifact snapshots."""
    from mxnet_tpu.ops.moe import MOE_DISPATCH, MOE_PATH

    import jax

    cfg = _moe_mesh_config(len(jax.devices()))
    sym = _lm_symbol(moe_experts=4, moe_capacity_factor=1.25, moe_top_k=2)
    mod, batch = _lm_mesh_module(cfg, symbol=sym)
    step = _drive_fused(mod, batch)
    if MOE_PATH["last"] != "sparse_a2a":
        raise MXNetError(
            "MoE fused step did not take the explicit all-to-all "
            "dispatch (MOE_PATH=%r); the moe_train_step budget would "
            "not cover the exchange" % (MOE_PATH["last"],))
    if MOE_DISPATCH["last"] != "sort":
        raise MXNetError(
            "MoE capacity dispatch did not take the default sort-based "
            "algorithm (MOE_DISPATCH=%r); the moe_train_step budget "
            "would price the wrong pack" % (MOE_DISPATCH["last"],))
    return step.artifact(name="moe_train_step")


# ---------------------------------------------------------------------------
# registry registrations — this module IS the canonical catalog now:
# each builder group registers once with mxnet_tpu.programs.registry,
# mxlint enumerates registry.canonical_names(), and adding the 13th
# canonical program is one register_canonical call
# ---------------------------------------------------------------------------
def _train_eval_builder(want):
    out = []
    mod, batch = _mlp_module()
    if "train_step" in want:
        # the eval program needs only the bound group; driving (and
        # compiling) the fused step is the train artifact's cost
        step = _drive_fused(mod, batch)
        out.append(("train_step", step.artifact(name="train_step")))
    if "eval_step" in want:
        out.append(("eval_step", _eval_artifact(mod, batch)))
    return out


def _decode_builder(want):
    prefill, decode = _decode_artifacts()
    return [("prefill", prefill), ("decode_step", decode)]


def _speculative_builder(want):
    decode_q, draft, verify = _speculative_artifacts()
    return [("decode_step_q", decode_q), ("draft_step", draft),
            ("verify_step", verify)]


def _paged_builder(want):
    paged_decode, paged_verify = _paged_artifacts()
    return [("paged_decode_step", paged_decode),
            ("paged_verify_step", paged_verify)]


def _mesh_note(kind):
    import jax

    return ("needs >= 4 devices for a %s mesh; %d present — run under "
            "the 8-virtual-device CPU platform (tools/mxlint.py --smoke "
            "does this)" % (kind, len(jax.devices())))


def _ring_available():
    import jax

    return None if _ring_mesh_config(len(jax.devices())) is not None \
        else _mesh_note("(seq, model)")


def _ring_builder(want):
    import jax

    mod, batch = _lm_mesh_module(_ring_mesh_config(len(jax.devices())))
    step = _drive_fused(mod, batch)
    return [("ring_tp_step", step.artifact(name="ring_tp_step"))]


def _moe_available():
    import jax

    return None if _moe_mesh_config(len(jax.devices())) is not None \
        else _mesh_note("(expert, model)")


def _moe_builder(want):
    return [("moe_train_step", _moe_train_step_artifact())]


def _gqa_builder(want):
    (art,) = _gqa_artifacts()
    return [("gqa_decode_step", art)]


def _ckpt_builder(want):
    return [("ckpt_train_step", _ckpt_train_step_artifact())]


if "train_step" not in _registry.canonical_names():
    # registered once per process (module reloads must not re-register)
    _registry.register_canonical(("train_step", "eval_step"),
                                 _train_eval_builder)
    _registry.register_canonical(("prefill", "decode_step"),
                                 _decode_builder)
    _registry.register_canonical(
        ("decode_step_q", "draft_step", "verify_step"),
        _speculative_builder)
    _registry.register_canonical(
        ("paged_decode_step", "paged_verify_step"), _paged_builder)
    _registry.register_canonical(("gqa_decode_step",), _gqa_builder)
    _registry.register_canonical(("ring_tp_step",), _ring_builder,
                                 availability=_ring_available)
    _registry.register_canonical(("moe_train_step",), _moe_builder,
                                 availability=_moe_available)
    _registry.register_canonical(("ckpt_train_step",), _ckpt_builder)

# the catalog, enumerated from the registry (kept as a module constant
# for existing importers)
CANONICAL_PROGRAMS = _registry.canonical_names()


def build_canonical_artifacts(names=None):
    """Build the requested canonical artifacts (default: all thirteen) —
    a registry enumeration now (``programs.registry.build_canonical``).

    Returns ``(artifacts, notes)`` — ``notes`` maps a program that could
    not be built on this host (e.g. ``ring_tp_step`` without >= 4
    devices) to the reason, so the caller can surface the gap instead of
    silently auditing a smaller set.
    """
    return _registry.build_canonical(names)
