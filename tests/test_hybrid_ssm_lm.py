"""``models.decoder_lm``'s parallel block (a ``SelectiveSSM`` mixer beside
grouped-query attention, muP multipliers) against the plain reference of the
``falcon_h1`` family (``chipbench/reference/falcon_h1.py``), on a 3-block toy
of the published shape: 4 query heads and 2 KV heads of 16, a mixer of 4
heads of 16 channels with a state of 8 in 2 groups, scan blocks of 8.

The system is compared with the reference on log-probabilities through all
three ways a sequence reaches the mixer: ``Module`` forward (a whole
sequence from zero state), ``DecodeServer`` (chunked prefill, then decode,
slots admitted at different ticks) and ``DecodePredictor.prefill`` / ``step``
as the benchmark's comparison drives them.

Tolerances.  ``FLOAT_ATOL`` 1e-4: system and reference both compute in
float32 on the CPU and differ in the order of their sums (1e-5 measured).
Every multiplier and the carried state move the log-probabilities by far
more when dropped (the parametrised tests hold each to ten times the
tolerance), so the tolerance separates right from wrong.  ``INT8_ATOL``
0.25: an int8 pool's keys and values at heads of 16 read 0.07 to 0.12 over
seeds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import correct, harness, manifest, weights
from chipbench.drivers import serve_ticks
from chipbench.reference import falcon_h1 as ref
from mxnet_tpu.base import MXNetError
from mxnet_tpu.decode import DecodePredictor, DecodeServer

FLOAT_ATOL, INT8_ATOL = 1e-4, 0.25
T, PROMPT, CHUNK, PAGE = 40, 27, 8, 4

TOY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, intermediate_size=128,
           mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
           mamba_n_groups=2, mamba_chunk_size=8, max_position_embeddings=64,
           serve_num_hidden_layers=3, serve_dtype="float32",
           attention_in_multiplier=0.5)       # published 1: nothing to drop
# matrices around 1 / sqrt(fan-in) times what the multipliers take away, so
# that every branch and every multiplier moves the output
TOY_INIT = [
    {"match": "^embed_weight$", "dist": "normal", "std": 0.177},
    {"match": "^head_weight$", "dist": "normal", "std": 30.0},
    {"match": "_(q|k)_weight$", "dist": "normal", "std": 1.5},
    {"match": "_(v|attout)_weight$", "dist": "normal", "std": 1.5},
    {"match": "_ffn_(gate|up|down)_weight$", "dist": "normal", "std": 0.9},
    {"match": "_ssm_in_weight$", "dist": "normal", "std": 1.8},
    {"match": "_ssm_out_weight$", "dist": "normal", "std": 1.4},
]


def toy_config(**over):
    cfg = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/falcon-h1-34b.json")
    init = [r for r in cfg["init"] if not r["match"].endswith("_weight$")
            or "conv" in r["match"]]
    return dict(cfg, init=init + TOY_INIT, **dict(TOY, **over))


def build(cfg, seed=7):
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, weights.make_params(shapes, cfg, seed, "float32")


def system_probs(sym, params, toks):
    ex = sym.simple_bind(mx.cpu(), grad_req="null", data=toks.shape,
                         softmax_label=toks.shape)
    for n, v in params.items():
        ex.arg_dict[n]._set_data(v)
    ex.arg_dict["data"]._set_data(jnp.asarray(toks, jnp.float32))
    ex.forward(is_train=False)
    return ex.outputs[0].data


def predictor(sym, params, kv_dtype="", **kw):
    return DecodePredictor(
        sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()},
        cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=PAGE,
        kv_dtype=kv_dtype, prefill_chunk=CHUNK, **kw)


_SHARED = {}


def shared(sym, params, kv_dtype=""):
    """The toy's predictor of one cache type, built once: what it compiled
    serves every test that neither counts its traces nor patches it (a
    server, like ``prefill``, opens fresh pools and a fresh manager)."""
    key = (id(params), kv_dtype)
    if key not in _SHARED:
        _SHARED[key] = (params, predictor(sym, params, kv_dtype))
    return _SHARED[key][1]


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    sym, params = build(cfg)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                             size=(1, T))
    return cfg, sym, params, toks, system_probs(sym, params, toks)


def test_the_block_is_parallel_and_the_old_graphs_are_as_they_were(toy):
    cfg, sym, params, _, _ = toy
    ops = [n.op.name for n in sym._topo() if not n.is_variable]
    assert ops.count("SelectiveSSM") == ops.count("dot_product_attention") == 3
    assert params["layer0_ssm_in_weight"].shape == (64 + 96 + 4, 64)
    assert params["layer0_ssm_conv_weight"].shape == (96, 4)
    assert params["layer0_q_weight"].shape == (64, 64)
    assert params["layer0_k_weight"].shape == (32, 64)
    # a configuration without the new keys builds no scalar multiply, no
    # mixer and no `scale`: the graph MiMo's file built before this PR
    mimo = manifest.load_json(manifest.ROOT,
                              "chipbench/configs/mimo-v2.5.json")
    old = harness.build_symbol(mimo)
    kinds = {n.op.name for n in old._topo() if not n.is_variable}
    assert "SelectiveSSM" not in kinds and "_mul_scalar" not in kinds
    assert all("scale" not in (n.attrs or {}) for n in old._topo()
               if not n.is_variable and n.op.name == "dot_product_attention")


def test_full_forward_matches_the_reference(toy):
    cfg, _, params, toks, probs = toy
    out = correct.compare_logp(probs, ref.forward(params, cfg, toks)[0],
                               FLOAT_ATOL)
    assert out["ok"] and out["positions"] == T, out


@pytest.mark.parametrize("dropped", [
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers.0",
    "mlp_multipliers.1", "skip_D", "conv_bias"])
def test_each_multiplier_dropped_fails_the_tolerance(toy, dropped):
    """The comparison sees every multiplier (and the two small parameters a
    mixer could lose unseen): the system as built, against a reference that
    lacks one, is off by more than ten times ``FLOAT_ATOL``."""
    cfg, _, params, toks, probs = toy
    ref_cfg, ref_params = dict(cfg), dict(params)
    if dropped == "ssm_multipliers":
        ref_cfg[dropped] = [1.0] * 5
    elif dropped.startswith("mlp_multipliers"):
        pair = list(cfg["mlp_multipliers"])
        pair[int(dropped[-1])] = 1.0
        ref_cfg["mlp_multipliers"] = pair
    elif dropped in ("skip_D", "conv_bias"):
        key = "ssm_D" if dropped == "skip_D" else "ssm_conv_bias"
        for n in params:
            if n.endswith(key):
                ref_params[n] = jnp.zeros_like(params[n])
    else:
        ref_cfg[dropped] = 1.0
    out = correct.compare_logp(probs,
                               ref.forward(ref_params, ref_cfg, toks)[0],
                               FLOAT_ATOL)
    assert not out["ok"] and out["max_abs_dlogp"] > 10 * FLOAT_ATOL, out


def _recorded(server, pred):
    """Have ``server`` keep the distribution behind every token it
    delivers: ``{rid: [probs of token 0, 1, ...]}``.  The chunk program's
    probabilities of a request's last chunk are its first token's; a decode
    step's row ``s`` is the next token's of the request in slot ``s``."""
    seen = {}
    step, chunk = pred.paged_step, pred._chunk_fn

    def paged_step(state, lens, key=None, active=None):
        out = step(state, lens, key, active)
        for slot, rec in server._ps["active"].items():
            seen.setdefault(rec["rid"], []).append(out[1][slot])
        return out

    class Chunk:
        def __call__(self, *args):
            out = chunk(*args)
            seen[server._ps["pending"]["rid"]] = [out[1][0]]
            return out

        def __getattr__(self, name):
            return getattr(chunk, name)

    pred.paged_step, pred._chunk_fn = paged_step, Chunk()
    return seen


@pytest.mark.parametrize("kv_dtype,atol", [("", FLOAT_ATOL),
                                           ("int8", INT8_ATOL)])
def test_server_logits_match_the_reference(toy, kv_dtype, atol):
    """Five requests through three slots of ``DecodeServer``: prompts that
    are multiples neither of the chunk (8) nor of the scan's block (8),
    admitted at different ticks, retired at different ticks, two slots
    reused.  Every delivered token's distribution against ONE forward pass
    of the reference over the request's own prompt and tokens."""
    cfg, sym, params, _, _ = toy
    pred = predictor(sym, params, kv_dtype)
    assert [(g.kind, g.capacity) for g in pred._groups] == [("full", 64),
                                                            ("state", 0)]
    server = DecodeServer(pred, max_prefill=32, slots=3, spec_k=0)
    seen = _recorded(server, pred)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 27, 9, 30)]
    caps = (12, 7, 9, 12, 5)
    rids = [server.submit(p, max_new_tokens=c) for p, c in zip(prompts, caps)]
    results = server.run()
    from test_decoder_lm import padded_rows

    # the five sequences as the rows of one padded batch
    seqs = [np.concatenate([p, results[rid][:-1]])
            for rid, p in zip(rids, prompts)]
    wants = ref.forward(params, cfg, padded_rows(seqs))
    for rid, p, cap, seq, want in zip(rids, prompts, caps, seqs, wants):
        assert len(results[rid]) == cap == len(seen[rid])
        out = correct.compare_logp(jnp.stack(seen[rid]),
                                   want[p.size - 1:seq.size], atol)
        assert out["ok"] and out["positions"] == cap, (rid, out)
    # one trace of each program served every chunk, every step, every slot
    assert pred.trace_counts["chunk"] == 1
    assert pred.trace_counts["decode"] == 1
    stats = server.stats()
    assert stats["groups"]["state"] == {"rows": 3, "used_rows": 0,
                                        "peak_used_rows": 3}
    assert "'state' group" in stats["prefix_cache_off"]


@pytest.mark.parametrize("kv_dtype,eos", [("", False), ("int8", False),
                                          ("", True)])
def test_reading_behind_gives_the_tokens_of_reading_first(toy, kv_dtype,
                                                          eos):
    """A state row beside the pages under the loop that reads a tick
    behind: six requests of mixed lengths through two slots (each reused; a
    row's next owner starts from zero while the last step of its old one is
    still queued) get the tokens of the loop that reads first, as many as
    their caps; with an ``eos_id`` one answer ends in its middle, a step
    late (the dropped step advances a state nobody reads again), and no
    token moves."""
    from mxnet_tpu.test_utils import check_reading_behind

    cfg, sym, params, _, _ = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 27, 9, 30, 12)]

    def make_server(eos_id):
        return DecodeServer(shared(sym, params, kv_dtype),
                            max_prefill=32, slots=2, spec_k=0, eos_id=eos_id)

    check_reading_behind(make_server, prompts, (9, 3, 12, 1, 6, 8), eos)


def test_the_benchmarks_comparison_at_a_toy_size(toy):
    """``serve_ticks.check_against_reference`` as the cell's run calls it:
    one long row (21 tokens: two chunks and 5 of a third), the other rows
    one token each, then 4 decoded positions."""
    cfg, sym, params, _, _ = toy
    traffic = {"slots": 4, "check_prompt": 21, "check_decode": 4}
    for kv_dtype, atol in (("", FLOAT_ATOL), ("int8", INT8_ATOL)):
        out = serve_ticks.check_against_reference(
            shared(sym, params, kv_dtype), cfg, traffic, params, 5, atol)
        assert out[0]["ok"] and out[0]["positions"] == 5, out


def _state_rows(state, pred):
    """The state group's arrays of a serving state, on the host."""
    return [np.asarray(a) for l, pair in zip(pred._layouts, state.caches)
            if l.kind == "state" for a in pair]


def test_a_masked_slots_state_is_bit_identical_across_a_decode_tick(toy):
    """Slot 0 decodes, slot 1 holds a state and is masked (as a slot in the
    middle of its chunked prefill is), slot 2 is empty: one decode tick
    changes slot 0's rows and no bit of the others'."""
    cfg, sym, params, toks, _ = toy
    pred = shared(sym, params)
    both = np.zeros((3, 20), np.float32)
    both[0], both[1] = toks[0, :20], toks[0, 20:]
    state, _ = pred.prefill(both, np.array([20, 13, 1]))
    before = _state_rows(state, pred)
    assert all(np.abs(a[1]).max() > 0 for a in before)
    lens = np.array([20, 13, 1])
    state, _ = pred.paged_step(state, lens, active=np.array([1, 0, 0]))
    assert int(state.ssm) == 3          # slot 0's row of each of 3 mixers
    for a, b in zip(before, _state_rows(state, pred)):
        assert np.array_equal(a[1:], b[1:])
        assert not np.array_equal(a[0], b[0])
    # and the masked slot goes on from where it was: its next logits are
    # those of a predictor that never ticked in between
    state, probs = pred.paged_step(state, lens + [1, 0, 0],
                                   active=np.array([0, 1, 0]))
    alone = pred                    # a fresh state of one row
    state1, _ = alone.prefill(both[1:2], np.array([13]))
    _, want = alone.step(state1)
    # (another batch shape: equal up to the order of the products' sums)
    assert np.allclose(probs[1], want[0], rtol=1e-4, atol=1e-7)


def test_a_reused_slot_gives_the_logits_of_a_fresh_server(toy):
    """One slot, two requests: the second runs in the row the first left
    (no program clears it) and reads what it reads on a fresh server."""
    cfg, sym, params, _, _ = toy
    rng = np.random.default_rng(11)
    first, second = rng.integers(0, 96, size=29), rng.integers(0, 96, size=13)

    def serve(prompts):
        pred = predictor(sym, params)
        server = DecodeServer(pred, max_prefill=32, slots=1, spec_k=0)
        seen = _recorded(server, pred)
        rids = [server.submit(p, max_new_tokens=6) for p in prompts]
        server.run()
        return [np.stack([np.asarray(x) for x in seen[r]]) for r in rids]

    after, = serve([first, second])[1:]
    fresh, = serve([second])
    assert np.array_equal(after, fresh)


@pytest.mark.parametrize("zeroed", [False, True])
def test_zeroing_the_carried_state_between_chunks_fails_the_limit(toy,
                                                                  zeroed):
    """The comparison sees the state a chunk hands the next: a prompt of 27
    in chunks of 8 with the state group's rows zeroed after the second chunk
    reads far outside the tolerance (and inside it when left alone)."""
    cfg, sym, params, toks, _ = toy
    pred = shared(sym, params)
    state = pred.paged_batch_state(1)
    mgr = pred._manager
    prompt = toks[0, :PROMPT].astype(np.int64)
    _, pages, reserve = mgr.gate(prompt, prompt.size, 64,
                                 budget_wrap_forks=False)
    mgr.map_slot(0, pages, reserve)
    key = pred._zero_key
    caches, _, _ = pred._chunked_fill(state.caches, 0, prompt[:16], 0, key)
    if zeroed:
        caches = tuple(
            tuple(jnp.zeros_like(a) for a in pair) if l.kind == "state"
            else pair for l, pair in zip(pred._layouts, caches))
    _, _, probs = pred._chunked_fill(caches, 0, prompt, 16, key)
    want = ref.forward(params, cfg, toks[:, :PROMPT])[0, PROMPT - 1:]
    out = correct.compare_logp(probs, want, FLOAT_ATOL)
    assert out["ok"] is not zeroed, out
    if zeroed:
        assert out["max_abs_dlogp"] > 100 * FLOAT_ATOL, out


def test_what_a_state_row_cannot_carry_is_refused_by_name(toy):
    cfg, sym, params, toks, _ = toy
    pred = shared(sym, params)
    assert not pred.has_window_group
    assert [g.kind for g in pred.unshared_groups] == ["state"]
    with pytest.raises(MXNetError, match="'state' cache group.*rejected "
                                         "draft has already advanced"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=2)
    server = DecodeServer(pred, max_prefill=32, slots=2, spec_k=0)
    assert not server._swap_armed
    with pytest.raises(MXNetError, match="'state' cache group.*not in pages"):
        server.inject(object())
    # the predictor's own verify step, past the server's refusal
    state, _ = pred.prefill(toks[:, :9].astype(np.float32), np.array([9]))
    with pytest.raises(MXNetError, match="SelectiveSSM.*rejected draft"):
        pred.verify_step(state, np.zeros((1, 3), np.int32))
    # restoring pages, at the manager
    mgr = pred._manager
    with pytest.raises(MXNetError, match="'state' group cannot carry it.*"
                                         "not in pages"):
        mgr.gate_pages(3)
    # no prefix cache: a repeated prompt is computed again, never shared
    server.submit(np.arange(20), max_new_tokens=4)
    server.submit(np.arange(20), max_new_tokens=4)
    out = server.run()
    assert np.array_equal(out[0], out[1])
    # the dense ring and a mesh refuse the graph by the op's name
    nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
    with pytest.raises(MXNetError, match="SelectiveSSM nodes is served "
                                         "paged only"):
        DecodePredictor(sym, nd, cache_len=64, ctx=mx.cpu(), paged=False)
    from mxnet_tpu.parallel.mesh import MeshConfig, build_mesh

    with pytest.raises(MXNetError, match="SelectiveSSM nodes is served on "
                                         "one device"):
        DecodePredictor(sym, nd, cache_len=64, paged=True, page_tokens=PAGE,
                        mesh=build_mesh(MeshConfig(data=2, seq=2, model=2)))


def test_serving_avals_and_pool_bytes_know_the_state_group(toy):
    cfg, sym, params, _, _ = toy
    pred = predictor(sym, params, "int8")
    avals = pred.serving_avals(5, chunk_w=CHUNK)
    assert sorted(avals) == ["chunk", "commit", "decode"]
    env, state, tables, active, key = avals["decode"]
    assert [t.shape for t in tables] == [(5, 16), (5, 1)]
    assert [t.shape for t in avals["chunk"][2]] == [(1, 16), (1, 1)]
    kinds = [l.kind for l in pred._layouts]
    for kind, pair in zip(kinds, state.caches):
        if kind == "state":
            assert [a.shape for a in pair] == [(5, 3, 96), (5, 4, 16, 8)]
            assert [str(a.dtype) for a in pair] == ["float32", "float32"]
        else:
            assert pair[0].data.shape == (5 * 16 + 1, PAGE, 32)
    assert pred.state_row_bytes() == 3 * 4 * (3 * 96 + 4 * 16 * 8)
    pred.paged_batch_state(5)
    pages = 3 * 2 * (81 * PAGE * 32 + 81 * PAGE * 2 * 4)
    assert pred.pool_bytes() == pages + 5 * pred.state_row_bytes()
    # the state's type is the node's: bfloat16 halves the state, not the tail
    half = predictor(harness.build_symbol(dict(
        cfg, ssm_state_dtype="bfloat16")), params)
    assert half.state_row_bytes() == 3 * (4 * 3 * 96 + 2 * 4 * 16 * 8)


@pytest.fixture(scope="module")
def bfloat16_served(toy):
    """A predictor over bfloat16 weights that has served four requests
    through three slots, and the shapes of the caches its loop carries."""
    from mxnet_tpu import obs
    from mxnet_tpu.analysis.hlo_parse import shape_str

    cfg, sym, params, _, _ = toy
    obs.programs.reset(clear_static=True)
    pred = predictor(sym, {k: v.astype(jnp.bfloat16)
                           for k, v in params.items()}, "int8")
    server = DecodeServer(pred, max_prefill=32, slots=3, spec_k=0)
    rng = np.random.default_rng(1)
    for n in (9, 12, 7, 10):
        server.submit(rng.integers(0, cfg["vocab_size"], size=(n,)),
                      max_new_tokens=4)
    server.serve_reset()
    ps = server.serve_open()
    while server.has_work:
        server.serve_tick()
    yield pred, sorted(shape_str(a.shape, a.dtype) for a in
                       jax.tree_util.tree_leaves(ps["state"].caches))
    obs.programs.reset(clear_static=True)


@pytest.mark.parametrize("program,stem", [
    ("paged_decode_step", "jit__paged_decode_impl"),
    ("prefill_chunk", "jit__chunk_impl")])
def test_the_maps_are_the_programs_the_loop_runs_from_its_second_tick(
        bfloat16_served, program, stem):
    """With bfloat16 weights a mixer's conv tail is allocated bfloat16 and
    comes back float32 from every block after the first, so jax traces a
    second decode and a second chunk program on a session's second tick,
    and those run from then on.  ``obs.programs``' maps must be theirs, not
    the first dispatch's (which ran once): read with nothing compiled, and
    with the entry parameters of the state the loop really carries."""
    import re

    from mxnet_tpu import obs

    _, live = bfloat16_served
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    entry = obs.programs.instruction_maps()[stem]
    assert not compiles and entry["source"] == "dispatched"
    carried = sorted(
        v["shape"].split("{")[0] for k, v in entry["instructions"].items()
        if v["opcode"] == "parameter" and re.match(r"(state_)?caches_", k))
    assert carried == live
    assert obs.programs.scope_map(program) == {
        k: v["scope"] for k, v in entry["instructions"].items()}
