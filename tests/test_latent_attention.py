"""Multi-head latent attention (``ops.attention.LATENT_OP``) and the
``"latent"`` cache layout, on the CPU at a toy size with seeded float32
weights: the op's three forms against each other and against the plain
reference (``chipbench/reference/mistral4.py``), YaRN's constants at the
published parameters, the query temperature either side of a multiple of
``original_max_position_embeddings``, every fault
``benchmarks/probe_mistral4_faults.py`` plants on the chip failing here too,
pages of a latent group forked, extracted and installed, what the predictor
refuses by name, and the graphs of the five other serving configurations as
they were.

The absorbed decode row's Pallas kernel (``ops.pallas_decode``, PR 51) runs
here through the interpreter (``MXNET_PALLAS_INTERPRET``) over ``TILED``, the
toy at a latent width the kernel tiles (rank 128 + rope 64: pages of 8 rows
of 384 lanes): the served rows against the reference, the absorbed form
against the expanded, and every planted fault, through the kernel as through
the walk.

Tolerance.  ``ATOL`` 1e-4 on log-probabilities: system and reference both
compute in float32 and differ in the order of their sums (5e-7 measured).
The least of the planted faults moves them by more than ten times that.
"""
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import correct, harness, manifest, weights
from chipbench.reference import mistral4 as ref
from mxnet_tpu import config
from mxnet_tpu.base import MXNetError
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.ops import attention as attn
from mxnet_tpu.serve.manager import why_not

ATOL = 1e-4
CONFIG = "chipbench/configs/mistral-small-4-119b.json"
T, PROMPT, PAGE, CACHE = 600, 560, 16, 1024
# the toy's window is 24 positions, so 600 tokens cross a multiple of it 24
# times; the blocks of the walk are 256 wide, so a cache of 1024 is walked
TOY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4, head_dim=16,
           v_head_dim=16, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=8, qk_head_dim=16, intermediate_size=128,
           moe_intermediate_size=32, n_routed_experts=16,
           num_experts_per_tok=4, held_n_routed_experts=4,
           first_held_expert=4, serve_num_hidden_layers=2,
           max_position_embeddings=1024)


# the toy at a latent width whose pages are whole tiles: what the absorbed
# row's kernel takes (a cache of 1024 is two of its steps of 512)
TILED = dict(kv_lora_rank=128, qk_rope_head_dim=64, qk_head_dim=72,
             head_dim=72)
PATHS = ("walk", "kernel")


def _probe():
    spec = importlib.util.spec_from_file_location(
        "probe_mistral4_faults", os.path.join(
            manifest.ROOT, "benchmarks", "probe_mistral4_faults.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


probe = _probe()


def toy_config(**over):
    """The published configuration at the toy's widths: YaRN over a window
    of 24 at factor 8, matrices drawn wider than the cell's 0.02 (0.08:
    towards 1 / sqrt(hidden 64)) so that every mechanism moves the output."""
    cfg = manifest.load_json(manifest.ROOT, CONFIG)
    rp = dict(cfg["rope_parameters"], original_max_position_embeddings=24,
              factor=8.0)
    init = [dict(r, std=0.08) if r["match"] == "_weight$" else r
            for r in cfg["init"]]
    return dict(cfg, init=init, rope_parameters=rp, **dict(TOY, **over))


def build(cfg, seed=7):
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, weights.make_params(shapes, cfg, seed, "float32")


def predictor(sym, params, chunk=100, **over):
    kw = dict(cache_len=CACHE, ctx=mx.cpu(), temperature=0.0, paged=True,
              page_tokens=PAGE, kv_dtype="bfloat16", prefill_chunk=chunk)
    kw.update(over)
    return DecodePredictor(
        sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()},
        **kw)


def whole(sym, params, toks):
    ex = sym.simple_bind(mx.cpu(), grad_req="null", data=toks.shape,
                         softmax_label=toks.shape)
    for n, v in params.items():
        if n in ex.arg_dict:
            ex.arg_dict[n]._set_data(v)
    ex.arg_dict["data"]._set_data(jnp.asarray(toks, jnp.float32))
    ex.forward(is_train=False)
    return ex.outputs[0].data


def served(pred, toks, slots=3):
    """Probabilities at positions PROMPT - 1 .. T - 2: the prompt in chunks
    through the latent pool, then teacher-forced decode rows."""
    t = np.zeros((slots, PROMPT), np.float32)
    t[0] = toks[0, :PROMPT]
    t[1:, 0] = 5
    lens = np.ones(slots, np.int64)
    lens[0] = PROMPT
    state, probs = pred.prefill(t, lens)
    got = [probs[0]]
    for i in range(T - PROMPT - 1):
        state = state._replace(
            tok=state.tok.at[0, 0].set(int(toks[0, PROMPT + i])))
        state, probs = pred.step(state)
        got.append(probs[0])
    return jnp.stack(got)


def _toy(**over):
    cfg = toy_config(**over)
    sym, params = build(cfg)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                             size=(1, T))
    want = ref.forward(params, cfg, toks)[0]
    return cfg, sym, params, toks, want


@pytest.fixture(scope="module")
def toy():
    return _toy()


@pytest.fixture(scope="module")
def tiled():
    return _toy(**TILED)


@pytest.fixture
def on_path(request):
    """``(toy, form)`` of a path of the absorbed decode row: the walk over
    the toy's plane (a page a row, which no kernel tiles), or the kernel
    over the tiled toy's on a backend that interprets Pallas."""
    if request.param == "walk":
        yield request.getfixturevalue("toy"), "absorbed"
        return
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield request.getfixturevalue("tiled"), "absorbed-kernel"


# ---------------------------------------------------------------------------
# constants at the published parameters
# ---------------------------------------------------------------------------
def _published_spec():
    cfg = manifest.load_json(manifest.ROOT, CONFIG)
    sym = harness.build_symbol(cfg)
    node = next(n for n in sym._topo()
                if not n.is_variable and n.op.name == attn.LATENT_OP)
    return cfg, attn.latent_spec(node.parsed_attrs())


def test_yarn_constants_at_the_published_parameters():
    cfg, spec = _published_spec()
    assert attn.yarn_ramp(64, 10000.0, 32, 1, 8192) == (12, 25)
    assert ref.ramp(cfg["rope_parameters"], 64) == (12, 25)
    assert spec.scale == pytest.approx(0.19497, abs=1e-5)
    assert ref.softmax_scale(cfg) == pytest.approx(spec.scale, rel=1e-12)
    assert spec.trig_scale == 1.0           # m(128, 1) / m(128, 1)
    theta = 10000.0 ** (-2.0 * np.arange(32) / 64)
    f = np.asarray(spec.inv_freq)
    np.testing.assert_allclose(f[:13], theta[:13], rtol=1e-12)
    np.testing.assert_allclose(f[25:], theta[25:] / 128, rtol=1e-12)
    assert np.all(f[13:25] < theta[13:25]) \
        and np.all(f[13:25] > theta[13:25] / 128)
    np.testing.assert_allclose(f, ref.frequencies(cfg), rtol=1e-12)
    assert (spec.heads, spec.nope, spec.rope, spec.v, spec.rank) \
        == (32, 64, 64, 128, 256)
    assert spec.layer == "attn_latent"


def test_query_temperature_either_side_of_a_multiple():
    _, spec = _published_spec()
    at = np.array([[0, 8191, 8192, 16383, 16384, 57344, 65535]])
    got = np.asarray(attn.latent_query_scale(at, spec))[0]
    want = 1 + 0.1 * np.log(1 + np.array([0, 0, 1, 1, 2, 7, 7]))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[2] == pytest.approx(1.0693, abs=1e-4)
    assert got[5] == pytest.approx(1.2079, abs=1e-4)
    # the toy's window is 24: a tiny test crosses it
    small = attn.latent_spec(dict(
        num_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=16, query_scaling_beta=0.1,
        original_max_position_embeddings=24))
    got = np.asarray(attn.latent_query_scale(np.array([[23, 24, 47, 48]]),
                                             small))[0]
    np.testing.assert_allclose(
        got, 1 + 0.1 * np.log([1, 2, 2, 3]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.query_temperature(toy_config(),
                                         jnp.array([23, 24, 47, 48]))),
        got, rtol=1e-6)
    assert attn.latent_query_scale(at, small._replace(temp_beta=0.0)) is None


# ---------------------------------------------------------------------------
# the three forms, the reference
# ---------------------------------------------------------------------------
def test_the_whole_sequence_is_the_references(toy):
    _, sym, params, toks, want = toy
    got = whole(sym, params, toks)
    assert correct.compare_logp(got, want, ATOL)["ok"]


@pytest.mark.parametrize("on_path,chunk,forms", [
    ("walk", 100, {"expanded"}),        # uneven: a chunk straddles pages
    ("walk", 40, set()),                # chunks below the switch: absorbed
    ("kernel", 100, {"expanded"}),
    # chunks of 40 rows are absorbed and walked; the decode row's kernel
    ("kernel", 40, {"absorbed"}),
], indirect=["on_path"])
def test_chunks_then_decode_rows_through_a_paged_latent_pool(on_path, chunk,
                                                             forms):
    (cfg, sym, params, toks, want), row_form = on_path
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    before = harness.program_counters()
    pred = predictor(sym, params, chunk=chunk)
    got = served(pred, toks)
    check = correct.compare_logp(got, want[PROMPT - 1:T - 1], ATOL)
    assert check["ok"], check
    took = harness.program_counters(since=before)
    assert {k.split("form=")[1].rstrip("}") for k in took
            if k.startswith("mx_attn_latent_dispatch_total")} \
        == forms | {row_form}
    # the program's record says the same, a set a rows-a-slot
    assert pred._decode_paths[1] == {row_form}
    assert pred._decode_paths[chunk] == {attn.latent_form(chunk)}
    layouts = pred.cache_layouts()
    assert [l.kind for l in layouts] == ["latent"] * 2
    assert all((l.kv_heads, l.capacity, l.key_width) == (0, CACHE, width)
               for l in layouts)
    assert [g.kind for g in pred._groups] == ["full"]
    # 193 pages of 16 positions x width values, two nodes, float32; a page a
    # row at 24 values, a page 8 rows of two positions at 192
    assert pred.pool_bytes() == 2 * 193 * 16 * width * 4
    state = pred.paged_batch_state(3)
    assert {x.shape for node in state.caches for x in node} \
        == {(193, PAGE * 24) if width == 24 else (193, 8, 2 * width)}
    assert pred.attn_walk(3) == [(CACHE, 256)] * 2


@pytest.mark.parametrize("path,rank,rope,cases", [
    # the walk over the live blocks (a pool of 1024 positions a slot) and
    # the view gathered whole (one block)
    ("walk", 16, 8, ((64, 5), (64, 130), (8, 3))),
    # one row a slot over pages of whole tiles: the kernel, two steps and
    # one and a quarter
    ("kernel", 128, 64, ((64, 1), (40, 1))),
])
def test_absorbed_is_expanded_to_float32_rounding(monkeypatch, path, rank,
                                                  rope, cases):
    """Both cached forms over the same pool, table and queries (the flip
    moved under and over the call's rows), the absorbed one by the walk and
    by the decode row's kernel."""
    from mxnet_tpu.ops.pallas_decode import latent_plane_shape

    spec = attn.latent_spec(dict(
        num_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=rope,
        v_head_dim=16, kv_lora_rank=rank))
    width = rank + rope
    rng = np.random.default_rng(1)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    w_kvb = f32(4 * 24, rank) * 0.3
    took = {"walk": "absorbed", "kernel": "absorbed-kernel"}[path]
    for pages_a_slot, t in cases:
        b = 2
        plane = f32(*latent_plane_shape(1 + b * pages_a_slot, PAGE, width))
        assert plane.ndim == {"walk": 2, "kernel": 3}[path]
        table = jnp.asarray(1 + np.arange(b * pages_a_slot).reshape(
            b, pages_a_slot), jnp.int32)
        total = jnp.asarray([pages_a_slot * PAGE - 7, 40 + t], jnp.int32)
        q_nope, q_rope = f32(b, t, 4, 8), f32(b, t, 4, rope)
        out = {}
        for form, flip in (("absorbed", t + 1), ("expanded", 1)):
            monkeypatch.setattr(attn, "LATENT_EXPAND_ROWS", flip)
            assert attn.latent_form(t) == form
            with config.overrides(
                    MXNET_PALLAS_INTERPRET=str(int(path == "kernel"))):
                out[form] = attn.latent_attend(q_nope, q_rope, plane, table,
                                               total, w_kvb, spec)
            assert attn.DECODE_PATH["last"] == \
                (took if form == "absorbed" else "expanded")
        monkeypatch.undo()
        assert out["absorbed"].shape == (b, t, 4 * 16)
        np.testing.assert_allclose(out["absorbed"], out["expanded"],
                                   rtol=2e-5, atol=2e-5)
    assert attn.latent_form(1) == "absorbed"
    assert attn.latent_form(2048) == "expanded"


# ---------------------------------------------------------------------------
# every planted fault fails the tolerance
# ---------------------------------------------------------------------------
def _patched():
    """What ``probe.planted`` patches, as it stands."""
    from mxnet_tpu.models import decoder_lm

    return [getattr(attn, n) for n in (
        "latent_spec", "latent_query_scale", "latent_attend",
        "latent_rotate")] + [decoder_lm.sym.RMSNorm]


@pytest.mark.parametrize("on_path", PATHS, indirect=True)
@pytest.mark.parametrize("fault", probe.FAULTS + ("sound",))
def test_a_planted_fault_fails(on_path, fault):
    """Each fault the probe plants in the serving programs on the chip, here:
    chunks and decode rows under it are not the reference's (ten times the
    tolerance at the least), whether the decode rows take the walk or the
    kernel; the module is as it was when the fault is lifted, and after the
    last of them a graph built and traced anew (``"sound"``, which plants
    nothing) serves the reference's rows."""
    (cfg, _, params, toks, want), row_form = on_path
    before = _patched()
    with probe.planted(fault):
        sym = harness.build_symbol(cfg)
        served_params = probe.coarse(params) if fault == "fp8_weights" \
            else params
        pred = predictor(sym, served_params)
        got = served(pred, toks)
    assert pred._decode_paths[1] == {row_form}
    check = correct.compare_logp(got, want[PROMPT - 1:T - 1], ATOL)
    if fault == "sound":
        assert check["ok"], check
    else:
        assert check["max_abs_dlogp"] > 10 * ATOL, (fault, check)
    # and the module is as it was
    assert attn.latent_attend.__module__ == attn.__name__
    assert all(a is b for a, b in zip(_patched(), before))


# ---------------------------------------------------------------------------
# pages are pages
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("on_path", PATHS, indirect=True)
def test_pages_extract_and_install_on_a_latent_group(on_path):
    """One slot's pages out of one predictor's pools and into another row of
    another's: the decode rows that follow are the same, whether a page is
    stored a row (the toy) or eight rows of two positions (the tiled toy,
    whose decode rows take the kernel)."""
    (cfg, sym, params, toks, want), _ = on_path
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    page = (PAGE * 24,) if width == 24 else (8, 2 * width)
    a, b = predictor(sym, params), predictor(sym, params)
    t = np.zeros((2, PROMPT), np.float32)
    t[0] = toks[0, :PROMPT]
    t[1, 0] = 5
    lens = np.array([PROMPT, 1], np.int64)
    state_a, _ = a.prefill(t, lens)
    flipped = t[::-1].copy()
    state_b, _ = b.prefill(flipped[:, :1], np.array([1, 1], np.int64))
    row = a._manager.tables[0]
    data = a.extract_pages(state_a.caches, row)
    assert [tuple(x.shape for x in node) for node in data] \
        == [((CACHE // PAGE,) + page,)] * 2
    # row 1 of b takes the pages, under ids of b's own (the restore path of
    # serve.swap: the same gate, fresh pages at the same ring positions)
    mgr = b._manager
    mgr.free_slot(1)
    valid = row > 0
    assert valid.sum() == -(-PROMPT // PAGE)
    need = int(valid.sum()) + 1
    assert mgr.gate_pages(need)
    mgr.restore_slot(1, valid, need)
    caches = b.install_pages(state_b.caches, mgr.tables[1], data)
    state_b = state_b._replace(
        caches=caches, lens=jnp.asarray([1, PROMPT], jnp.int32),
        tok=jnp.asarray([[5], [int(toks[0, PROMPT])]], jnp.int32))
    b._paged_lens = np.array([1, PROMPT], np.int64)
    state_a = state_a._replace(
        tok=state_a.tok.at[0, 0].set(int(toks[0, PROMPT])))
    _, probs_a = a.step(state_a)
    _, probs_b = b.step(state_b)
    np.testing.assert_allclose(probs_b[1], probs_a[0], rtol=1e-5, atol=1e-7)
    assert correct.compare_logp(probs_b[1:2], want[PROMPT:PROMPT + 1],
                                ATOL)["ok"]


def test_a_shared_prefix_forks_pages_of_a_latent_group(toy):
    """Two requests with one long prefix through ``DecodeServer``: the second
    maps the first's pages, forks the one it writes into, and both decode
    what the reference's argmax says."""
    cfg, sym, params, toks, _ = toy
    pred = predictor(sym, params, chunk=96)
    assert why_not("prefix", pred._groups) is None
    assert why_not("restore", pred._groups) is None
    assert why_not("speculation", pred._groups, rows=3) is None
    server = DecodeServer(pred, max_prefill=PROMPT, slots=2, spec_k=0)
    shared = toks[0, :200]
    prompts = [np.concatenate([shared, toks[0, 300:330]]),
               np.concatenate([shared, toks[0, 400:450]])]
    rids = [server.submit(p, max_new_tokens=4) for p in prompts]
    results = server.run()
    stats = server.stats()
    assert stats["prefix_cache_hits"] >= 1 and stats["cow_forks"] >= 1
    for rid, prompt in zip(rids, prompts):
        # one pass over the finished sequence gives every position's argmax
        seq = np.concatenate([prompt, results[rid]]).astype(np.int64)
        logits = ref.forward(params, cfg, seq[None, :-1])[0]
        assert [int(t) for t in jnp.argmax(logits[len(prompt) - 1:], -1)] \
            == [int(t) for t in results[rid]]


def test_publishing_a_long_prompt_costs_by_its_pages():
    """The prefix cache under prompts of tens of thousands of tokens (the
    first traffic that shares a group with them): a key is one page long, so
    publishing 65,536 tokens makes 4096 small entries (whole-chain keys were
    134 M token references and seconds of host time), and matching, the
    radix frontier and the router's chain digests read as they did."""
    import time

    from mxnet_tpu.serve import PageAllocator, PrefixCache, chain_hash

    n, pt = 65536, 16
    toks = np.random.default_rng(5).integers(0, 131072, size=n + 5)
    alloc = PageAllocator(2 * (n // pt) + 8)
    pages = [alloc.alloc() for _ in range(n // pt + 1)]
    cache = PrefixCache(pt, alloc)
    began = time.perf_counter()
    cache.insert(toks, n + 5, pages)
    assert time.perf_counter() - began < 5.0
    assert cache.pages_held == n // pt + 1
    assert all(len(content) <= pt for _, content in cache._entries)
    assert cache.match(toks[:n + 5]) == (n + 4, pages)
    other = toks[:50000].copy()
    other[40003] += 1           # diverges three tokens into page 2500
    matched, got = cache.match(other)
    assert matched == 40003 and got == pages[:2501]
    assert cache.match(toks[5:])[0] == 0
    summ = cache.summary()
    assert len(summ["full"]) == n // pt and len(summ["partial"]) == 1
    for k in (0, 1, 2047, 4095):
        assert chain_hash(toks[:(k + 1) * pt]) in summ["full"]
    assert summ["partial"][0] == {"prefix": chain_hash(toks[:n]), "len": 5,
                                  "hash": chain_hash(toks[n:n + 5])}
    # a page recycled under the chain drops its entry and what continued it
    # (out of reach from then on); the slot's refs hold the pages
    assert cache.release_page(pages[10]) == n // pt + 1 - 10
    assert cache.match(toks[:n])[0] == 10 * pt
    assert cache.pages_held == 10 and len(cache.summary()["full"]) == 10
    assert cache.evict(10 ** 6) == 0
    for page in pages:
        alloc.decref(page)
    assert alloc.used_pages == 10
    assert cache.evict(1) == 10             # the first page takes the rest
    assert cache.pages_held == 0 and not cache._children and not cache._meta


def test_what_a_latent_graph_refuses_by_name(toy):
    _, sym, params, _, _ = toy
    with pytest.raises(MXNetError, match="latent plane in the serving type"):
        predictor(sym, params, kv_dtype="int8")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                             ("data", "model"))
    with pytest.raises(MXNetError, match="latent row has none"):
        predictor(sym, params, mesh=mesh)
    # "bfloat16" says what an empty string says: the pools in the type the
    # graph computes in (float32 here)
    named, empty = predictor(sym, params), predictor(sym, params, kv_dtype="")
    assert named._kv_dtype is None and empty._kv_dtype is None
    state = named.paged_batch_state(2)
    assert [tuple((x.shape, str(x.dtype)) for x in node)
            for node in state.caches] \
        == [(((129, PAGE * 24), "float32"),)] * 2


def test_the_probe_refuses_to_read_on_the_cpu():
    """Its readings set the cell's limit: none comes from another device."""
    with pytest.raises(SystemExit, match="not a TPU"):
        probe.main(["--seeds", "1"])


def test_what_the_builder_refuses_by_name():
    """The two published keys whose other value no configuration brings."""
    for over in ({"q_lora_rank": 0}, {"rope_interleave": False}):
        with pytest.raises(ValueError, match="q_lora_rank .*rope_interleave"):
            harness.build_symbol(toy_config(**over))


def test_a_latent_node_under_plain_rotary_is_the_references():
    """``rope_parameters`` without YaRN and without a temperature: plain
    frequencies, the softmax's scale 1 / sqrt(nope + rope)."""
    cfg = toy_config()
    cfg["rope_parameters"] = {"rope_theta": 10000.0, "rope_type": "default"}
    sym, params = build(cfg)
    node = next(n for n in sym._topo()
                if not n.is_variable and n.op.name == attn.LATENT_OP)
    spec = attn.latent_spec(node.parsed_attrs())
    assert spec.scale == 16 ** -0.5 and spec.trig_scale == 1.0
    assert attn.latent_query_scale(np.zeros((1, 4)), spec) is None
    np.testing.assert_allclose(spec.inv_freq,
                               10000.0 ** (-np.arange(4) / 4.0), rtol=1e-12)
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"],
                                             size=(1, 96))
    assert correct.compare_logp(whole(sym, params, toks),
                                ref.forward(params, cfg, toks)[0], ATOL)["ok"]


def test_a_dense_ring_holds_latent_rows_too(toy):
    """Without pages the node keeps a (B, C, rank + rope) ring: prefill and
    decode steps are the reference's."""
    _, sym, params, toks, want = toy
    pred = predictor(sym, params, paged=False, cache_len=T)
    state, probs = pred.prefill(toks[:, :PROMPT].astype(np.float32))
    got = [probs[0]]
    for i in range(8):
        state = state._replace(
            tok=state.tok.at[0, 0].set(int(toks[0, PROMPT + i])))
        state, probs = pred.step(state)
        got.append(probs[0])
    assert [tuple(x.shape for x in node) for node in state.caches] \
        == [((1, T, 24),)] * 2
    assert correct.compare_logp(jnp.stack(got),
                                want[PROMPT - 1:PROMPT + 8], ATOL)["ok"]


# ---------------------------------------------------------------------------
# the other configurations' graphs stand
# ---------------------------------------------------------------------------
# DecodePredictor._symbol_fingerprint of each serving configuration's graph
# at its cell's size, read on the parent of the PR that added the latent
# block (PR 50): the builder's new arguments change no graph but its own
GRAPHS = {
    "opt-1.3b": "bceb4581d890d2e4b51d3ab0ab854177",
    "mimo-v2.5": "48787ef4ee3512d76c19e26a4a1b80c5",
    "falcon-h1-34b": "810f014fd3c99efaee92299dd2ba9485",
    "minicpm-sala": "cead51deaa3a7687c0c7eef3d5405d2c",
    "k-exaone-236b": "46f9590840df9a11de020f0314a632ec",
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_the_other_configurations_keep_their_graphs(name):
    entry = manifest.find(manifest.load_manifest()["configs"], name,
                          "config")
    cfg = manifest.load_json(manifest.ROOT, entry["file"])
    sym = harness.build_symbol(cfg)
    assert not any(n.op.name == attn.LATENT_OP for n in sym._topo()
                   if not n.is_variable)
    assert DecodePredictor._symbol_fingerprint(
        types.SimpleNamespace(_symbol=sym)) == GRAPHS[name]
