"""The decode row's Pallas kernel over paged KV pools (ops/pallas_decode.py),
the rule that chooses it and the wiring that reports it.

All in interpret mode on the CPU harness (the same kernel Mosaic compiles on
a TPU), plus compiles of the kernel at the serving cells' shapes for a
described v5e:

* parity of ``paged_attend``'s kernel path with ``_sdpa_cache`` over the
  whole gathered view, at toy sizes with each admitted node's proportions
  (H = H_kv with heads of 64, 4 and 30 of them: one product over every KV
  head; heads of 128 over 4 and over 8 KV heads, two, five and eight query
  heads a KV head: a product a KV head; K and V of unequal width with a sink
  and a value scale: a product two KV heads) over float32, bfloat16, int8
  and fp8 pools, with slots at length 0, 1, exactly one block, one past it
  and a wrapped ring;
* dead rows of the padded list reach nothing; the int8 case against
  ``dequantize_kv`` + dense attention;
* ``decode_kernel_selected`` as a table over the eight serving
  configurations' nodes (read from ``chipbench/configs``), which products
  the kernel takes at each (``Tiles.body``), and what the rule refuses;
  ``mx_attn_dispatch_total{path}`` and ``mx_attn_decode_body_total{body}``
  after tracing a toy graph with one full and one window node, four query
  heads over four KV heads and eight;
* the paged server token-identical with the kernel and with the walk;
  ``program_cost`` prices the walk's gather and not the kernel's; the
  flop-dtype pass's ``pallas-fallback`` artifact tripwire;
* the absorbed row's kernel over a latent plane (``attend_latent_blocks``,
  PR 51) at the published widths (32 heads, rank 256, rope 64, pages of 16)
  against the walk, its dead rows, its rule (``latent_kernel_selected``) and
  its compile at ``mistral4_serve_longdoc``'s shapes.

Tolerance: rtol 1e-4 / atol 1e-5, what docs/inference.md states for
reordered float32 sums (``tests/test_paged_live_blocks.py`` holds the walk
to the same).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import config, obs
from mxnet_tpu.ops import attention as attn
from mxnet_tpu.ops import pallas_decode as pd

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
import bench_decode_kernel as probe  # noqa: E402

RTOL, ATOL = 1e-4, 1e-5
PT, M = 16, 40                  # a view of 640 positions: 2.5 blocks of 256
# the admitted nodes' proportions at toy sizes: (H, H_kv, hd, hdv, sink,
# value scale)
NODES = {
    "mha_heads_of_64": (4, 4, 64, 64, False, 1.0),          # opt-1.3b
    "heads_of_128_over_4": (8, 4, 128, 128, False, 1.0),    # falcon-h1-34b
    "unequal_sink_scale": (8, 4, 192, 128, True, 0.707),    # mimo-v2.5
    # 30 KV heads: a token's 60 scales padded to 64 (attn.scale_group)
    "mha_30_heads": (30, 30, 64, 64, False, 1.0),           # olmo-hybrid-7b
    # eight query heads a KV head: a product a KV head, 24 rows of 32
    "heads_of_128_over_8": (64, 8, 128, 128, False, 1.0),   # solar-open2-250b
    # five: a group's piece is 5 rows of a sublane tile's 8
    "five_heads_a_kv_head": (20, 4, 128, 128, False, 1.0),  # falcon-h1-34b
}
# empty, one position, exactly a block, one past it, a wrapped ring
LENS = (0, 1, 256, 257, M * PT + 9)


@pytest.fixture
def interpret():
    """A backend that runs Pallas through the interpreter."""
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield


def _case(node, dtype, seed=0, lens=LENS, qdtype=jnp.float32):
    h, kvh, hd, hdv, sink, value_scale = NODES[node]
    b = len(lens)
    rng = np.random.RandomState(seed)
    k = jnp.asarray(rng.randn(1 + b * M, PT, kvh * hd).astype(np.float32))
    v = jnp.asarray(rng.randn(1 + b * M, PT, kvh * hdv).astype(np.float32))
    if dtype in ("int8", "float8_e4m3fn"):
        kp, vp = attn.quantize_pools(k, v, dtype, kvh)
    else:
        kp, vp = k.astype(dtype), v.astype(dtype)
    table = jnp.asarray(1 + rng.permutation(b * M).reshape(b, M), jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, h * hd).astype(np.float32)).astype(qdtype)
    kw = dict(num_heads=h, num_kv_heads=kvh, value_scale=value_scale,
              sink=jnp.asarray(rng.randn(h).astype(np.float32))
              if sink else None)
    return (q, kp, vp, table, jnp.asarray(lens, jnp.int32)), kw


def _whole(args, kw):
    q, kp, vp, table, total = args
    return attn._sdpa_cache(q, *attn.paged_gather_kv(
        kp, vp, table, kw["num_kv_heads"]), total, kw["num_heads"], None,
                            num_kv_heads=kw["num_kv_heads"], sink=kw["sink"],
                            value_scale=kw["value_scale"])


def _walk(args, kw):
    """``paged_attend`` on a backend shown no Pallas."""
    out = attn.paged_attend(*args, **kw)
    assert attn.DECODE_PATH["last"] == "walk"
    return out


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8",
                                   "float8_e4m3fn"])
@pytest.mark.parametrize("node", sorted(NODES))
def test_kernel_parity_with_the_whole_view(node, dtype, interpret):
    """The kernel path of ``paged_attend`` against ``_sdpa_cache`` over the
    whole gathered view, every slot at another length; the empty slot (whose
    answer is an average of unwritten pages, of the first block's alone on
    the block paths) against the walk."""
    args, kw = _case(node, dtype,
                     qdtype=jnp.bfloat16 if dtype == "bfloat16"
                     else jnp.float32)
    got = attn.paged_attend(*args, **kw)
    assert attn.DECODE_PATH["last"] == "decode-kernel"
    ref = _whole(args, kw)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    # a bfloat16 pool's output is rounded to bfloat16 on both sides
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" \
        else dict(rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(got[1:], np.float32),
                               np.asarray(ref[1:], np.float32), **tol)
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        walk = _walk(args, kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(walk, np.float32), **tol)


def test_int8_against_dequantized_dense_attention(interpret):
    """The int8 kernel path against attention over the DEQUANTIZED float
    buffers (``dequantize_kv``, then plain float32 einsums and a softmax):
    the pools are attended as stored and the result is that of attending
    what they stand for."""
    args, kw = _case("heads_of_128_over_4", "int8", seed=3,
                     lens=(5, 200, 256, 300, 640))
    q, kp, vp, table, total = args
    h, kvh = kw["num_heads"], kw["num_kv_heads"]
    got = attn.paged_attend(*args, **kw)
    assert attn.DECODE_PATH["last"] == "decode-kernel"
    kq, vq = attn.paged_gather_kv(kp, vp, table)
    k, v = (np.asarray(attn.dequantize_kv(c)) for c in (kq, vq))
    b, c, _ = k.shape
    qh = np.asarray(q, np.float32).reshape(b, kvh, h // kvh, -1)
    logits = np.einsum("bhgd,bkhd->bhgk", qh, k.reshape(b, c, kvh, -1)) \
        / np.sqrt(qh.shape[-1])
    seen = np.arange(c)[None, :] < np.minimum(np.asarray(total), c)[:, None]
    logits = np.where(seen[:, None, None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhgk,bkhe->bhge", p, v.reshape(b, c, kvh, -1))
    np.testing.assert_allclose(np.asarray(got), ref.reshape(b, 1, -1),
                               rtol=RTOL, atol=ATOL)


def test_dead_rows_of_the_padded_list_reach_nothing(interpret):
    """``attend_blocks`` visits the live prefix of the list alone: what the
    dead rows name (here: pages that do not exist) is never copied, the
    live rows' shares are ``_sdpa_cache``'s over the same blocks, and
    ``_attend_live_blocks`` over a list padded further is the same
    result."""
    args, kw = _case("mha_heads_of_64", "int8", seed=4, lens=(300, 1, 40))
    q, kp, vp, table, total = args
    h = kw["num_heads"]
    block, ppb = 256, 256 // PT
    t = pd.tiles(q.shape, kp, vp, h, h, block)
    # slot 0 has two live blocks, slots 1 and 2 one: four live rows of 12
    slot = jnp.asarray([0, 0, 1, 2] + [2] * 8, jnp.int32)
    blk = np.asarray([0, 1, 0, 0] + [0] * 8)
    pages = np.full((12, ppb), 10 ** 6, np.int32)       # no such page
    for r in range(4):
        pages[r] = np.asarray(table)[int(slot[r]),
                                     blk[r] * ppb:(blk[r] + 1) * ppb]
    valid = jnp.clip(total[slot] - blk * block, 0, block)
    m, den, acc = pd.attend_blocks(q, kp, vp, jnp.asarray(pages), slot,
                                   valid, jnp.int32(4), t,
                                   1.0 / np.sqrt(64), interpret=True)
    k_blk, v_blk = attn.paged_gather_kv(kp, vp, jnp.asarray(pages[:4]))
    want = attn._sdpa_cache(q[slot[:4]], k_blk, v_blk, total[slot[:4]], h,
                            None, block=(jnp.asarray(blk[:4] * block),
                                         M * PT))
    for got, ref in zip((m, den, acc), want):
        np.testing.assert_allclose(np.asarray(got[:4]),
                                   np.asarray(ref[:, 0]), rtol=RTOL,
                                   atol=ATOL)
    outs = [attn._attend_live_blocks(q, kp, vp, table, total, h, None, h,
                                     block, group, kernel=(t, True))
            for group in (1, 7)]
    np.testing.assert_array_equal(*(np.asarray(o) for o in outs))
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.asarray(_whole(args, kw)), rtol=RTOL,
                               atol=ATOL)


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
# what each attention node of a serving cell takes in the decode step and in
# a prefill chunk, in graph order (runs of equal nodes written once)
RULE = {
    # heads of 64: no whole lane tiles for the chunk's kernel
    "opt_serve_backlog": [("decode-kernel", "walk")] * 24,
    # chunks of 256 rows: under the chunk kernel's constant
    "falconh1_serve_chat": [("decode-kernel", "walk")] * 6,
    # full (keys of 192: the chunk keeps the walk), four window rings, full,
    # a window ring
    "mimo_serve_longshort": [("decode-kernel", "walk")]
    + [("whole", "whole")] * 4 + [("decode-kernel", "walk"),
                                  ("whole", "whole")],
    # two rows a slot (a draft beside the committed token): the walk; its
    # chunks of 512 rows too
    "exaone_serve_reason": [("whole", "whole")] * 3 + [("walk", "walk"),
                                                       ("whole", "whole"),
                                                       ("walk", "walk")],
    # a decode row attends the list it chose; a chunk of 2048 rows lays its
    # selection over the live blocks inside the chunk's kernel
    "sala_serve_longctx": [("sparse", "chunk-kernel")] * 3,
    "solar2_serve_agent": [("decode-kernel", "chunk-kernel")],
    # 30 KV heads over a padded scale row; chunks of 512 rows: under the
    # chunk kernel's constant
    "olmoh_serve_rollouts": [("decode-kernel", "walk")] * 2,
    # 2 KV heads: a page's scale row is 64 lanes at pages of 16 positions,
    # half a lane tile (``tiles`` refuses it; 16 query heads a KV head would
    # tile); chunks of 2048 rows over heads of 128
    "nemotron3_serve_agent": [("walk", "chunk-kernel")] * 2,
}
# the products the decode row's kernel takes at each cell's first node that
# takes it: (body, KV heads a product, rows a product)
BODY = {
    "opt_serve_backlog": ("whole", 32, 96),             # H = H_kv
    "olmoh_serve_rollouts": ("whole", 30, 96),
    "falconh1_serve_chat": ("grouped", 1, 32),          # 5 heads a KV head
    "mimo_serve_longshort": ("grouped", 2, 96),         # keys of 192: 384 lanes
    "solar2_serve_agent": ("grouped", 1, 32),           # 8 heads a KV head
}


@pytest.mark.parametrize("cell", sorted(RULE))
def test_rule_over_the_serving_configurations(cell, interpret):
    """``decode_kernel_selected`` and ``chunk_kernel_selected`` over the
    decode-row and chunk shapes of every attention node of six serving
    configurations, the shapes read from the files under
    ``chipbench/configs`` and ``chipbench/traffic``."""
    nodes = probe.serving_nodes(cell)
    got = [(probe.decode_path(n), probe.decode_path(n, tq=n["chunk"]))
           for n in nodes]
    assert got == RULE[cell], list(zip((n["name"] for n in nodes), got))
    # a backend that runs no Pallas takes the kernel nowhere
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        assert "decode-kernel" not in {probe.decode_path(n) for n in nodes}
        assert "chunk-kernel" not in {
            probe.decode_path(n, tq=n["chunk"]) for n in nodes}


@pytest.mark.parametrize("cell", sorted(BODY))
def test_body_of_the_kernel_at_the_serving_configurations(cell, interpret):
    """Which products ``tiles`` gives the decode row's kernel at a cell's
    shapes: a group of KV heads at a time where several query heads share
    one, the fewest whose columns are whole lane tiles; one product over
    them all where H = H_kv."""
    node = next(n for n in probe.serving_nodes(cell)
                if probe.decode_path(n) == "decode-kernel")
    b, m = node["slots"], node["cap"] // node["pt"]
    t, _ = attn.decode_kernel_selected(
        (b, 1, node["e"]), *probe.abstract_pools(node), (b, m),
        node["heads"], node["kv_heads"])
    rows = t.prows if t.body == "grouped" else t.pieces * t.rows
    assert (t.body, t.group, rows) == BODY[cell]
    g = node["heads"] // node["kv_heads"]
    assert (t.body == "grouped") == (g > 1)
    # a group's columns are whole lane tiles, its shares fit one tile of heads
    assert t.group * t.hd % 128 == 0 and t.group * t.hdv % 128 == 0
    if t.body == "grouped":
        assert t.rows == node["kv_heads"] // t.group * t.stride <= 128
        assert t.stride >= t.group * g and t.qw == t.group * t.hd


def _selected(q_shape=(4, 1, 256), ek=256, ev=256, kvh=4, heads=4, pages=M,
              dtype=jnp.int8, mesh_active=False, window=0):
    k = jax.ShapeDtypeStruct((1 + 4 * pages, PT, ek), dtype)
    v = jax.ShapeDtypeStruct((1 + 4 * pages, PT, ev), dtype)
    if jnp.dtype(dtype).itemsize == 1:
        k = attn.QuantKV(k, jax.ShapeDtypeStruct(
            (k.shape[0], PT * 2 * kvh), jnp.float32))
        v = attn.QuantKV(v, None)
    return attn.decode_kernel_selected(q_shape, k, v, (4, pages), heads, kvh,
                                       mesh_active=mesh_active,
                                       window=window)[0]


@pytest.mark.parametrize("why,kw", [
    ("two query rows a slot", dict(q_shape=(4, 2, 256))),
    ("a prefill chunk", dict(q_shape=(1, 64, 256))),
    ("a window node", dict(window=128)),
    ("a mesh shards the pools", dict(mesh_active=True)),
    ("a view of one block", dict(pages=16)),
    ("heads of 80: no whole lane tiles", dict(q_shape=(4, 1, 320), ek=320,
                                              ev=320)),
    ("2 KV heads: a scale row of 64 lanes", dict(kvh=2, ek=128, ev=128)),
    ("160 heads", dict(q_shape=(4, 1, 160 * 64), heads=160, kvh=4)),
])
def test_rule_refuses(why, kw, interpret):
    assert _selected() is not None
    assert _selected(dtype=jnp.float32) is not None
    assert _selected(**kw) is None, why


def test_a_scale_row_is_padded_only_where_its_heads_do_not_divide_a_tile(
        interpret):
    """A token's stretch of its page's scale row: ``2 * H_kv`` floats where
    that divides 128 (every accepted configuration's pools keep their plane's
    shape), the next width that does where it does not; ``tiles`` takes the
    padded plane and refuses the bare one."""
    assert [attn.scale_group(h) for h in (1, 2, 4, 8, 32, 64, 96)] \
        == [2, 4, 8, 16, 64, 128, 192]
    assert [attn.scale_group(h) for h in (3, 6, 30, 40)] == [8, 16, 64, 128]
    rng = np.random.RandomState(1)
    for kvh, width in ((8, 16), (32, 64), (30, 64)):
        k = jnp.asarray(rng.randn(3, PT, kvh * 64).astype(np.float32))
        kp, vp = attn.quantize_pools(k, k, "int8", kvh)
        assert kp.scale.shape == (3, PT * width) and vp.scale is None
        assert pd.tiles((2, 1, kvh * 64), kp, vp, kvh, kvh, 256) is not None
        # K's heads, V's heads, then zeros
        row = np.asarray(kp.scale).reshape(3, PT, width)
        assert (row[..., :2 * kvh] > 0).all() and (row[..., 2 * kvh:] == 0).all()
    bare = attn.QuantKV(kp.data, kp.scale.reshape(3, PT, 64)[..., :60]
                        .reshape(3, PT * 60))
    assert pd.tiles((2, 1, 30 * 64), bare, vp, 30, 30, 256) is None


def test_rule_needs_a_backend_that_runs_pallas():
    assert _selected() is None                  # the CPU, no interpreter
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        t = _selected()
        assert (t.heads, t.kv_heads, t.rows, t.pieces, t.ppb) == \
            (4, 4, 16, 3, 16)
        assert _selected(dtype=jnp.float32).pieces == 1


# ---------------------------------------------------------------------------
# a toy graph with one full and one window node, served
# ---------------------------------------------------------------------------
VOCAB, SLOTS, CACHE = 64, 2, 512


def _toy_lm(seed=5, heads=4):
    from mxnet_tpu.models import decoder_lm

    sym = decoder_lm.get_symbol(
        vocab_size=VOCAB, hidden_size=64, num_layers=2,
        num_attention_heads=heads,
        head_dim=64, num_key_value_heads=4, swa_num_key_value_heads=4,
        hybrid_layer_pattern=(0, 1), sliding_window=8, intermediate_size=64)
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    params = {n: (1.0 + 0.1 * rng.randn(*s) if len(s) == 1
                  else rng.normal(0, 0.08, s)).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, params


def _predictor(kv_dtype="int8", heads=4):
    from mxnet_tpu.decode import DecodePredictor

    sym, params = _toy_lm(heads=heads)
    return DecodePredictor(sym, params, cache_len=CACHE, temperature=0.0,
                           paged=True, page_tokens=PT, prefill_chunk=64,
                           kv_dtype=kv_dtype)


def _dispatched():
    counter = obs.registry.counter("mx_attn_dispatch_total",
                                   labels=("path",))
    return {path: counter.labels(path=path).get()
            for path in ("decode-kernel", "walk", "whole")}


def _bodies():
    counter = obs.registry.counter("mx_attn_decode_body_total",
                                   labels=("body",))
    return {body: counter.labels(body=body).get()
            for body in ("grouped", "whole")}


def _serve(pred):
    from mxnet_tpu.decode import DecodeServer

    server = DecodeServer(pred, max_prefill=320, slots=SLOTS,
                          max_new_tokens=3)
    rng = np.random.RandomState(9)
    ids = [server.submit(rng.randint(0, VOCAB, size=(n,)))
           for n in (300, 70, 260)]
    results = server.run()
    return [np.asarray(results[i]) for i in ids], pred


@pytest.mark.parametrize("heads,body", [(4, "whole"), (8, "grouped")])
def test_dispatch_counter_and_token_identity(heads, body, interpret):
    """Tracing the toy's programs counts, in
    ``mx_attn_dispatch_total{path}``, the full node's decode row as
    ``decode-kernel``, its chunk as ``walk`` and the window node's ring as
    ``whole`` in both, and in ``mx_attn_decode_body_total{body}`` which
    products that kernel takes (four heads over four KV heads: one over them
    all; eight: two KV heads of 64 a product); and the server emits exactly
    the walk's tokens."""
    before, bodies = _dispatched(), _bodies()
    on, pred = _serve(_predictor(heads=heads))
    after = _dispatched()
    took = {p: after[p] - before[p] for p in after}
    # one decode program and one chunk program, two nodes each
    assert took == {"decode-kernel": 1, "walk": 1, "whole": 2}, took
    assert {b: n - bodies[b] for b, n in _bodies().items() if n > bodies[b]} \
        == {body: 1}
    assert pred._decode_paths[1] == {"decode-kernel", "whole"}
    assert pred._decode_paths[64] == {"walk", "whole"}
    assert [(t.body, t.group) for t in pred._decode_tiles[1]] \
        == [(body, 4 if body == "whole" else 2)]
    assert pred._decode_tiles[64] == []
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        off, pred = _serve(_predictor(heads=heads))
    assert pred._decode_paths[1] == {"walk", "whole"}
    assert pred._decode_tiles[1] == []
    for i, (a, b) in enumerate(zip(on, off)):
        assert np.array_equal(a, b), \
            "request %d diverged: kernel %s vs walk %s" % (i, a, b)


def test_artifact_meta_and_flop_pass_tripwire(interpret):
    """A decode artifact whose trace took the kernel says so
    (``attn_paths``, ``pallas_decode``); the flop-dtype pass blesses a
    program with a ``pallas_call`` and errors on one that promised the
    kernel and lowered without it.  On a backend that runs no Pallas the
    same predictor promises nothing."""
    from mxnet_tpu.analysis import run_passes
    from mxnet_tpu.analysis.artifact import ProgramArtifact
    from mxnet_tpu.analysis.passes import FlopDtypePass

    pred = _predictor()
    art = pred.decode_artifact(pred.paged_batch_state(SLOTS))
    assert art.meta["attn_paths"] == ["decode-kernel", "whole"]
    assert art.meta["decode_bodies"] == ["whole"]
    assert art.meta["pallas_decode"] is True
    assert "pallas_call" in art.jaxpr_text
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert any(f.code == "pallas-decode" for f in rep.findings)
    assert not any(f.code == "pallas-fallback" for f in rep.findings)

    fallback = ProgramArtifact(
        name="paged_decode_step", jaxpr_text="no kernels here",
        stablehlo_text="", compiled_text="HloModule stub\n",
        meta={"pallas_decode": True})
    rep = run_passes([fallback], passes=[FlopDtypePass()])
    assert any(f.code == "pallas-fallback" for f in rep.errors)

    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        pred = _predictor()
        art = pred.decode_artifact(pred.paged_batch_state(SLOTS))
    assert art.meta["attn_paths"] == ["walk", "whole"]
    assert art.meta["decode_bodies"] == []
    assert art.meta["pallas_decode"] is False
    assert "pallas_call" not in art.jaxpr_text
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert not any(f.code.startswith("pallas") for f in rep.findings)


# ---------------------------------------------------------------------------
# pricing: the walk's gathered blocks are priced, the kernel has none
# ---------------------------------------------------------------------------
def test_gather_stats_price_paged_view():
    from mxnet_tpu.analysis.hlo_parse import stablehlo_gather_stats

    kp = jnp.zeros((9, 4, 8), jnp.float32)
    table = jnp.zeros((2, 4), jnp.int32)
    low = jax.jit(attn.paged_gather).lower(kp, table).as_text()
    stats = stablehlo_gather_stats(low)
    view_bytes = 2 * 4 * 4 * 8 * 4
    assert stats["count"] >= 1
    assert stats["bytes"] >= 2 * view_bytes  # write + re-read floor


def test_program_cost_gather_bytes_drop_with_kernel():
    """``program_cost`` over the toy's paged decode-step program: the
    walk's program is priced the pages it gathers (one step of its loop),
    the kernel's program none of the full node's."""
    from mxnet_tpu.analysis.cost import program_cost

    def price(kernel):
        with config.overrides(MXNET_PALLAS_INTERPRET="1" if kernel else "0"):
            pred = _predictor()
            state = pred.paged_batch_state(SLOTS)
            tables, active = pred._paged_probe_args(state)
            pred._probing = True
            try:
                return program_cost(
                    pred._decode_fn, (pred._env, state, tables, active,
                                      jax.random.PRNGKey(0)))
            finally:
                pred._probing = False

    walk, kernel = price(False), price(True)
    # a step of the walk gathers one block of 256 positions of K and V, a
    # byte each, for its two slots' share: the kernel's program lacks them
    assert walk["gather_bytes"] - kernel["gather_bytes"] >= 2 * 2 * 256 * 256


# ---------------------------------------------------------------------------
# the kernel at the cells' shapes, compiled for a described v5e
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler on this machine
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compiled_for_the_chip(fn, args, **jit):
    """``jax.jit(fn, **jit)`` compiled over ``args`` (avals on the described
    chip) with the persistent compilation cache off: what is compiled for a
    chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, **jit).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", ["opt_serve_backlog", "falconh1_serve_chat",
                                  "mimo_serve_longshort",
                                  "olmoh_serve_rollouts",
                                  "solar2_serve_agent"])
def test_kernel_compiles_for_the_chip_at_the_cells_shapes(cell, one_chip,
                                                          monkeypatch):
    """The decode row of each cell's first full node through
    ``paged_attend``, compiled by the chip's own compiler: Mosaic takes the
    kernel at the published widths, and the program holds no loop and no
    array of a gathered block's shape."""
    import re

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    node = next(n for n in probe.serving_nodes(cell)
                if probe.decode_path(n) == "decode-kernel")
    b, m = node["slots"], node["cap"] // node["pt"]
    aval = lambda s: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        s)
    kp, vp = probe.abstract_pools(node)
    args = (jax.ShapeDtypeStruct((b, 1, node["e"]), jnp.bfloat16),
            kp, vp, jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32))

    def attend(q, kp, vp, table, total):
        return attn.paged_attend(q, kp, vp, table, total,
                                 num_heads=node["heads"],
                                 num_kv_heads=node["kv_heads"],
                                 value_scale=node["value_scale"])

    text = _compiled_for_the_chip(attend, aval(args)).as_text()
    assert attn.DECODE_PATH["last"] == "decode-kernel"
    assert "tpu_custom_call" in text
    assert " while(" not in text
    block = attn.live_block_plan((b, 1), (b, m), node["pt"])[0]
    assert not re.search(r"s8\[\d+,%d,%d\]" % (block, node["ek"]), text)


@pytest.mark.parametrize("cell", ["sala_serve_longctx", "solar2_serve_agent",
                                  "falconh1_serve_chat",
                                  "exaone_serve_reason"])
def test_chunk_kernel_compiles_for_the_chip_at_the_cells_shapes(
        cell, one_chip, monkeypatch):
    """A prefill chunk of each cell's first full node through
    ``_attend_live_blocks`` (MiniCPM-SALA's with its selection's mask laid
    over it), compiled by the chip's own compiler: Mosaic takes the chunk's
    kernel at the published widths (``tests/test_pallas_chunk.py`` holds its
    results against the walk's), and the program holds no loop and no array
    of a gathered block's shape.  Falcon-H1's chunks of 256 rows and
    K-EXAONE's of 512 lie under the rule's constant, lowered here: a cell
    that cut the same nodes' prompts into chunks of 1024 would take the
    kernel at these widths (blocks of 256, 20 heads over 4)."""
    import re

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 256)
    node = next(n for n in probe.serving_nodes(cell)
                if probe.decode_path(n, tq=n["chunk"]) == "chunk-kernel")
    tq, m = node["chunk"], node["cap"] // node["pt"]
    h, kvh = node["heads"], node["kv_heads"]
    aval = lambda s: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        s)
    kp, vp = probe.abstract_pools(node)
    args = [jax.ShapeDtypeStruct((1, tq, node["e"]), jnp.float32), kp, vp,
            jax.ShapeDtypeStruct((1, m), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32)]
    width = node["spec"].block if node["sparse"] else 0
    if width:
        args.append(jax.ShapeDtypeStruct(
            (1, kvh, tq, -(-node["cap"] // width)), jnp.bool_))
    plan = attn.live_block_plan((1, tq), (1, m), node["pt"])
    tiles, _ = attn.chunk_kernel_selected(
        args[0].shape, kp, vp, (1, m), h, kvh,
        chosen=(args[5].shape, width) if width else None)
    assert tiles is not None and tiles.rows >= 128

    def attend(q, kp, vp, table, total, mask=None):
        return attn._attend_live_blocks(
            q, kp, vp, table, total, h, None, kvh, *plan,
            chosen=None if mask is None else (mask, width),
            chunk=(tiles, False))

    text = _compiled_for_the_chip(attend, aval(args)).as_text()
    assert "tpu_custom_call" in text
    assert " while(" not in text
    assert not re.search(r"s8\[\d+,%d,%d\]" % (plan[0], node["ek"]), text)


# ---------------------------------------------------------------------------
# the absorbed row of latent attention: a kernel of its own over one plane
# ---------------------------------------------------------------------------
# the published widths of mistral-small-4-119b's latent node
LATENT = dict(num_heads=32, qk_nope_head_dim=64, qk_rope_head_dim=64,
              v_head_dim=128, kv_lora_rank=256)
LM = 72                         # 1152 positions a slot: 2.25 steps of 512
# an empty slot, one position, a length that is no multiple of a page, a
# step exactly, one past it, a last block that is not whole, a slot at its
# capacity, a ring that has wrapped
LATENT_LENS = (0, 1, 37, 512, 513, 1100, LM * PT, LM * PT + 9)


def _latent_case(dtype, seed=0, lens=LATENT_LENS, pages=LM, **over):
    spec = attn.latent_spec(dict(LATENT, **over))
    width = spec.rank + spec.rope
    b = len(lens)
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    plane = draw(*pd.latent_plane_shape(1 + b * pages, PT, width))
    table = jnp.asarray(1 + rng.permutation(b * pages).reshape(b, pages),
                        jnp.int32)
    w_kvb = 0.06 * draw(spec.heads * (spec.nope + spec.v), spec.rank)
    return (draw(b, 1, spec.heads, spec.nope),
            draw(b, 1, spec.heads, spec.rope), plane, table,
            jnp.asarray(lens, jnp.int32), w_kvb.astype(dtype), spec)


def _latent_walk(args):
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        out = attn.latent_attend(*args)
    assert attn.DECODE_PATH["last"] == "absorbed"
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_parity_with_the_walk(dtype, interpret):
    """``latent_attend``'s kernel path against the walk over the same plane
    at the published widths, every slot at another length.  The empty
    slot's answer is an average of unwritten pages, of its first block's
    alone on either path, and the two paths' blocks differ: it is finite."""
    args = _latent_case(dtype)
    assert args[2].shape == (1 + len(LATENT_LENS) * LM, 8, 640)
    before = obs.registry.counter(
        "mx_attn_latent_dispatch_total", labels=("form",)).labels(
            form="absorbed-kernel").get()
    got = attn.latent_attend(*args)
    assert attn.DECODE_PATH["last"] == "absorbed-kernel"
    assert obs.registry.counter(
        "mx_attn_latent_dispatch_total", labels=("form",)).labels(
            form="absorbed-kernel").get() == before + 1
    ref = _latent_walk(args)
    assert got.shape == ref.shape == (len(LATENT_LENS), 1, 32 * 128)
    assert got.dtype == ref.dtype
    # the walk rounds its logits and its output to a bfloat16 plane's type
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == "bfloat16" \
        else dict(rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(got[1:], np.float32),
                               np.asarray(ref[1:], np.float32), **tol)
    assert np.all(np.isfinite(np.asarray(got[0], np.float32)))


def test_latent_kernel_against_the_plane_stored_a_page_a_row(interpret):
    """The same pages' values stored a page a row (as the plane was until
    PR 51, and is where a width gives no rows of whole lanes): the rule
    refuses it, the walk reads the same positions, the results agree."""
    args = _latent_case("float32", seed=2, lens=(700, 1152, 3))
    plane = args[2]
    a_row = plane.reshape(plane.shape[0], -1)
    got = attn.latent_attend(*args)
    assert attn.DECODE_PATH["last"] == "absorbed-kernel"
    ref = attn.latent_attend(*args[:2], a_row, *args[3:])
    assert attn.DECODE_PATH["last"] == "absorbed"
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=RTOL,
                               atol=ATOL)


def test_latent_dead_rows_of_the_padded_list_reach_nothing(interpret):
    """``attend_latent_blocks`` visits the live prefix of the list alone:
    what the dead rows name (pages that do not exist) is never copied, the
    live rows' shares are ``_sdpa_cache``'s over the same blocks re-laid out
    to positions, and ``_attend_live_blocks`` over a list padded further is
    the same result."""
    qn, qr, plane, table, total, w_kvb, spec = _latent_case(
        "float32", seed=4, lens=(600, 1, 40))
    width, h = spec.rank + spec.rope, spec.heads
    t = attn.latent_kernel_selected((3, 1, h * width), plane, table.shape,
                                    spec)[0]
    assert (t.block, t.ppb, t.per, t.pr, t.rows, t.exact) \
        == (512, 32, 2, 8, 32, True)
    w_k, _ = attn._latent_weights(w_kvb, spec)
    q = jnp.concatenate([jnp.einsum("bthd,hdr->bthr", qn, w_k), qr],
                        axis=-1).reshape(3, 1, -1)
    # slot 0 has two live blocks, slots 1 and 2 one: four live rows of 9
    slot = jnp.asarray([0, 0, 1, 2] + [2] * 5, jnp.int32)
    blk = np.asarray([0, 1, 0, 0] + [0] * 5)
    pages = np.full((9, t.ppb), 10 ** 6, np.int32)      # no such page
    padded = np.pad(np.asarray(table), ((0, 0), (0, 3 * t.ppb - LM)))
    for r in range(4):
        pages[r] = padded[int(slot[r]), blk[r] * t.ppb:(blk[r] + 1) * t.ppb]
    valid = jnp.clip(total[slot] - blk * t.block, 0, t.block)
    m, den, acc = pd.attend_latent_blocks(
        q, plane, jnp.asarray(pages), slot, valid, jnp.int32(4), t,
        spec.scale, interpret=True)
    rows = attn.latent_pages(plane, jnp.asarray(pages[:4]), width)
    want = attn._sdpa_cache(q[slot[:4]], rows, rows[..., :spec.rank],
                            total[slot[:4]], h, spec.scale, num_kv_heads=1,
                            block=(jnp.asarray(blk[:4] * t.block), LM * PT))
    for got, ref in zip((m, den, acc), want):
        np.testing.assert_allclose(np.asarray(got[:4]),
                                   np.asarray(ref[:, 0]), rtol=RTOL,
                                   atol=ATOL)
    outs = [attn._attend_live_blocks(
        q, plane, plane, table, total, h, spec.scale, 1, t.block, group,
        hdv=spec.rank, page_tokens=PT, kernel=(t, True), layer=spec.layer)
        for group in (1, 7)]
    np.testing.assert_array_equal(*(np.asarray(o) for o in outs))


def _latent_selected(rows=1, slots=20, pages=4160, mesh_active=False,
                     ring=False, a_row=False, dtype=jnp.bfloat16, **over):
    spec = attn.latent_spec(dict(LATENT, **over))
    width = spec.rank + spec.rope
    shape = pd.latent_plane_shape(1 + slots * pages, PT, width)
    if a_row:
        shape = (shape[0], PT * width)
    plane = jax.ShapeDtypeStruct(
        (slots, pages * PT, width) if ring else shape, dtype)
    return attn.latent_kernel_selected(
        (slots, rows, spec.heads * width), plane,
        None if ring else (slots, pages), spec, mesh_active=mesh_active)[0]


@pytest.mark.parametrize("why,kw", [
    ("two query rows a slot", dict(rows=2)),
    ("a mesh shards the executor", dict(mesh_active=True)),
    ("a dense ring", dict(ring=True)),
    ("a view of one step", dict(pages=32)),
    ("a plane stored a page a row", dict(a_row=True)),
    ("288 values a position: pages of four rows",
     dict(qk_rope_head_dim=32)),
    ("a rank of 192: values of no whole lane tiles",
     dict(kv_lora_rank=192, qk_rope_head_dim=128)),
    ("an int8 plane", dict(dtype=jnp.int8)),
])
def test_latent_rule_refuses(why, kw, interpret):
    assert _latent_selected() is not None
    assert _latent_selected(dtype=jnp.float32).exact
    assert _latent_selected(**kw) is None, why


def test_latent_rule_at_the_cells_shapes():
    """``latent_kernel_selected`` over ``mistral4_serve_longdoc``'s decode
    row, its shapes read from the cell's files: taken where the backend
    runs Pallas, by steps of the kernel's own rule; refused for the cell's
    prefill chunk, which is the expanded form."""
    import probe_latent_decode as latent_probe

    spec, slots, m, pt = latent_probe.cell_shapes()
    assert (spec.heads, spec.rank, spec.rope, slots, m, pt) \
        == (32, 256, 64, 20, 4160, 16)
    assert pd.latent_plane_shape(slots * m + 1, pt, 320) == (83201, 8, 640)
    assert _latent_selected() is None           # the CPU, no interpreter
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        t = _latent_selected()
        assert (t.heads, t.rows, t.width, t.rank, t.per, t.pr, t.exact) \
            == (32, 32, 320, 256, 2, 8, False)
        # steps of 2048 from a view of 8192 on, of 512 below
        assert t.block == pd.LATENT_STEP_TOKENS[8192] == 2048
        assert _latent_selected(pages=256).block == 512
        assert t.vmem < pd._VMEM_BUDGET
        assert _latent_selected(rows=2048, slots=1) is None
    # the walk's own plan for the same call is what it was
    assert attn.live_block_plan((20, 1, 32 * 320), (20, 4160), 16) \
        == (512, 16)


def test_latent_kernel_compiles_for_the_chip_at_the_cells_shapes(
        one_chip, monkeypatch):
    """The cell's absorbed decode row through ``latent_attend``, compiled by
    the chip's own compiler: Mosaic takes the kernel at the published
    widths over the plane as it is stored, and the program holds no loop,
    no copy of the pool and no array of a gathered block's shape."""
    import re

    import probe_latent_decode as latent_probe

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    spec, b, m, pt = latent_probe.cell_shapes()
    width = spec.rank + spec.rope
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    args = (sds((b, 1, spec.heads, spec.nope), jnp.bfloat16),
            sds((b, 1, spec.heads, spec.rope), jnp.bfloat16),
            sds(pd.latent_plane_shape(b * m + 1, pt, width), jnp.bfloat16),
            sds((b, m), jnp.int32), sds((b,), jnp.int32),
            sds((spec.heads * (spec.nope + spec.v), spec.rank),
                jnp.bfloat16))

    def attend(*a):
        return attn.latent_attend(*a, spec)

    text = _compiled_for_the_chip(attend, args).as_text()
    assert attn.DECODE_PATH["last"] == "absorbed-kernel"
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text
    assert not re.search(r"bf16\[83201,8,640\]\S* copy\(", text)
    assert not re.search(r"bf16\[\d+,512,320\]", text)


def test_latent_chunk_kernel_compiles_for_the_chip_at_the_cells_shapes(
        one_chip, monkeypatch):
    """The cell's prefill chunk (ONE slot's 2048 rows) through
    ``latent_attend``, compiled by the chip's own compiler: Mosaic takes the
    segments' kernel at the published widths, the loop is still there (a
    segment a step), and no array holds a block's float32 scores (results:
    tests/test_latent_chunk_kernel.py)."""
    import re

    import probe_latent_decode as latent_probe

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    spec, b, m, pt = latent_probe.cell_shapes()
    width = spec.rank + spec.rope
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    args = (sds((1, 2048, spec.heads, spec.nope), jnp.bfloat16),
            sds((1, 2048, spec.heads, spec.rope), jnp.bfloat16),
            sds(pd.latent_plane_shape(b * m + 1, pt, width), jnp.bfloat16),
            sds((1, m), jnp.int32), sds((1,), jnp.int32),
            sds((spec.heads * (spec.nope + spec.v), spec.rank),
                jnp.bfloat16))

    def attend(*a):
        return attn.latent_attend(*a, spec)

    compiled = _compiled_for_the_chip(attend, args)
    text = compiled.as_text()
    assert attn.DECODE_PATH["last"] == "expanded-kernel"
    t, _ = attn.latent_chunk_kernel_selected(
        (1, 2048, spec.heads * (spec.nope + spec.rope)), args[2], (1, m),
        spec)
    assert (t.hd, t.v, t.tile, t.block, t.segment) == \
        (128, 128, 256, 1024, 8192)
    assert text.count("tpu_custom_call") == 1 and " while(" in text
    assert not re.search(r"bf16\[83201,8,640\]\S* copy\(", text)
    assert not re.search(r"f32\[1,32,2048,\d+\]", text)      # the scores
    # a segment's keys and values, not a context's
    assert compiled.memory_analysis().temp_size_in_bytes < 300 << 20


# ---------------------------------------------------------------------------
# the delta rule's decode step (ops/pallas_delta.py; its results against the
# elementwise step are tests/test_delta_step_kernel.py's)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell,h,dk,dv,per_head", [
    ("solar2_serve_agent", 64, 128, 128, False),
    ("olmoh_serve_rollouts", 30, 96, 192, True)])
def test_delta_step_compiles_for_the_chip_at_the_cells_shapes(
        cell, h, dk, dv, per_head, one_chip):
    """A delta layer's 96 rows through ``delta_step``, the state donated,
    compiled by the chip's own compiler: Mosaic takes the kernel at the
    published widths with the rule's head block, the state's buffer is the
    result's, and nothing copies it."""
    import re

    from mxnet_tpu.ops import pallas_delta as pdl

    b = 96
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    args = (sds(b, h, dk), sds(b, h, dk), sds(b, h, dv),
            sds(b, h, 1 if per_head else dk), sds(b, h), sds(b, h, dk, dv),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip))
    assert pdl.supported(h, dk, dv)
    compiled = _compiled_for_the_chip(pdl.delta_step, args,
                                      donate_argnums=(5,))
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "delta_step" in text
    assert not re.search(r"f32\[%d,%d,%d,%d\]\S* copy\(" % (b, h, dk, dv),
                         text)
    # the donated state, as the chip stores it (whole (8, 128) tiles)
    assert compiled.memory_analysis().alias_size_in_bytes \
        == b * h * pdl._head_bytes(dk, dv)


@pytest.mark.parametrize("shape", [
    (96, 30, 96, 192), (192, 30, 96, 192), (64, 30, 96, 192),
    (96, 64, 128, 128), (128, 30, 96, 128),
    # another dim wastes less of its lane tiles than the values' 192
    (128, 30, 96, 192), (120, 30, 96, 192), (384, 30, 96, 192),
    (96, 128, 96, 192), (96, 30, 128, 192)], ids=lambda s: "x".join(
        map(str, s)))
def test_the_shape_rule_knows_how_the_chip_stores_a_state(shape, one_chip):
    """``pallas_delta.supported(..., rows=)`` against the compiler itself:
    the layout XLA:TPU gives a donated (rows, H, Dk, Dv) float32 operand of
    the elementwise step (which asks for none) is value dim minor exactly
    where the rule admits the kernel."""
    import re

    from mxnet_tpu.ops import kda
    from mxnet_tpu.ops import pallas_delta as pdl

    b, h, dk, dv = shape
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    text = _compiled_for_the_chip(
        kda._step, (sds(b, h, dk), sds(b, h, dk), sds(b, h, dv),
                    sds(b, h, 1), sds(b, h), sds(*shape)),
        donate_argnums=(5,)).as_text()
    stored, = set(re.findall(
        r"f32\[%d,%d,%d,%d\]\{([\d,]+):T\(8,128\)\} parameter\(" % shape,
        text[text.index("ENTRY"):]))
    assert pdl.supported(h, dk, dv)
    assert (stored == "3,2,1,0") == pdl.supported(h, dk, dv, rows=b), stored


@pytest.mark.parametrize("kind,heads", [("kda", (2, 128, 128)),
                                        ("gdn", (2, 96, 256))])
def test_the_decode_program_holds_one_delta_step_a_layer(kind, heads,
                                                         one_chip,
                                                         monkeypatch):
    """The paged decode program of a toy with three delta layers (Solar-
    Open2's keys, Olmo-Hybrid's; two heads of whole lane tiles),
    compiled for the chip with the rule's question about the backend
    answered for it: one ``delta_step`` custom call a delta layer, each
    matrix-state leaf read by that call alone and its buffer the call's
    result (and the program's: the leaf is donated), no copy of a leaf's
    shape.  1024 slots: a leaf the compiler can fit into the chip's 128 MiB
    of fast memory it moves there whole before the call, a toy's artefact."""
    import re

    import mxnet_tpu as mx
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.programs import spec as pspec
    from mxnet_tpu.test_utils import delta_toy_lm

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    slots = 1024
    pred = DecodePredictor(*delta_toy_lm(kind, *heads[1:]), cache_len=64,
                           ctx=mx.cpu(),
                           temperature=0.0, paged=True, page_tokens=4,
                           prefill_chunk=8)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        pred.serving_avals(slots, chunk_w=8)["decode"])
    with pspec.probing(pred):
        compiled = _compiled_for_the_chip(pred._paged_decode_impl, avals,
                                          donate_argnums=(1,))
    text = compiled.as_text()
    assert pred._delta_steps[1] == ["kernel"] * 3
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line
             and "delta_step" in line]
    assert len(calls) == 3
    leaf = r"f32\[%d,%d,%d,%d\]" % ((slots,) + heads)
    entry = text[text.index("ENTRY"):]
    leaves = re.findall(r"(%%\S+) = %s\S* parameter\(" % leaf, entry)
    assert len(leaves) == 3
    for name in leaves:
        readers = [line for line in entry.splitlines()
                   if re.search(r"[(, ]%s[,)]" % re.escape(name), line)]
        assert len(readers) == 1 and readers[0] in calls, readers
        # the state in is operand 3 of the call, its result 1
        assert "output_to_operand_aliasing={{1}: (3, {})}" in readers[0]
    assert not re.search(r"%s\S* copy\(" % leaf, text)
    state_bytes = 3 * slots * heads[0] * heads[1] * heads[2] * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes


# ---------------------------------------------------------------------------
# the KV layout knob (layout_probe.py --kv wiring)
# ---------------------------------------------------------------------------
def test_kv_layout_knob_applies_or_degrades():
    """MXNET_KV_LAYOUT requests a device layout at pool allocation;
    values round-trip regardless, and a backend that cannot honor the
    request degrades to native layout with a warning, not a failure."""
    buf = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    try:
        attn._KV_LAYOUT_WARNED["done"] = False
        with config.overrides(MXNET_KV_LAYOUT="2,1,0"):
            out = attn.apply_kv_layout(jnp.asarray(buf))
            np.testing.assert_array_equal(np.asarray(out), buf)
        # malformed spec: warn once, keep native layout
        attn._KV_LAYOUT_WARNED["done"] = False
        with config.overrides(MXNET_KV_LAYOUT="0,0,1"):
            with pytest.warns(UserWarning):
                out = attn.apply_kv_layout(jnp.asarray(buf))
            np.testing.assert_array_equal(np.asarray(out), buf)
    finally:
        attn._KV_LAYOUT_WARNED["done"] = False
