"""Paged attention over the blocks a slot has reached
(``ops.attention._attend_live_blocks`` behind ``paged_attend``).

The walk over live blocks against what it replaces, ``paged_gather`` +
``_sdpa_cache`` over the whole view: every live position is attended and
only the order of the softmax's sums differs, so the two agree within the
tolerance ``tests/test_pallas_decode.py`` sets for reordered sums (rtol
1e-4, atol 1e-5).  A view of one block, a node with a window and a sharded
pool trace what they traced before; one decode program serves every length;
and the server counts the blocks it attends from its own lengths.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, obs
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.models import attention_lm
from mxnet_tpu.ops import attention as attn

PT, PAGES, BLOCK = 4, 16, 16          # a view of 64 positions in 4 blocks
CAP = PT * PAGES
TOL = dict(rtol=1e-4, atol=1e-5)

# heads, kv heads, key head width, value head width, sink, value scale
LAYOUTS = {
    "mha": (4, 4, 8, 8, False, 1.0),
    "grouped": (8, 2, 8, 8, False, 1.0),
    "sink_unequal_kv": (8, 2, 12, 8, True, 0.707),
}
# an empty (inactive) slot, one token, a block's edge, one past it, a view
# exactly full, a wrapped ring, and two lengths inside a block
RAGGED = [0, 1, BLOCK, BLOCK + 1, CAP, CAP + 9, 40, 33]


def _pools(rng, pages, kvh, hd, hdv, kv_dtype, pt=PT):
    k, v = (jnp.asarray(rng.normal(size=(pages, pt, kvh * width)),
                        jnp.float32) for width in (hd, hdv))
    return attn.quantize_pools(k, v, kv_dtype, kvh) if kv_dtype else (k, v)


def _whole_view(q, kp, vp, table, total, heads, kvh, sink, value_scale):
    return attn._sdpa_cache(
        q, *attn.paged_gather_kv(kp, vp, table),
        total, heads, None, num_kv_heads=kvh,
        **attn._extras(0, sink, value_scale, "attn"))


@pytest.mark.parametrize("query", ["row", "verify", "chunk"])
@pytest.mark.parametrize("kv_dtype", ["int8", ""], ids=["int8", "float"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_live_blocks_match_the_whole_view(layout, kv_dtype, query):
    heads, kvh, hd, hdv, has_sink, value_scale = LAYOUTS[layout]
    rng = np.random.RandomState(7)
    sink = jnp.asarray(rng.normal(size=(heads,)), jnp.float32) \
        if has_sink else None
    if query == "chunk":
        # one slot, a chunk of 8 at position 21 of which 5 rows are real:
        # the pad rows' keys never reach the pool
        b, tq, pos0, nvalid, group = 1, 8, 21, 5, 3
        total = [pos0 + tq]
    else:
        b, tq, group = len(RAGGED), (1 if query == "row" else 4), 3
        # a multi-row query has its own rows behind it and has not wrapped
        total = RAGGED if tq == 1 else [min(max(n, tq), CAP) for n in RAGGED]
    kp, vp = _pools(rng, 1 + b * PAGES, kvh, hd, hdv, kv_dtype)
    table = jnp.asarray(
        1 + rng.permutation(b * PAGES).reshape(b, PAGES), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, tq, heads * hd)), jnp.float32)
    if query == "chunk":
        new = [jnp.asarray(rng.normal(size=(b, tq, kvh * w)), jnp.float32)
               for w in (hd, hdv)]
        kp, vp = attn.paged_append_kv(kp, vp, table, *new, pos0,
                                      num_heads=kvh,
                                      valid=jnp.asarray([nvalid]))
    total = jnp.asarray(total, jnp.int32)
    want = _whole_view(q, kp, vp, table, total, heads, kvh, sink,
                       value_scale)
    got = jax.jit(lambda *a: attn._attend_live_blocks(
        *a, heads, None, kvh, BLOCK, group, sink=sink,
        value_scale=value_scale))(q, kp, vp, table, total)
    assert got.shape == want.shape and got.dtype == want.dtype
    rows = slice(0, nvalid) if query == "chunk" else slice(None)
    live = np.asarray(total) > 0      # an empty slot's answer is never read
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live, rows],
                               np.asarray(want)[live, rows], **TOL)


def test_a_last_block_that_is_not_whole_reads_the_scratch_page():
    """A table of 18 pages in blocks of 4 pages: the fifth block holds two
    pages, and the walk pads it with the scratch page above the capacity."""
    rng = np.random.RandomState(8)
    pages, b = 18, 3
    kp, vp = _pools(rng, 1 + b * pages, 4, 8, 8, "int8")
    table = jnp.asarray(
        1 + rng.permutation(b * pages).reshape(b, pages), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, 32)), jnp.float32)
    total = jnp.asarray([70, 72, 100], jnp.int32)
    want = _whole_view(q, kp, vp, table, total, 4, 4, None, 1.0)
    got = attn._attend_live_blocks(q, kp, vp, table, total, 4, None, 4,
                                   BLOCK, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def _attend_jaxpr(cap, **kw):
    b, heads, hd = 2, 4, 8
    kp, vp = _pools(np.random.RandomState(0), 1 + b * cap // 16, heads, hd,
                    hd, "int8", pt=16)
    table = jnp.ones((b, cap // 16), jnp.int32)
    q = jnp.ones((b, 1, heads * hd), jnp.float32)
    total = jnp.asarray([3, cap], jnp.int32)
    paged = jax.make_jaxpr(lambda *a: attn.paged_attend(
        *a, num_heads=heads, **kw))(q, kp, vp, table, total)
    extra = attn._extras(kw.get("window", 0), None, 1.0, "attn")
    whole = jax.make_jaxpr(lambda q, kp, vp, table, total: attn._sdpa_cache(
        q, *attn.paged_gather_kv(kp, vp, table), total,
        heads, None, num_kv_heads=0,
        mesh_active=kw.get("mesh_active", False), **extra))(
            q, kp, vp, table, total)
    return str(paged), str(whole)


@pytest.mark.parametrize("case,cap,kw,walks", [
    ("one_block", 256, {}, False),
    ("two_blocks", 512, {}, True),
    ("window", 512, {"window": 128}, False),
    ("mesh", 512, {"mesh_active": True}, False),
])
def test_what_paged_attend_traces(case, cap, kw, walks):
    """A view of one block, a window node and a sharded pool are gathered
    whole and attended by the jaxpr they were attended by before the walk;
    a longer view is walked by a loop whose trip count is data."""
    paged, whole = _attend_jaxpr(cap, **kw)
    assert ("while" in paged) == walks
    assert (paged == whole) == (not walks)
    assert (attn.live_block_plan((2, 1), (2, cap // 16), 16, **kw)
            is not None) == walks


def test_the_plan_follows_the_calls_shapes():
    plan = attn.live_block_plan
    # the two serving cells' decode steps and prefill chunks
    assert plan((32, 1), (32, 128), 16) == (256, 16)
    assert plan((64, 1), (64, 576), 16) == (512, 16)
    assert plan((1, 256), (1, 128), 16) == (256, 1)
    assert plan((1, 512), (1, 576), 16) == (512, 1)
    # the verify window: eight blocks a step, as the slots' rows allow
    assert plan((32, 9), (32, 128), 16) == (256, 16)
    # a block is whole pages, or the view is gathered whole
    assert plan((4, 1), (4, 100), 48) is None


def _tiny_lm(seq_len):
    sym = attention_lm.get_symbol(17, seq_len, num_layers=2, embed=8, heads=2,
                                  ffn_hidden=16)
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(1, seq_len),
                                   softmax_label=(1, seq_len))
    return sym, {n: rng.normal(0, 0.5, s).astype(np.float32)
                 for n, s in zip(sym.list_arguments(), shapes)
                 if n not in ("data", "softmax_label")}


def _attn_blocks():
    fam = obs.registry.snapshot().get("mx_attn_blocks_total", {})
    return {r["labels"]["kind"]: r["value"] for r in fam.get("series", ())}


def test_one_decode_program_serves_every_length_and_counts_its_blocks():
    """Slots at 10 and at 700 positions of a 1024-position view ride one
    traced decode step and one traced chunk, deliver what a dense ring of
    the same capacity generates, and the server's counter follows their
    lengths: one block of four for the short slot, three for the long."""
    cap, block = 1024, 256
    sym, params = _tiny_lm(cap)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 17, (n,)) for n in (10, 700)]
    dense = DecodePredictor(sym, params, cache_len=cap, kv_dtype="int8")
    paged = DecodePredictor(sym, params, cache_len=cap, kv_dtype="int8",
                            paged=True, page_tokens=16, prefill_chunk=64)
    assert paged.attn_walk(2) == [(cap, block)] * 2
    server = DecodeServer(paged, max_prefill=cap, slots=2, max_new_tokens=6)
    before, seen = _attn_blocks(), len(obs.timeline.events())
    ids = [server.submit(p) for p in prompts]
    results = server.run()
    for rid, p in zip(ids, prompts):
        want = dense.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=6, seed=0)[0]
        np.testing.assert_array_equal(results[rid], want)
    assert paged.trace_counts["decode"] == 1
    assert paged.trace_counts["chunk"] == 1
    ticks = [e["args"] for e in obs.timeline.events()[seen:]
             if e["name"] == "serve.readback" and e.get("args")]
    assert ticks and all(0 < a["attn_blocks_live"] <= a["attn_blocks_view"]
                         for a in ticks)
    # 2 nodes x 2 slots x 4 blocks a tick; while both decode, the short
    # slot reaches one block and the long one three
    assert {a["attn_blocks_view"] for a in ticks} == {2 * 2 * 4}
    assert 2 * (1 + 3) in {a["attn_blocks_live"] for a in ticks}
    after = _attn_blocks()
    assert after["live"] - before.get("live", 0) \
        == sum(a["attn_blocks_live"] for a in ticks)
    assert after["view"] - before.get("view", 0) \
        == sum(a["attn_blocks_view"] for a in ticks)


def test_a_table_of_one_block_counts_as_attended_whole():
    sym, params = _tiny_lm(32)
    paged = DecodePredictor(sym, params, cache_len=32, paged=True,
                            page_tokens=4)
    assert paged.attn_walk(3) == [(32, 32)] * 2
    server = DecodeServer(paged, max_prefill=16, slots=3, max_new_tokens=3)
    seen = len(obs.timeline.events())
    server.submit(np.arange(5))
    server.run()
    ticks = [e["args"] for e in obs.timeline.events()[seen:]
             if e["name"] == "serve.readback" and e.get("args")]
    assert ticks and all(a["attn_blocks_live"] == a["attn_blocks_view"]
                         == 2 * 3 for a in ticks)


# ---------------------------------------------------------------------------
# the type the output leaves in: over a quantized pool the queries' (the
# stream's) where a slot brings more than one row and float32 where it brings
# one; the pool's over a float one; on every path
# ---------------------------------------------------------------------------
KPT, KM = 16, 40                # the kernels' view: 640 positions, 2.5 blocks
SPARSE = attn.SparseSpec(topk=2, block=64, kernel=32, stride=KPT,
                         init_blocks=1, window=64, dense_len=128)
# path -> (slots, query rows, pages a slot, keywords, Pallas shown,
#          the path counted)
OUT_PATHS = {
    "walk": (3, 1, KM, {}, False, "walk"),
    "walk_chunk": (1, 32, KM, {}, False, "walk"),
    "walk_verify": (3, 4, KM, {}, False, "walk"),
    "decode-kernel": (3, 1, KM, {}, True, "decode-kernel"),
    "chunk-kernel": (1, 32, KM, {}, True, "chunk-kernel"),
    "whole": (3, 1, 16, {}, False, "whole"),
    "whole_verify": (3, 4, 16, {}, False, "whole"),
    "window_ring": (3, 1, KM, {"window": 128}, False, "whole"),
    "window_ring_chunk": (1, 32, KM, {"window": 128}, False, "whole"),
    "sink_and_value_scale": (
        3, 1, KM, {"value_scale": 0.5, "sink": np.zeros((4,), np.float32)},
        False, "walk"),
    "sparse_row": (3, 1, KM, {"sparse": True}, False, None),
    "sparse_walk": (1, 32, KM, {"sparse": True}, False, "walk"),
    "sparse_chunk-kernel": (1, 32, KM, {"sparse": True}, True,
                            "chunk-kernel"),
    "sparse_whole": (1, 8, 16, {"sparse": True}, False, "whole"),
}


def _out_aval(path, pool, qdtype, monkeypatch):
    slots, tq, pages, kw, pallas, counted = OUT_PATHS[path]
    kw = dict(kw)
    heads, hd = 4, 128      # a page's scale row fills a lane tile
    sds = jax.ShapeDtypeStruct
    plane = sds((1 + slots * pages, KPT, heads * hd),
                jnp.int8 if pool == "int8" else jnp.dtype(pool))
    if pool == "int8":
        kp = attn.QuantKV(plane, sds((plane.shape[0], KPT * 2 * heads),
                                     jnp.float32))
        vp = attn.QuantKV(plane, None)
    else:
        kp = vp = plane
    q = sds((slots, tq, heads * hd), qdtype)
    table = sds((slots, pages), jnp.int32)
    total = sds((slots,), jnp.int32)
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 32)
    attn.DECODE_PATH["last"] = None
    with config.overrides(MXNET_PALLAS_INTERPRET="1" if pallas else "0"):
        if kw.pop("sparse", False):
            index = sds((plane.shape[0], heads * hd), jnp.bfloat16)
            out = jax.eval_shape(
                lambda q, kp, vp, index, table, total:
                attn.paged_attend_sparse(q, kp, vp, index, table, total,
                                         SPARSE, num_heads=heads)[0],
                q, kp, vp, index, table, total)
        else:
            out = jax.eval_shape(
                lambda q, kp, vp, table, total: attn.paged_attend(
                    q, kp, vp, table, total, num_heads=heads, **kw),
                q, kp, vp, table, total)
    assert attn.DECODE_PATH["last"] == counted, path
    assert out.shape == q.shape
    return out


@pytest.mark.parametrize("qdtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("path", sorted(OUT_PATHS))
def test_over_int8_pages_the_output_leaves_in_the_queries_type(
        path, qdtype, monkeypatch):
    """A quantized pool has no float type to hand back, so every path
    returns rows of many positions (a chunk, a verify window) in the type
    of the queries it was given: a bfloat16 stream stays bfloat16 through
    the attention, and float32 queries get what they got.  One row a slot
    (the decode step) leaves in the float32 of its sums, whatever the
    queries' type (``_out_dtype`` says why)."""
    rows = OUT_PATHS[path][1]
    assert _out_aval(path, "int8", qdtype, monkeypatch).dtype \
        == (qdtype if rows > 1 else "float32")


@pytest.mark.parametrize("path", ["walk", "chunk-kernel", "whole",
                                  "window_ring", "decode-kernel"])
def test_over_float_pages_the_output_leaves_in_the_pools_type(
        path, monkeypatch):
    assert _out_aval(path, "bfloat16", "bfloat16", monkeypatch).dtype \
        == jnp.bfloat16
    if path in ("walk", "whole", "window_ring"):
        # the einsum paths take float32 queries over a bfloat16 pool too
        assert _out_aval(path, "bfloat16", "float32", monkeypatch).dtype \
            == jnp.bfloat16


@pytest.mark.parametrize("ring", ["dense_ring", "paged_walk", "paged_whole"])
def test_the_sums_inside_are_float32_and_the_cast_comes_last(ring):
    """A verify window of bfloat16 queries over int8 keys and values: the
    answer is the one float32 queries of the same values get (the planes
    are exact in bfloat16, the sums float32 on both sides), rounded once."""
    rng = np.random.RandomState(11)
    heads, hd, b = 4, 8, 3
    kp, vp = _pools(rng, 1 + b * PAGES, heads, hd, hd, "int8")
    table = jnp.asarray(
        1 + rng.permutation(b * PAGES).reshape(b, PAGES), jnp.int32)
    total = jnp.asarray([5, CAP - 3, CAP], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 4, heads * hd)), jnp.bfloat16)
    if ring == "dense_ring":
        kc, vc = attn.paged_gather_kv(kp, vp, table)
        call = lambda q: attn.cache_attend(q, kc, vc, total, num_heads=heads)
    elif ring == "paged_whole":
        call = lambda q: attn.paged_attend(q, kp, vp, table, total,
                                           num_heads=heads)
    else:
        call = lambda q: attn._attend_live_blocks(
            q, kp, vp, table, total, heads, None, heads, BLOCK, 3)
    got, want = call(q), call(q.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16 and want.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want.astype(jnp.bfloat16), np.float32),
        rtol=2e-2, atol=2e-2)
