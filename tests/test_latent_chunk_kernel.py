"""The expanded latent chunk's Pallas kernel
(``ops.pallas_decode.attend_latent_segment`` as the step of
``ops.attention._attend_latent_segments``), the rule that chooses it
(``ops.attention.latent_chunk_kernel_selected``) and the wiring that reports
it.

All in interpret mode on the CPU harness (the same kernel Mosaic compiles on a
TPU), at toy widths that tile: 2 heads of 64 + 64 key columns and 128 value
columns over a rank of 128, so a plane of ``(P, 8, 384)``, two positions a
row.  Chunks of 256 rows under a threshold moved down to them, blocks of 128
and segments of 512 where a case wants several of each cheaply, and the
constants as they stand (tiles of 256 rows, blocks of 1024) at a chunk of
1024 rows.

* the kernel against the walk (``_attend_live_blocks``' running row over the
  same pages) and against ``latent_mix``'s self-attended form (which reads no
  page at all): a chunk that starts at 0, at a block's edge and inside a
  block; a last block that is not whole (the scratch page past the table's
  end); a context shorter than one segment and one that ends mid-segment; the
  causal limit crossing a tile of rows; a float32 plane and a bfloat16 one;
* two planted faults the benchmark's comparison cannot see at its size: a
  tail of positions dropped at a segment's edge, and the odd positions of a
  row read as the even ones;
* the rule's refusals and the ``mx_attn_latent_dispatch_total{form}`` each
  call leaves;
* pins that ``paged_attend``'s and ``paged_attend_sparse``'s chunks trace to
  the jaxprs they had before this kernel was there;
* a paged server whose chunk program takes the kernel, against the reference.
"""
import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import config, obs
from mxnet_tpu.ops import attention as attn
from mxnet_tpu.ops import pallas_decode as pd

PT = 16
HEADS, NOPE, ROPE, V, RANK = 2, 64, 64, 128, 128
WIDTH = RANK + ROPE
ATTRS = dict(num_heads=HEADS, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
             v_head_dim=V, kv_lora_rank=RANK)
SPEC = attn.latent_spec(ATTRS)
# float32 through the interpreter is float32 products on both sides
# (reordered sums); a bfloat16 plane rounds the probabilities to bfloat16
# about different maxima (the walk's is a block's, the kernel's the running
# one) and the walk rounds its logits where the kernel does not
TOL = {jnp.float32: dict(rtol=1e-4, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.fixture
def small(monkeypatch):
    """A backend that runs Pallas through the interpreter, a rule that takes
    chunks of 256 rows for chunks, and a kernel of two tiles of rows, blocks
    of 128 and segments of 512."""
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 256)
    monkeypatch.setattr(pd, "LATENT_CHUNK_TILE_ROWS", (128,))
    monkeypatch.setattr(pd, "LATENT_CHUNK_BLOCK", 128)
    monkeypatch.setattr(pd, "LATENT_CHUNK_SEGMENT", 512)
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield


def _streams(t, dtype, seed=0, pages=80):
    """The projected streams of ``t`` positions, ``W_kvb``, an empty plane of
    ``pages`` pages whose scratch page holds large values, and a table over
    the others in a drawn order."""
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q = draw(1, t, HEADS * (NOPE + ROPE)).astype(dtype)
    c, kr = draw(1, t, RANK).astype(dtype), draw(1, t, ROPE).astype(dtype)
    w = (0.1 * draw(HEADS * (NOPE + V), RANK)).astype(dtype)
    plane = jnp.zeros(pd.latent_plane_shape(1 + pages, PT, WIDTH), dtype) \
        .at[0].set(300.0)
    table = jnp.asarray(1 + rng.permutation(pages)[None], jnp.int32)
    return q, c, kr, w, plane, table


@functools.lru_cache(maxsize=2)
def _prefilled(start, t, dtype, seed, pages):
    """The streams of ``start + t`` positions, the plane after ``start`` of
    them were appended by the walk, and the self-attended form's rows of the
    last ``t``."""
    q, c, kr, w, plane, table = _streams(start + t, dtype, seed, pages)
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        if start:
            _, plane = attn.latent_mix(
                ATTRS, q[:, :start], c[:, :start], kr[:, :start], w,
                cache=plane, table=table, pos0=jnp.zeros((1,), jnp.int32))
        whole, _ = attn.latent_mix(ATTRS, q, c, kr, w)
    return (q[:, start:], c[:, start:], kr[:, start:], w, plane, table,
            np.asarray(whole[:, start:], np.float32))


def _chunk(start, t, dtype=jnp.float32, seed=0, pages=80, path="kernel"):
    """``(out of the chunk through the pool, the self-attended form's rows of
    it)``: ``start`` positions appended by the walk, then ``t`` rows through
    ``latent_mix`` on ``path``."""
    q, c, kr, w, plane, table, whole = _prefilled(start, t, dtype, seed,
                                                  pages)
    with config.overrides(MXNET_PALLAS_INTERPRET=str(int(path == "kernel"))):
        out, _ = attn.latent_mix(ATTRS, q, c, kr, w, cache=plane, table=table,
                                 pos0=jnp.full((1,), start, jnp.int32))
    assert attn.DECODE_PATH["last"] == \
        ("expanded-kernel" if path == "kernel" else "expanded")
    return np.asarray(out, np.float32), whole


# (the chunk's first position, pages of the table): blocks of 128, segments
# of 512, a chunk of 256 rows in two tiles
CHUNKS = {
    "starts_at_0": (0, 80),                 # its rows see only each other
    "starts_at_a_blocks_edge": (384, 80),
    "starts_inside_a_block": (200, 80),
    # shorter than one segment: 100 + 256 positions
    "a_context_shorter_than_a_segment": (100, 80),
    # three segments, the last ends inside its second block
    "ends_mid_segment": (900, 80),
    # ends on a segment's edge: 768 + 256 = 1024
    "ends_on_a_segments_edge": (768, 80),
    # a table of 74 pages is 1184 positions: 9.25 blocks, and the last
    # segment's pages past the table's end are the scratch page
    "a_last_block_that_is_not_whole": (928, 74),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", sorted(CHUNKS))
def test_kernel_parity_with_the_walk_and_the_self_attended_form(chunk, dtype,
                                                                small):
    start, pages = CHUNKS[chunk]
    got, whole = _chunk(start, 256, dtype, seed=len(chunk), pages=pages)
    walk, _ = _chunk(start, 256, dtype, seed=len(chunk), pages=pages,
                     path="walk")
    assert got.shape == (1, 256, HEADS * V) and np.all(np.isfinite(got))
    np.testing.assert_allclose(got, walk, **TOL[dtype])
    np.testing.assert_allclose(got, whole, **TOL[dtype])


def test_the_causal_limit_crosses_a_tile_of_rows(small):
    """Every row of both tiles against a dense softmax over exactly the
    positions under its own limit: the first tile's rows end inside the block
    the second tile's begin in."""
    start, t = 200, 256
    q, c, kr, w, plane, table = _streams(start + t, jnp.float32, seed=3)
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        _, rows = attn.latent_mix(ATTRS, q, c, kr, w)
    got, _ = _chunk(start, t, seed=3)
    k, v = attn.latent_expand(rows, w, SPEC)
    k = np.asarray(k, np.float64).reshape(start + t, HEADS, NOPE + ROPE)
    v = np.asarray(v, np.float64).reshape(start + t, HEADS, V)
    # the queries as ``latent_mix`` hands them on: rotated
    qh = q.reshape(1, start + t, HEADS, NOPE + ROPE)
    pos = jnp.arange(start + t, dtype=jnp.int32)[None]
    q_rope = attn.latent_rotate(qh[..., NOPE:].reshape(1, start + t, -1), pos,
                                HEADS, SPEC).reshape(1, -1, HEADS, ROPE)
    qs = np.concatenate([np.asarray(qh[..., :NOPE], np.float64),
                         np.asarray(q_rope, np.float64)], axis=-1)[0]
    for row in (0, 55, 127, 128, 129, 255):         # both tiles, their edges
        p = start + row
        logits = np.einsum("hd,khd->hk", qs[p], k[:p + 1]) * SPEC.scale
        prob = np.exp(logits - logits.max(axis=1, keepdims=True))
        want = np.einsum("hk,khe->he", prob / prob.sum(axis=1, keepdims=True),
                         v[:p + 1]).reshape(-1)
        np.testing.assert_allclose(got[0, row], want, rtol=1e-4, atol=2e-5)


def test_a_ring_that_has_wrapped_attends_the_whole_view(small):
    """``total`` past the capacity: every position of the view is under every
    row's limit, and the scratch page past the table's end under none."""
    pages = 74
    rng = np.random.default_rng(5)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    plane = draw(*pd.latent_plane_shape(1 + pages, PT, WIDTH)).at[0].set(300.)
    args = (draw(1, 256, HEADS, NOPE), draw(1, 256, HEADS, ROPE), plane,
            jnp.asarray(1 + rng.permutation(pages)[None], jnp.int32),
            jnp.asarray([pages * PT + 40], jnp.int32),
            0.1 * draw(HEADS * (NOPE + V), RANK), SPEC)
    got = attn.latent_attend(*args)
    assert attn.DECODE_PATH["last"] == "expanded-kernel"
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        walk = attn.latent_attend(*args)
    np.testing.assert_allclose(got, walk, **TOL[jnp.float32])


@pytest.mark.parametrize("start", [0, 1500])
def test_the_constants_as_they_stand(start):
    """A chunk of 1024 rows by tiles of 256 and blocks of 1024 over a table of
    2720 positions, which is the segment then (whole blocks of it)."""
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        t, _ = attn.latent_chunk_kernel_selected(
            (1, 1024, HEADS * (NOPE + ROPE)),
            jnp.zeros(pd.latent_plane_shape(171, PT, WIDTH), jnp.float32),
            (1, 170), SPEC)
        assert (t.tile, t.block, t.segment, t.exact) == (256, 1024, 3072, True)
        got, whole = _chunk(start, 1024, pages=170)
    np.testing.assert_allclose(got, whole, **TOL[jnp.float32])


# ---------------------------------------------------------------------------
# planted faults: each must fail the comparison the tests above make
# ---------------------------------------------------------------------------
def test_a_tail_dropped_at_a_segments_edge_fails(small, monkeypatch):
    """A segment's last live block left out (one block of 128 of 1156
    positions): the rows that see it no longer agree with the self-attended
    form."""
    live = pd._segment_live_blocks
    monkeypatch.setattr(pd, "_segment_live_blocks", lambda at, t, cap:
                        jnp.maximum(live(at, t, cap) - 1, 1))
    # (a table no other test has: the kernel is traced anew, fault and all)
    got, whole = _chunk(900, 256, seed=11, pages=81)
    assert np.all(np.isfinite(got))
    assert np.abs(got - whole).max() > 50 * TOL[jnp.float32]["atol"]


def test_odd_positions_read_as_the_even_ones_fails(small, monkeypatch):
    """A row of the plane holds two positions: re-laid out so that the second
    is the first again, the chunk no longer agrees with the form that reads no
    page."""
    pages_of = attn.latent_pages

    def twice(plane, ids, width):
        rows = pages_of(plane, ids, width)
        return rows.at[:, 1::2].set(rows[:, 0::2])

    monkeypatch.setattr(attn, "latent_pages", twice)
    got, whole = _chunk(900, 256, seed=12)
    assert np.all(np.isfinite(got))
    assert np.abs(got - whole).max() > 50 * TOL[jnp.float32]["atol"]


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
def _selected(rows=1024, slots=1, pages=170, spec=SPEC, paged=True,
              mesh_active=False, dtype=jnp.bfloat16, width=None):
    width = width or spec.rank + spec.rope
    plane = jnp.zeros(pd.latent_plane_shape(1 + slots * pages, PT, width),
                      dtype)
    return attn.latent_chunk_kernel_selected(
        (slots, rows, spec.heads * (spec.nope + spec.rope)), plane,
        (slots, pages) if paged else None, spec, mesh_active=mesh_active)[0]


@pytest.mark.parametrize("why,kw", [
    ("95 rows: the absorbed form", dict(rows=95)),
    ("512 rows: fewer than the rule's constant", dict(rows=512)),
    ("two slots", dict(slots=2)),
    ("a dense ring", dict(paged=False)),
    ("a mesh", dict(mesh_active=True)),
    ("a view of one block", dict(pages=16)),
    ("key columns of 128 + 64 a head: no whole lane tiles",
     dict(spec=attn.latent_spec(dict(ATTRS, qk_nope_head_dim=128)))),
    ("value columns of 192", dict(
        spec=attn.latent_spec(dict(ATTRS, v_head_dim=192)))),
    ("rows that no tile divides", dict(rows=1100)),
])
def test_rule_refuses(why, kw):
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        assert _selected() is not None
        assert _selected(dtype=jnp.float32).exact
        assert _selected(**kw) is None, why
    assert _selected() is None              # the CPU, no interpreter


def test_the_cells_chunk_tiles_within_fast_memory():
    """Mistral-Small-4's chunk: 2048 rows of 32 heads over a table of 66,560
    positions takes the first tile listed and segments of 8192."""
    t = pd.latent_chunk_tiles(2048, 32, 64 + 64, 128, jnp.bfloat16, PT, 66560)
    assert (t.tile, t.block, t.segment, t.exact) == \
        (pd.LATENT_CHUNK_TILE_ROWS[0], pd.LATENT_CHUNK_BLOCK,
         pd.LATENT_CHUNK_SEGMENT, False)
    assert t.vmem <= pd._VMEM_BUDGET


def _forms():
    counter = obs.registry.counter("mx_attn_latent_dispatch_total",
                                   labels=("form",))
    return {f: counter.labels(form=f).get() for f in
            ("expanded", "expanded-kernel", "absorbed", "absorbed-kernel")}


@pytest.mark.parametrize("rows,slots,form", [
    (256, 1, "expanded-kernel"), (128, 1, "expanded"), (256, 2, "expanded"),
    (1, 3, "absorbed-kernel"), (40, 1, "absorbed")])
def test_each_call_counts_under_the_form_it_took(rows, slots, form, small):
    rng = np.random.default_rng(2)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pages = 80
    before = _forms()
    text = str(jax.make_jaxpr(lambda *a: attn.latent_attend(*a, SPEC))(
        draw(slots, rows, HEADS, NOPE), draw(slots, rows, HEADS, ROPE),
        draw(*pd.latent_plane_shape(1 + slots * pages, PT, WIDTH)),
        jnp.asarray(1 + np.arange(slots * pages).reshape(slots, pages),
                    jnp.int32),
        jnp.full((slots,), 700, jnp.int32),
        0.1 * draw(HEADS * (NOPE + V), RANK)))
    after = _forms()
    assert {f: after[f] - before[f] for f in after if after[f] != before[f]} \
        == {form: 1}
    assert attn.DECODE_PATH["last"] == form
    assert ("latent_chunk_segment" in text) == (form == "expanded-kernel")
    # the rows' label keeps the form, not the path
    assert attn.latent_form(rows) == form.split("-")[0]


# ---------------------------------------------------------------------------
# every other chunk traces as it did
# ---------------------------------------------------------------------------
def _digest(fn, *args):
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()) \
        .hexdigest()[:16]


def _pools(kvh=2, hd=128, pages=40):
    rng = np.random.RandomState(0)
    k, v = (jnp.asarray(rng.randn(1 + pages, PT, kvh * hd).astype(np.float32))
            for _ in range(2))
    return attn.quantize_pools(k, v, "int8", kvh)


# the digests of the tree before this kernel (commit 8e336cb, PR 61), by
# this file's own ``_digest`` there
PARENTS = {
    "paged_attend": {"walk": "2331f0ec7c5d13a3",
                     "chunk-kernel": "00614f7a4a6ecb64"},
    "paged_attend_sparse": {"walk": "464990b06e0d7920",
                            "chunk-kernel": "4a891c2fb9646101"},
}


@pytest.mark.parametrize("path", ["walk", "chunk-kernel"])
def test_paged_attends_chunk_is_the_parents(path, monkeypatch):
    """One slot's chunk of 64 rows of 2 heads of 128 over int8 pools of 640
    positions, by the walk and by the chunk's kernel."""
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 32)
    kp, vp = _pools()
    with config.overrides(MXNET_PALLAS_INTERPRET=str(int(path != "walk"))):
        got = _digest(
            lambda q, table, total: attn.paged_attend(
                q, kp, vp, table, total, num_heads=2, num_kv_heads=2),
            jnp.zeros((1, 64, 256), jnp.float32),
            jnp.arange(1, 41, dtype=jnp.int32)[None],
            jnp.asarray([300], jnp.int32))
    assert attn.DECODE_PATH["last"] == path
    assert got == PARENTS["paged_attend"][path]


@pytest.mark.parametrize("path", ["walk", "chunk-kernel"])
def test_paged_attend_sparses_chunk_is_the_parents(path, monkeypatch):
    """The same chunk under a selection of 3 of its blocks of 64."""
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 32)
    kp, vp = _pools()
    spec = attn.SparseSpec(topk=3, block=64, kernel=32, stride=16,
                           init_blocks=1, window=64, dense_len=300)
    index = jnp.zeros((41, 2 * 128), jnp.float32)
    with config.overrides(MXNET_PALLAS_INTERPRET=str(int(path != "walk"))):
        got = _digest(
            lambda q, table, total: attn.paged_attend_sparse(
                q, kp, vp, index, table, total, spec, num_heads=2,
                num_kv_heads=2)[0],
            jnp.zeros((1, 64, 256), jnp.float32),
            jnp.arange(1, 41, dtype=jnp.int32)[None],
            jnp.asarray([300], jnp.int32))
    assert attn.DECODE_PATH["last"] == path
    assert got == PARENTS["paged_attend_sparse"][path]


# ---------------------------------------------------------------------------
# a toy Mistral-Small-4 whose widths tile, served in chunks of 256
# ---------------------------------------------------------------------------
def test_served_chunks_take_the_kernel_and_say_so(small):
    """The toy's prompt of 560 goes through the chunk program in three
    chunks over a pool of 1024 positions (segments of 512: one, two and two
    and a bit), then 39 decode rows: the probabilities are the plain
    reference's, the chunk program's record says ``expanded-kernel`` and the
    decode program's ``absorbed-kernel``, and no call took the walk."""
    import test_latent_attention as toy
    from chipbench import correct, harness

    cfg, sym, params, toks, want = toy._toy(
        num_attention_heads=2, kv_lora_rank=RANK, qk_rope_head_dim=ROPE,
        qk_nope_head_dim=NOPE, qk_head_dim=NOPE + ROPE, head_dim=NOPE + ROPE,
        v_head_dim=V)
    before = harness.program_counters()
    pred = toy.predictor(sym, params, chunk=256)
    got = toy.served(pred, toks)
    check = correct.compare_logp(got, want[toy.PROMPT - 1:toy.T - 1],
                                 toy.ATOL)
    assert check["ok"], check
    took = harness.program_counters(since=before)
    assert {k.split("form=")[1].rstrip("}") for k in took
            if k.startswith("mx_attn_latent_dispatch_total")} \
        == {"expanded-kernel", "absorbed-kernel"}
    assert pred._decode_paths[256] == {"expanded-kernel"}
    assert pred._decode_paths[1] == {"absorbed-kernel"}
