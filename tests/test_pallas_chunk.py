"""A prefill chunk's Pallas kernel over paged KV pools
(``ops.pallas_decode.attend_chunk_blocks``), the rule that chooses it
(``ops.attention.chunk_kernel_selected``) and the wiring that reports it.

All in interpret mode on the CPU harness (the same kernel Mosaic compiles on
a TPU; its compiles at the serving cells' shapes for a described v5e are in
``tests/test_pallas_decode.py``, beside the decode row's, one process a
described chip):

* the kernel against the walk (``_attend_live_blocks``'s loop over the same
  list of blocks) over int8 and bfloat16 pools, MHA and GQA 16 : 1, a
  context that ends inside a block, the first chunk of a prompt, a sink and
  a value scale through ``_combine_blocks``;
* a selection laid over the walk (``chosen``): drawn masks, and
  ``paged_attend_sparse``'s own with rows on both sides of ``dense_len``
  and rows whose chosen set leaves a whole block of the walk empty;
* the rule's refusals and the ``mx_attn_dispatch_total{path}`` each leaves;
* a paged server whose chunk program takes the kernel, token for token
  against the walk's, and what the program's meta says.

Tolerance: float32 queries through the interpreter are float32 products on
both sides, held to rtol 1e-4 / atol 1e-5 as the decode row's tests hold
theirs (reordered float32 sums); bfloat16 queries round the probabilities
to bfloat16 on both sides, about different maxima (the walk's is a block's,
the kernel's the running one), held to the bfloat16 pools' 2e-2.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import config, obs
from mxnet_tpu.ops import attention as attn
from mxnet_tpu.ops import pallas_decode as pd

RTOL, ATOL = 1e-4, 1e-5
PT, M = 16, 40                  # a view of 640 positions: 2.5 blocks of 256
# (H, H_kv, head width of keys, of values)
NODES = {
    "mha": (2, 2, 128, 128),
    "gqa_16_to_1": (32, 2, 128, 128),       # minicpm-sala's sparse layers
    "values_of_256": (4, 1, 128, 256),
    "four_kv_heads": (8, 4, 128, 128),      # a scale row of a lane tile
}


@pytest.fixture
def interpret(monkeypatch):
    """A backend that runs Pallas through the interpreter, and a rule that
    takes the toy chunks here for chunks."""
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 32)
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield


def _case(node, dtype, tq, total, seed=0, qdtype=jnp.float32, slots=1,
          sink=False, value_scale=1.0):
    h, kvh, hd, hdv = NODES[node]
    rng = np.random.RandomState(seed)
    k = jnp.asarray(rng.randn(1 + slots * M, PT, kvh * hd).astype(np.float32))
    v = jnp.asarray(rng.randn(1 + slots * M, PT, kvh * hdv).astype(np.float32))
    if dtype == "int8":
        kp, vp = attn.quantize_pools(k, v, dtype, kvh)
    else:
        kp, vp = k.astype(dtype), v.astype(dtype)
    table = jnp.asarray(1 + rng.permutation(slots * M).reshape(slots, M),
                        jnp.int32)
    q = jnp.asarray(rng.randn(slots, tq, h * hd).astype(np.float32)) \
        .astype(qdtype)
    kw = dict(num_heads=h, num_kv_heads=kvh, value_scale=value_scale,
              sink=jnp.asarray(rng.randn(h).astype(np.float32))
              if sink else None)
    return (q, kp, vp, table, jnp.full((slots,), total, jnp.int32)), kw


def _walk(fn, *args, **kw):
    """``fn`` on a backend shown no Pallas."""
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        out = fn(*args, **kw)
    assert attn.DECODE_PATH["last"] == "walk"
    return out


def _dispatched():
    counter = obs.registry.counter("mx_attn_dispatch_total",
                                   labels=("path",))
    return {path: counter.labels(path=path).get()
            for path in ("chunk-kernel", "decode-kernel", "walk", "whole")}


def _took(before):
    after = _dispatched()
    return {p: after[p] - before[p] for p in after if after[p] != before[p]}


# ---------------------------------------------------------------------------
# parity with the walk
# ---------------------------------------------------------------------------
# (rows, the slot's length through the chunk's last row): a context that
# ends inside a block; one that ends on a block's edge; the first chunk of a
# prompt (nothing before it, its rows see only each other); a chunk that
# crosses a block's edge; a ring that has wrapped
CHUNKS = {"ends_inside_a_block": (64, 300), "ends_on_an_edge": (32, 512),
          "first_chunk": (64, 64), "crosses_an_edge": (64, 280),
          "wrapped": (32, M * PT + 9)}


@pytest.mark.parametrize("chunk", sorted(CHUNKS))
@pytest.mark.parametrize("dtype", ["int8", "bfloat16"])
@pytest.mark.parametrize("node", ["mha", "gqa_16_to_1"])
def test_kernel_parity_with_the_walk(node, dtype, chunk, interpret):
    """``paged_attend``'s chunk path against the walk over the same blocks:
    float32 queries over an int8 pool (a chunk program's, exact in the
    interpreter), bfloat16 queries over a bfloat16 pool."""
    tq, total = CHUNKS[chunk]
    exact = dtype == "int8"
    args, kw = _case(node, dtype, tq, total, seed=len(chunk),
                     qdtype=jnp.float32 if exact else jnp.bfloat16)
    before = _dispatched()
    got = attn.paged_attend(*args, **kw)
    assert attn.DECODE_PATH["last"] == "chunk-kernel"
    assert _took(before) == {"chunk-kernel": 1}
    ref = _walk(attn.paged_attend, *args, **kw)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    tol = dict(rtol=RTOL, atol=ATOL) if exact else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_a_chunk_programs_own_types(interpret):
    """bfloat16 queries over an int8 pool: the probabilities are rounded to
    bfloat16 before the second product on both sides, as on the chip."""
    args, kw = _case("gqa_16_to_1", "int8", 64, 300, seed=5,
                     qdtype=jnp.bfloat16)
    got = attn.paged_attend(*args, **kw)
    assert attn.DECODE_PATH["last"] == "chunk-kernel"
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_walk(attn.paged_attend, *args, **kw), np.float32),
        rtol=2e-2, atol=2e-2)


def test_a_sink_and_a_value_scale_join_in_the_combine(interpret):
    """What the kernel returns is the walk's running row, not yet
    normalized: the sink and the value scale join in ``_combine_blocks`` as
    they do behind the loop; values of another width than the keys."""
    args, kw = _case("values_of_256", "int8", 32, 420, seed=7, sink=True,
                     value_scale=0.707)
    got = attn.paged_attend(*args, **kw)
    assert attn.DECODE_PATH["last"] == "chunk-kernel"
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_walk(attn.paged_attend, *args, **kw)),
        rtol=RTOL, atol=ATOL)
    q, kp, vp, table, total = args
    whole = attn._sdpa_cache(q, *attn.paged_gather_kv(kp, vp, table), total,
                             kw["num_heads"], None,
                             num_kv_heads=kw["num_kv_heads"], sink=kw["sink"],
                             value_scale=kw["value_scale"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=RTOL, atol=ATOL)


def test_the_running_row_is_the_walks(interpret):
    """``attend_chunk_blocks`` alone: the maxima, the sums and the
    accumulated values of every row and head against ``_sdpa_cache`` over
    the whole gathered view as one block; tiles of 32 rows of a chunk of 64,
    so the second tile visits a block the first does not."""
    args, kw = _case("gqa_16_to_1", "int8", 64, 290, seed=2)
    q, kp, vp, table, total = args
    h, kvh = kw["num_heads"], kw["num_kv_heads"]
    t = pd.chunk_tiles(q.shape, kp, vp, h, kvh, 256)._replace(rows=32)
    pages = jnp.pad(table, ((0, 0), (0, 8))).reshape(3, 16)
    m, den, acc = pd.attend_chunk_blocks(
        q, kp, vp, pages, total[0], M * PT, t, 1.0 / np.sqrt(128),
        interpret=True)
    want = attn._sdpa_cache(q, *attn.paged_gather_kv(kp, vp, table), total,
                            h, None, num_kv_heads=kvh,
                            block=(jnp.zeros((1,), jnp.int32), M * PT))
    np.testing.assert_allclose(np.asarray(m), np.asarray(want[0]),
                               rtol=RTOL, atol=ATOL)
    # the sums and the values lie about the same maxima
    np.testing.assert_allclose(np.asarray(den), np.asarray(want[1]),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want[2]),
                               rtol=RTOL, atol=1e-4)


# ---------------------------------------------------------------------------
# a selection laid over the walk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,qdtype", [("int8", jnp.float32),
                                          ("bfloat16", jnp.bfloat16)])
@pytest.mark.parametrize("node", ["mha", "gqa_16_to_1"])
def test_a_drawn_selection(node, dtype, qdtype, interpret):
    """``chosen`` as the walk takes it, half of the blocks of 64 positions
    drawn a row and a KV group, none of block 1 of the walk (positions
    256-511) for the even rows of group 0."""
    tq, total, width = 64, 600, 64
    args, kw = _case(node, dtype, tq, total, seed=11, qdtype=qdtype)
    q, kp, vp, table, tot = args
    h, kvh = kw["num_heads"], kw["num_kv_heads"]
    rng = np.random.RandomState(12)
    mask = rng.rand(1, kvh, tq, M * PT // width) < 0.5
    mask[..., 0] = True
    mask[0, 0, ::2, 4:8] = False
    chosen = (jnp.asarray(mask), width)
    tiles, interp = attn.chunk_kernel_selected(
        q.shape, kp, vp, table.shape, h, kvh, chosen=(mask.shape, width))
    assert tiles.per == 4 and interp
    run = lambda **how: attn._attend_live_blocks(
        q, kp, vp, table, tot, h, None, kvh, 256, 1, chosen=chosen, **how)
    got, ref = run(chunk=(tiles, True)), run()
    tol = dict(rtol=RTOL, atol=ATOL) if dtype == "int8" \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **tol)
    # and the selection weighs: with nothing chosen away the rows differ
    plain = attn._attend_live_blocks(q, kp, vp, table, tot, h, None, kvh,
                                     256, 1, chunk=(tiles._replace(per=0),
                                                    True))
    assert np.abs(np.asarray(plain, np.float32)
                  - np.asarray(got, np.float32)).max() > 0.05


SPEC = attn.SparseSpec(topk=3, block=64, kernel=32, stride=16, init_blocks=1,
                       window=64, dense_len=300)


@pytest.mark.parametrize("total", [330, 600])
def test_paged_attend_sparse_takes_the_kernel(total, interpret):
    """``paged_attend_sparse``'s chunk branch: at 330 the chunk's rows lie
    on both sides of ``dense_len`` (300: the earlier rows take every block
    they see, the later ones choose three); at 600 every row chooses three
    blocks of 64 of the ten it sees, and most rows so leave a whole block of
    the walk (256 positions) empty."""
    tq = 64
    args, kw = _case("gqa_16_to_1", "int8", tq, total, seed=total)
    q, kp, vp, table, tot = args
    h, kvh = kw["num_heads"], kw["num_kv_heads"]
    index = jnp.asarray(np.random.RandomState(3).randn(
        kp.data.shape[0], kvh * 128).astype(np.float32))
    call = lambda: attn.paged_attend_sparse(
        q, kp, vp, index, table, tot, SPEC, num_heads=h, num_kv_heads=kvh)
    before = _dispatched()
    got, (chosen, live) = call()
    assert attn.DECODE_PATH["last"] == "chunk-kernel"
    assert _took(before) == {"chunk-kernel": 1}
    ref, (chosen_w, live_w) = _walk(call)
    assert int(chosen) == int(chosen_w) and int(live) == int(live_w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=RTOL,
                               atol=ATOL)
    n = np.asarray(tot)[0] - (tq - 1) + np.arange(tq)
    mask = np.asarray(attn.sparse_block_mask(
        q, index[table], n[None], SPEC, M * PT // SPEC.block, h, kvh))
    took = mask.sum(-1)
    if total == 330:
        assert (n <= SPEC.dense_len).any() and (n > SPEC.dense_len).any()
        assert (took[0, :, n <= SPEC.dense_len] > SPEC.topk).all()
        assert (took[0, :, n > SPEC.dense_len] == SPEC.topk).all()
    else:
        blocks = np.pad(mask, ((0, 0),) * 3 + ((0, 2),)).reshape(
            1, kvh, tq, 3, 4)
        assert (~blocks.any(-1)).any(-1).sum() >= tq // 2


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
def _pools(ek=256, ev=256, kvh=2, pages=M, slots=1, dtype=jnp.int8):
    k = jax.ShapeDtypeStruct((1 + slots * pages, PT, ek), dtype)
    v = jax.ShapeDtypeStruct((1 + slots * pages, PT, ev), dtype)
    if jnp.dtype(dtype).itemsize == 1:
        k = attn.QuantKV(k, jax.ShapeDtypeStruct(
            (k.shape[0], PT * 2 * kvh), jnp.float32))
        v = attn.QuantKV(v, None)
    return k, v


def _selected(q_shape=(1, 64, 256), kvh=2, heads=2, pages=M,
              mesh_active=False, window=0, chosen=None, **pools):
    return attn.chunk_kernel_selected(
        q_shape, *_pools(kvh=kvh, pages=pages, slots=q_shape[0], **pools),
        (q_shape[0], pages), heads, kvh, mesh_active=mesh_active,
        window=window, chosen=chosen)[0]


@pytest.mark.parametrize("why,kw", [
    ("one query row", dict(q_shape=(1, 1, 256))),
    ("two rows of many slots", dict(q_shape=(48, 2, 256))),
    ("many rows of two slots", dict(q_shape=(2, 64, 256))),
    ("fewer rows than the rule's constant", dict(q_shape=(1, 16, 256))),
    ("a window node", dict(window=128)),
    ("a mesh shards the pools", dict(mesh_active=True)),
    ("a view of one block", dict(pages=16)),
    ("heads of 64: no whole lane tiles", dict(heads=4, kvh=4)),
    ("keys of 192, values of 128", dict(q_shape=(1, 64, 384), ek=384)),
    ("a float32 pool", dict(dtype=jnp.float32)),
    ("rows that no tile divides", dict(q_shape=(1, 72, 256))),
    ("a selection of three blocks a block of the walk",
     dict(pages=48, chosen=((1, 2, 64, 9), 256 // 3))),
    ("a selection for other rows", dict(chosen=((1, 2, 32, 10), 64))),
])
def test_rule_refuses(why, kw, interpret):
    assert _selected() is not None
    assert _selected(dtype=jnp.bfloat16) is not None
    assert _selected(chosen=((1, 2, 64, 10), 64)).per == 4
    assert _selected(**kw) is None, why


def test_rule_needs_a_backend_that_runs_pallas(monkeypatch):
    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 32)
    assert _selected() is None                  # the CPU, no interpreter
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        t = _selected()
        assert (t.heads, t.kv_heads, t.hd, t.hdv, t.ppb, t.rows, t.per) == \
            (2, 2, 128, 128, 16, 64, 0)
    # the constant as it stands keeps a chunk of 64 rows on the walk
    monkeypatch.undo()
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        assert _selected() is None
        assert _selected(q_shape=(1, attn.CHUNK_MIN_ROWS, 256)) is not None


def test_tile_rows_follow_fast_memory():
    """The tile is the largest of ``CHUNK_TILE_ROWS`` that divides the
    chunk and keeps every head's running state within the budget: MiniCPM-
    SALA's 32 heads and Solar-Open2's 64 at chunks of 2048."""
    for heads, kvh, rows in ((32, 2, 512), (64, 8, 256)):
        t = pd.chunk_tiles((1, 2048, heads * 128),
                           *_pools(ek=kvh * 128, ev=kvh * 128, kvh=kvh,
                                   pages=4160), heads, kvh, 512)
        assert t.rows == rows and t.vmem <= pd._VMEM_BUDGET, t


@pytest.mark.parametrize("why,path,kw", [
    ("one row a slot: the decode row's kernel", "decode-kernel",
     dict(tq=1, slots=3)),
    ("two rows of many slots", "walk", dict(tq=2, slots=3)),
    ("a window ring", "whole", dict(tq=64, extra=dict(window=128))),
    ("a mesh", "whole", dict(tq=64, extra=dict(mesh_active=True))),
])
def test_what_the_rule_refuses_takes_its_old_path(why, path, kw, interpret):
    """``mx_attn_dispatch_total{path}`` after a refused call: the path it
    took before there was a chunk kernel."""
    args, akw = _case("four_kv_heads", "int8", kw["tq"], 300,
                      slots=kw.get("slots", 1))
    before = _dispatched()
    out = attn.paged_attend(*args, **akw, **kw.get("extra", {}))
    assert attn.DECODE_PATH["last"] == path, why
    assert _took(before) == {path: 1}
    assert np.all(np.isfinite(np.asarray(out)))


def test_heads_of_64_keep_the_walk(interpret):
    rng = np.random.RandomState(1)
    k, v = (jnp.asarray(rng.randn(1 + M, PT, 256).astype(np.float32))
            for _ in range(2))
    kp, vp = attn.quantize_pools(k, v, "int8", 4)
    before = _dispatched()
    attn.paged_attend(jnp.asarray(rng.randn(1, 64, 256), jnp.float32), kp, vp,
                      jnp.arange(1, M + 1, dtype=jnp.int32)[None],
                      jnp.asarray([300], jnp.int32), num_heads=4,
                      num_kv_heads=4)
    assert _took(before) == {"walk": 1}


def test_an_expanded_latent_chunk_keeps_the_walk(interpret):
    """Latent attention's expanded chunk brings its own ``gather`` (a block's
    rows turned back into keys and values inside the walk): it does not ask
    THIS rule, and a chunk of 96 rows, which its own rule
    (``latent_chunk_kernel_selected``, tests/test_latent_chunk_kernel.py)
    does not tile, holds no kernel."""
    spec = attn.latent_spec(dict(num_heads=2, qk_nope_head_dim=128,
                                 qk_rope_head_dim=64, v_head_dim=128,
                                 kv_lora_rank=256))
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    t = max(attn.LATENT_EXPAND_ROWS, 64)
    plane = draw(*pd.latent_plane_shape(1 + M, PT, spec.rank + spec.rope))
    args = (draw(1, t, 2, 128), draw(1, t, 2, 64), plane,
            jnp.arange(1, M + 1, dtype=jnp.int32)[None],
            jnp.asarray([500], jnp.int32),
            0.06 * draw(2 * (128 + 128), spec.rank), spec)
    before = _dispatched()
    text = str(jax.make_jaxpr(lambda *a: attn.latent_attend(*a, spec))(
        *args[:-1]))
    assert attn.DECODE_PATH["last"] == "expanded"
    assert _took(before) == {} and "pallas_call" not in text


# ---------------------------------------------------------------------------
# a toy graph with heads of 128, served in chunks of 64
# ---------------------------------------------------------------------------
VOCAB, SLOTS, CACHE = 64, 2, 512


def _predictor():
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.models import decoder_lm

    sym = decoder_lm.get_symbol(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_attention_heads=8,
        head_dim=128, num_key_value_heads=4, intermediate_size=64)
    rng = np.random.RandomState(5)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    params = {n: (1.0 + 0.1 * rng.randn(*s) if len(s) == 1
                  else rng.normal(0, 0.08, s)).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return DecodePredictor(sym, params, cache_len=CACHE, temperature=0.0,
                           paged=True, page_tokens=PT, prefill_chunk=64,
                           kv_dtype="int8")


def _serve(pred):
    from mxnet_tpu.decode import DecodeServer

    server = DecodeServer(pred, max_prefill=320, slots=SLOTS,
                          max_new_tokens=3)
    rng = np.random.RandomState(9)
    ids = [server.submit(rng.randint(0, VOCAB, size=(n,)))
           for n in (300, 70, 260)]
    results = server.run()
    return [np.asarray(results[i]) for i in ids]


def test_served_chunks_take_the_kernel_and_say_so(interpret, monkeypatch):
    """The toy's chunk program takes the chunk kernel at both nodes, its
    decode program the decode row's; the predictor's record of each
    program's paths says so, an artifact's meta promises the kernel from it,
    and the server emits exactly the walk's tokens."""
    from mxnet_tpu.analysis import run_passes
    from mxnet_tpu.analysis.artifact import ProgramArtifact
    from mxnet_tpu.analysis.passes import FlopDtypePass

    monkeypatch.setattr(attn, "CHUNK_MIN_ROWS", 64)
    before = _dispatched()
    pred = _predictor()
    on = _serve(pred)
    assert _took(before) == {"chunk-kernel": 2, "decode-kernel": 2}
    assert pred._decode_paths[64] == {"chunk-kernel"}
    assert pred._decode_paths[1] == {"decode-kernel"}
    # a program of that width that promised the kernel and lowered
    # without it trips the flop-dtype pass
    art = pred._refine_decode_meta(ProgramArtifact(
        name="prefill_chunk", jaxpr_text="no kernels here",
        stablehlo_text="", compiled_text="HloModule stub\n", meta={}),
        rows=64)
    assert art.meta["attn_paths"] == ["chunk-kernel"]
    assert art.meta["pallas_decode"] is True
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert any(f.code == "pallas-fallback" for f in rep.errors)
    with config.overrides(MXNET_PALLAS_INTERPRET="0"):
        walk = _predictor()
        off = _serve(walk)
    assert walk._decode_paths[64] == {"walk"}
    for i, (a, b) in enumerate(zip(on, off)):
        assert np.array_equal(a, b), \
            "request %d diverged: kernel %s vs walk %s" % (i, a, b)
