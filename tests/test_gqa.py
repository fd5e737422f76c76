"""GQA/MQA head groups end to end (num_kv_heads < num_heads).

The grouped-KV contract: every K/V tensor — dense attention inputs, ring
hop slices, flash-kernel blocks, decode caches and page pools — carries
``H_kv = num_heads / G`` heads physically (never a broadcast copy), each
query head h reads kv head ``h // G``, and the G=1 configuration is
bit-identical to the ungrouped code (the grouped machinery must vanish
when there is nothing to group).  Satellite coverage rides along: the
named head-divisibility ``ValueError``s, the ``mha-under-gqa`` cache-bytes
finding, the swap-restore layout guard, and the ``gqa_decode_step``
canonical program registration.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.models import attention_lm
from mxnet_tpu.ops import attention
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.attention import check_head_groups, sdpa


def _np_sdpa(q, k, v, num_heads, causal=False):
    b, tq, e = q.shape
    tk = k.shape[1]
    hd = e // num_heads
    ev = v.shape[2] // num_heads
    qh = q.reshape(b, tq, num_heads, hd)
    kh = k.reshape(b, tk, num_heads, hd)
    vh = v.reshape(b, tk, num_heads, ev)
    logits = np.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(hd)
    if causal:
        mask = np.tril(np.ones((tq, tk), bool), k=tk - tq)
        logits = np.where(mask[None, None], logits, -1e30)
    logits -= logits.max(-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bkhe->bqhe", p, vh)
    return out.reshape(b, tq, num_heads * ev)


def _np_gqa(q, k, v, num_heads, num_kv_heads, causal=False):
    """Grouped reference BY CONSTRUCTION: repeat each kv head across its
    G query heads, then run the plain MHA reference — the semantics the
    physically-grouped kernels must reproduce without materializing the
    repeat."""
    b, tk, ekv = k.shape
    g = num_heads // num_kv_heads
    hd = ekv // num_kv_heads
    ev = v.shape[2] // num_kv_heads
    kfull = np.repeat(k.reshape(b, tk, num_kv_heads, hd), g,
                      axis=2).reshape(b, tk, num_heads * hd)
    vfull = np.repeat(v.reshape(b, tk, num_kv_heads, ev), g,
                      axis=2).reshape(b, tk, num_heads * ev)
    return _np_sdpa(q, kfull, vfull, num_heads, causal)


def _rand(rng, *shape):
    return rng.normal(size=shape).astype(np.float32)


# ---------------------------------------------------------------------------
# satellite: the named head-divisibility guards
# ---------------------------------------------------------------------------
def test_head_group_guard_messages():
    rng = np.random.RandomState(0)
    q = _rand(rng, 2, 4, 16)

    with pytest.raises(ValueError, match="num_heads=4 not divisible by "
                                         "num_kv_heads=3"):
        sdpa(q, _rand(rng, 2, 4, 12), _rand(rng, 2, 4, 12),
             num_heads=4, num_kv_heads=3)
    with pytest.raises(ValueError, match="query embed dim 16 not "
                                         "divisible by num_heads=3"):
        sdpa(q, q, q, num_heads=3)
    # key width must be exactly H_kv * head_dim — a full-width K under a
    # grouped config is the silent-broadcast bug the guard names
    with pytest.raises(ValueError, match="key embed dim 16 != "
                                         "num_kv_heads=2"):
        sdpa(q, q, _rand(rng, 2, 4, 8), num_heads=4, num_kv_heads=2)
    with pytest.raises(ValueError, match="value embed dim 9 not "
                                         "divisible by num_kv_heads=2"):
        sdpa(q, _rand(rng, 2, 4, 8), _rand(rng, 2, 4, 9),
             num_heads=4, num_kv_heads=2)
    with pytest.raises(ValueError, match="num_kv_heads=-1 must be "
                                         "positive"):
        check_head_groups(4, -1, 16)
    with pytest.raises(ValueError, match="num_heads=0 must be positive"):
        check_head_groups(0, 0, 16)

    # the decode-cache variants name the cache dims
    kc = np.zeros((2, 8, 8), np.float32)
    with pytest.raises(ValueError, match="value cache dim 9 not "
                                         "divisible by num_kv_heads=2"):
        attention.sdpa_decode(q[:, :1], kc, np.zeros((2, 8, 9),
                                                     np.float32),
                              total_len=np.array([4, 4]), num_heads=4,
                              num_kv_heads=2)

    with pytest.raises(ValueError, match="attention_lm.block: "
                                         "num_heads=4 not divisible by "
                                         "num_kv_heads=3"):
        attention_lm.get_symbol(vocab_size=8, seq_len=8, num_layers=1,
                                embed=16, heads=4, ffn_hidden=16,
                                num_kv_heads=3)


def test_ring_head_axis_rejects_indivisible_kv_heads():
    """A model-axis split that does not divide H_kv must raise the named
    guard at trace time, never shard a head group across devices."""
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map
    from mxnet_tpu.parallel.ring import ring_attention

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("seq", "model"))
    b, t, heads, kvh, hd = 1, 16, 4, 1, 4
    q = np.zeros((b, t, heads * hd), np.float32)
    kv = np.zeros((b, t, kvh * hd), np.float32)

    fn = shard_map(
        lambda q_, k_, v_: ring_attention(
            q_, k_, v_, axis_name="seq", num_heads=heads,
            num_kv_heads=kvh, head_axis="model"),
        mesh=mesh,
        in_specs=(P(None, "seq", "model"), P(None, "seq", None),
                  P(None, "seq", None)),
        out_specs=P(None, "seq", "model"), check_vma=False)
    with pytest.raises(ValueError, match="num_kv_heads=1 not divisible"):
        jax.eval_shape(fn, q, kv, kv)


# ---------------------------------------------------------------------------
# tentpole numerics: dense / decode / verify vs the grouped reference,
# G=1 bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads,kvh,causal", [(4, 2, False), (4, 1, True),
                                              (6, 3, True)])
def test_sdpa_grouped_matches_reference(heads, kvh, causal):
    rng = np.random.RandomState(1)
    hd = 8
    q = _rand(rng, 2, 5, heads * hd)
    k = _rand(rng, 2, 5, kvh * hd)
    v = _rand(rng, 2, 5, kvh * hd)
    out = np.asarray(sdpa(q, k, v, num_heads=heads, causal=causal,
                          num_kv_heads=kvh))
    ref = _np_gqa(q, k, v, heads, kvh, causal)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_sdpa_g1_bit_identical():
    """num_kv_heads == num_heads must take the VERBATIM ungrouped code:
    outputs and gradients bit-equal, not just close."""
    rng = np.random.RandomState(2)
    q, k, v = (_rand(rng, 2, 6, 16) for _ in range(3))

    def loss(fn):
        return jax.grad(lambda a, b_, c: (fn(a, b_, c) ** 2).sum(),
                        argnums=(0, 1, 2))

    base = sdpa(q, k, v, num_heads=4, causal=True)
    grouped = sdpa(q, k, v, num_heads=4, causal=True, num_kv_heads=4)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(grouped))
    gb = loss(lambda a, b_, c: sdpa(a, b_, c, num_heads=4,
                                    causal=True))(q, k, v)
    gg = loss(lambda a, b_, c: sdpa(a, b_, c, num_heads=4, causal=True,
                                    num_kv_heads=4))(q, k, v)
    for x, y in zip(gb, gg):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_decode_verify_grouped_matches_reference():
    """sdpa_decode / sdpa_verify over H_kv-width caches equal the MHA
    path over the repeat-expanded caches; G=1 is bit-identical."""
    rng = np.random.RandomState(3)
    b, heads, kvh, hd, clen = 2, 4, 2, 8, 12
    g = heads // kvh
    total = np.array([7, 10], np.int32)
    kc = _rand(rng, b, clen, kvh * hd)
    vc = _rand(rng, b, clen, kvh * hd)
    kfull = np.repeat(kc.reshape(b, clen, kvh, hd), g,
                      axis=2).reshape(b, clen, heads * hd)
    vfull = np.repeat(vc.reshape(b, clen, kvh, hd), g,
                      axis=2).reshape(b, clen, heads * hd)

    q1 = _rand(rng, b, 1, heads * hd)
    out = np.asarray(attention.sdpa_decode(q1, kc, vc, total,
                                           num_heads=heads,
                                           num_kv_heads=kvh))
    ref = np.asarray(attention.sdpa_decode(q1, kfull, vfull, total,
                                           num_heads=heads))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    qs = _rand(rng, b, 3, heads * hd)
    outv = np.asarray(attention.sdpa_verify(qs, kc, vc, total,
                                            num_heads=heads,
                                            num_kv_heads=kvh))
    refv = np.asarray(attention.sdpa_verify(qs, kfull, vfull, total,
                                            num_heads=heads))
    np.testing.assert_allclose(outv, refv, rtol=1e-5, atol=1e-6)

    same = np.asarray(attention.sdpa_decode(q1, kfull, vfull, total,
                                            num_heads=heads,
                                            num_kv_heads=heads))
    np.testing.assert_array_equal(same, ref)


def test_quantkv_grouped_scales_per_kv_head():
    """int8 caches scale per (token, kv-head): the scale plane is H_kv
    wide, and the grouped round trip stays within int8 error."""
    from mxnet_tpu.ops.attention import dequantize_kv, quantize_kv

    rng = np.random.RandomState(4)
    kvh, hd = 2, 8
    x = _rand(rng, 3, 5, kvh * hd)
    cache = quantize_kv(x, "int8", num_heads=kvh)
    assert cache.data.dtype == jnp.int8
    assert cache.scale.shape == (3, 5, kvh)
    back = np.asarray(dequantize_kv(cache, num_heads=kvh))
    np.testing.assert_allclose(back, x, atol=np.abs(x).max() / 100)


# ---------------------------------------------------------------------------
# tentpole: flash kernels (interpret mode) — grouped fwd/bwd, G=1 identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grouped_matches_einsum(causal):
    rng = np.random.RandomState(5)
    b, t, heads, kvh, hd = 2, 128, 4, 1, 32
    q = _rand(rng, b, t, heads * hd)
    k = _rand(rng, b, t, kvh * hd)
    v = _rand(rng, b, t, kvh * hd)

    def flash(a, b_, c):
        return pa.sdpa_flash(a, b_, c, heads, causal, None,
                             interpret=True, num_kv_heads=kvh)

    def ein(a, b_, c):
        return sdpa(a, b_, c, num_heads=heads, causal=causal,
                    num_kv_heads=kvh)

    out = np.asarray(flash(q, k, v))
    ref = np.asarray(ein(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-5)

    gf = jax.grad(lambda *a: (flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    ge = jax.grad(lambda *a: (ein(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for x, y in zip(gf, ge):
        scale = max(np.abs(np.asarray(y)).max(), 1.0)
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-3, atol=2e-4 * scale)


def test_flash_g1_bit_identical():
    rng = np.random.RandomState(6)
    b, t, heads, hd = 1, 128, 2, 32
    q, k, v = (_rand(rng, b, t, heads * hd) for _ in range(3))
    base = np.asarray(pa.sdpa_flash(q, k, v, heads, True, None,
                                    interpret=True))
    grouped = np.asarray(pa.sdpa_flash(q, k, v, heads, True, None,
                                       interpret=True,
                                       num_kv_heads=heads))
    np.testing.assert_array_equal(base, grouped)


def test_flash_supported_gates_grouped_shapes():
    assert pa.supported((2, 128, 256), (2, 128, 64), False,
                        num_heads=4, num_kv_heads=1)
    # H % H_kv != 0 and a K width that disagrees with H_kv both gate out
    assert not pa.supported((2, 128, 256), (2, 128, 64), False,
                            num_heads=4, num_kv_heads=3)
    assert not pa.supported((2, 128, 256), (2, 128, 256), False,
                            num_heads=4, num_kv_heads=1)


# ---------------------------------------------------------------------------
# tentpole: ring rotates H_kv-width slices — wire bytes divided by G
# ---------------------------------------------------------------------------
def test_ring_grouped_numerics_and_wire_bytes():
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map
    from mxnet_tpu.parallel.hlo_stats import collective_stats
    from mxnet_tpu.parallel.ring import dense_attention, ring_attention

    n = 2
    b, t, heads, kvh, hd = 1, 32, 4, 1, 8
    g = heads // kvh
    rng = np.random.RandomState(7)
    q = _rand(rng, b, t, heads * hd)
    k = _rand(rng, b, t, kvh * hd)
    v = _rand(rng, b, t, kvh * hd)
    kf = _rand(rng, b, t, heads * hd)
    vf = _rand(rng, b, t, heads * hd)
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))

    # one compile per ring config serves BOTH the numerics and the
    # compiled-HLO wire accounting (multi-device ring compiles dominate
    # this test's tier-1 cost)
    def ring_exec(num_kv_heads, kk, vv):
        fn = shard_map(
            lambda q_, k_, v_: ring_attention(
                q_, k_, v_, axis_name="seq", num_heads=heads,
                causal=True, num_kv_heads=num_kv_heads),
            mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
            out_specs=P(None, "seq", None), check_vma=False)
        ce = jax.jit(fn).lower(q, kk, vv).compile()
        st = collective_stats(ce.as_text())["collective-permute"]
        return np.asarray(ce(q, kk, vv)), st

    out, st_g = ring_exec(kvh, k, v)
    ref = np.asarray(dense_attention(q, k, v, num_heads=heads,
                                     causal=True, num_kv_heads=kvh))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out, _np_gqa(q, k, v, heads, kvh, True),
                               rtol=1e-4, atol=1e-5)

    # the wire budget: only (B, T_loc, H_kv*hd) K/V slices rotate, so
    # the grouped ring's collective-permute bytes are EXACTLY 1/G the
    # MHA ring's at identical hop count
    base, st_m = ring_exec(0, kf, vf)
    assert st_g["count"] == st_m["count"] == 2 * (n - 1), (st_g, st_m)
    assert st_g["bytes"] * g == st_m["bytes"], (st_g, st_m, g)

    # G=1 grouped spelling is the identical program
    same, _ = ring_exec(heads, kf, vf)
    np.testing.assert_array_equal(same, base)


# ---------------------------------------------------------------------------
# tentpole: the grouped LM end to end — dense rings vs paged pools, cache
# widths, graph stability at G=1
# ---------------------------------------------------------------------------
VOCAB, T, EMBED, HEADS = 17, 16, 16, 4
B = 2


def _lm_and_params(num_kv_heads=0, seed=0):
    sym = attention_lm.get_symbol(VOCAB, T, num_layers=2, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16,
                                  num_kv_heads=num_kv_heads)
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(data=(B, T), softmax_label=(B, T))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = rng.normal(0, 0.5, shape).astype(np.float32)
    return sym, params


def test_grouped_lm_paged_matches_dense_and_shrinks_caches():
    """The MQA LM through both cache layouts: paged pools reproduce the
    dense-ring logits and greedy tokens, every cache plane is H_kv wide,
    and the paged programs trace once."""
    kvh = 1
    sym, params = _lm_and_params(num_kv_heads=kvh)
    rng = np.random.RandomState(8)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    lens = np.array([5, 9], np.int32)
    for i in range(B):
        x[i, lens[i]:] = 0.0

    dense = DecodePredictor(sym, params, cache_len=T)
    paged = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4, prefill_chunk=4)
    assert dense._grouped_kv_heads == kvh
    ds, dp = dense.prefill(x, lens)
    ps, pp = paged.prefill(x, lens)
    np.testing.assert_allclose(np.asarray(pp), np.asarray(dp),
                               rtol=1e-5, atol=1e-6)
    # the physical promise: every K/V plane carries H_kv * hd columns
    hd = EMBED // HEADS
    for kc, vc in ds.caches:
        kdata = kc.data if hasattr(kc, "data") else kc
        vdata = vc.data if hasattr(vc, "data") else vc
        assert kdata.shape[2] == kvh * hd, kdata.shape
        assert vdata.shape[2] == kvh * hd, vdata.shape
    for i in range(3):
        ds, dp = dense.step(ds)
        ps, pp = paged.step(ps)
        np.testing.assert_allclose(np.asarray(pp), np.asarray(dp),
                                   rtol=1e-5, atol=1e-6, err_msg="i=%d" % i)
        np.testing.assert_array_equal(np.asarray(ps.tok),
                                      np.asarray(ds.tok))
    assert paged.trace_counts["chunk"] == 1
    assert paged.trace_counts["decode"] == 1
    # the artifact meta carries the grouped layout for CacheBytesPass
    meta = dense._cache_meta(ds)
    assert meta["num_kv_heads"] == kvh
    assert meta["cache_kv_dims"] == [kvh * hd]


def test_grouped_lm_matches_repeat_reference():
    """The grouped LM's prefill logits equal an ungrouped LM whose K/V
    projection weights are the grouped ones repeated per group — the
    whole-model version of the einsum-level reference."""
    kvh = 2
    g = HEADS // kvh
    hd = EMBED // HEADS
    gsym, gparams = _lm_and_params(num_kv_heads=kvh, seed=9)
    msym, _ = _lm_and_params(seed=9)
    gshapes = dict(zip(gsym.list_arguments(),
                       gsym.infer_shape(data=(B, T),
                                        softmax_label=(B, T))[0]))
    mshapes = dict(zip(msym.list_arguments(),
                       msym.infer_shape(data=(B, T),
                                        softmax_label=(B, T))[0]))

    mparams = {}
    for name, val in gparams.items():
        gs, ms = tuple(gshapes[name]), tuple(mshapes[name])
        if gs == ms:
            mparams[name] = val
            continue
        # the one differing axis is the kv-head one: repeat each kv
        # head's slice across its G query heads for the MHA twin
        ax = [i for i in range(len(gs)) if gs[i] != ms[i]]
        assert ax and gs[ax[0]] == kvh * hd and ms[ax[0]] == HEADS * hd
        w = np.moveaxis(val, ax[0], -1)
        lead = w.shape[:-1]
        w = np.repeat(w.reshape(lead + (kvh, hd)), g, axis=-2)
        mparams[name] = np.moveaxis(w.reshape(lead + (HEADS * hd,)),
                                    -1, ax[0])

    rng = np.random.RandomState(10)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    gpred = DecodePredictor(gsym, gparams, cache_len=T)
    mpred = DecodePredictor(msym, mparams, cache_len=T)
    gs, glog = gpred.prefill(x, T - 2)
    ms, mlog = mpred.prefill(x, T - 2)
    np.testing.assert_allclose(np.asarray(glog), np.asarray(mlog),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gs.tok), np.asarray(ms.tok))


def test_attention_lm_g1_graph_json_identical():
    """num_kv_heads == heads must serialize the IDENTICAL graph (no new
    attr), so fingerprints and AOT cache keys of every existing MHA
    checkpoint survive the refactor."""
    from mxnet_tpu.base import NameManager

    # fresh name scopes so the process-global gensym counters cannot
    # differ between the two otherwise-identical builds
    with NameManager():
        a = attention_lm.get_symbol(VOCAB, T, num_layers=1, embed=EMBED,
                                    heads=HEADS, ffn_hidden=16)
    with NameManager():
        b = attention_lm.get_symbol(VOCAB, T, num_layers=1, embed=EMBED,
                                    heads=HEADS, ffn_hidden=16,
                                    num_kv_heads=HEADS)
    assert a.tojson() == b.tojson()
    # grouped params keep the MHA names (checkpoints load by name), only
    # the K/V widths change
    c = attention_lm.get_symbol(VOCAB, T, num_layers=1, embed=EMBED,
                                heads=HEADS, ffn_hidden=16,
                                num_kv_heads=1)
    assert c.list_arguments() == a.list_arguments()


# ---------------------------------------------------------------------------
# satellites: cache-bytes finding, swap guard, TP pspec, canonical program
# ---------------------------------------------------------------------------
def test_cache_bytes_pass_mha_under_gqa():
    """A pool/cache plane at the full q width under a grouped config is
    the dropped-layout regression the pass must error on."""
    from mxnet_tpu.analysis import ProgramArtifact, run_passes
    from mxnet_tpu.analysis.passes import CacheBytesPass

    def art(widths):
        return ProgramArtifact(
            name="gqa_decode_step", jaxpr_text="", stablehlo_text="",
            compiled_text="HloModule stub\n",
            meta={"cache_bytes": 1024, "kv_dtype": None,
                  "cache_data_dtypes": ["float32"],
                  "num_kv_heads": 1,
                  "attn_dims": [{"num_heads": 4, "num_kv_heads": 1,
                                 "q_dim": 16, "kv_dim": 4}],
                  "cache_kv_dims": widths})

    rep = run_passes([art([16])], passes=[CacheBytesPass()])
    bad = [f for f in rep.findings if f.code == "mha-under-gqa"]
    assert len(bad) == 1 and bad[0].severity == "error", rep.findings
    assert "q width 16" in bad[0].message

    rep = run_passes([art([4])], passes=[CacheBytesPass()])
    assert not [f for f in rep.findings if f.code == "mha-under-gqa"]


def test_swap_restore_rejects_mismatched_kv_layout():
    """A grouped swap record must never install into an MHA host (page
    planes are raw pool bytes — a silent install would misread every
    page)."""
    from mxnet_tpu.serve.swap import SwappedRequest

    sym, params = _lm_and_params()  # MHA host
    pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                           page_tokens=4)
    server = DecodeServer(pred, max_prefill=T, slots=2)
    rec = SwappedRequest(prompt=np.arange(4), delivered=[], history=[],
                         cap=4, priority=0, lens=4, tok=1,
                         row_valid=np.ones(4, bool), data=None,
                         rid=7, kv_heads=1)
    with pytest.raises(MXNetError, match="kv layout"):
        server._try_restore({"active": {}}, {"swap": rec})
    assert rec.kv_heads == 1
    # an MHA record (kv_heads=None) is what an MHA host emits: the guard
    # compares None == None and proceeds past the layout check
    assert pred._grouped_kv_heads is None


def test_kv_pspec_grouped_sharding_degrades_visibly():
    """H_kv % model == 0 shards kv heads on 'model'; otherwise the pspec
    degrades to replicated-group with a warning that names the dims."""
    from mxnet_tpu.parallel.tp_rules import kv_cache_pspec, kv_pool_pspec

    sizes = {"data": 2, "model": 2}
    assert kv_cache_pspec(sizes, num_kv_heads=2)[2] == "model"
    assert kv_pool_pspec(sizes, num_kv_heads=4)[2] == "model"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spec = kv_cache_pspec(sizes, num_kv_heads=1)
    assert spec[2] is None
    assert any("replicated-group" in str(x.message) for x in w), \
        [str(x.message) for x in w]
    # legacy MHA configs (num_kv_heads unset) keep the old rule silently
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        kv_cache_pspec(sizes)
    assert not w2


def test_gqa_decode_step_is_canonical():
    import mxnet_tpu.analysis.programs as _progs
    from mxnet_tpu.programs.registry import REGISTRY

    assert "gqa_decode_step" in _progs.CANONICAL_PROGRAMS
    assert "gqa_decode_step" in REGISTRY.canonical_names()
