"""Fused Pallas flash-decoding kernels (ops/pallas_decode.py) and their
dispatch/pricing/lint wiring.

The ISSUE-11 acceptance surface, all in interpret mode on the CPU
harness (the same kernels Mosaic compiles on TPU):

* kernel parity vs the three-pass einsum path (``paged_gather`` +
  ``sdpa_decode``/``sdpa_verify``) on padded lens, ring wrap, shared /
  recycled pages, int8 and fp8 pools, and k+1 verify windows;
* the dense-ring variant (identity page table) vs ``sdpa_decode``;
* dispatch gating: ``MXNET_PALLAS_DECODE`` + supported shapes take the
  kernel (``DECODE_PATH``), unsupported shapes / meshes / knob-off fall
  back to einsum — and the fallback is priced+linted, never silent;
* the paged speculative server is token-identical kernel-on vs
  kernel-off;
* ``program_cost`` prices the einsum path's materialized gather view
  (``gather_bytes``) so the fused path's attention bytes visibly drop;
* the flop-dtype pass's ``pallas-fallback`` artifact tripwire.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import config
from mxnet_tpu.ops import attention as attn
from mxnet_tpu.ops import pallas_decode as pd

VOCAB, T, EMBED, HEADS = 17, 16, 8, 2
B = 2


@pytest.fixture
def kernel_on():
    """Arm the fused decode kernel (interpret mode — CPU harness)."""
    with config.overrides(MXNET_PALLAS_DECODE="1",
                          MXNET_PALLAS_INTERPRET="1"):
        yield


def _pools(rng, pages, pt, e, dtype=None, heads=HEADS):
    k = jnp.asarray(rng.randn(pages, pt, e).astype(np.float32))
    v = jnp.asarray(rng.randn(pages, pt, e).astype(np.float32))
    if dtype is None:
        return k, v
    # quantize through the production path so scales match exactly
    return attn.quantize_pools(k, v, dtype, heads)


def _einsum_paged(q, kp, vp, table, lens, heads):
    return attn._sdpa_cache(q, *attn.paged_gather_kv(kp, vp, table), lens,
                            heads, None)


# ---------------------------------------------------------------------------
# kernel parity vs the einsum path
# ---------------------------------------------------------------------------
def test_paged_decode_parity_padded_full_wrapped():
    """tq=1 over paged pools: padded short rows, an exactly-full ring and
    a wrapped ring (page recycle: every view slot live) all match the
    gather+attend einsum path; the table deliberately SHARES pages across
    slots (prefix sharing) and repeats one page inside a slot."""
    rng = np.random.RandomState(0)
    m, pt = 4, 4
    kp, vp = _pools(rng, 1 + B * m, pt, EMBED)
    table = np.array([[1, 2, 3, 4], [2, 5, 6, 5]], np.int32)  # shared + dup
    lens = jnp.asarray([5, m * pt + 7], dtype=jnp.int32)      # padded, wrap
    q = jnp.asarray(rng.randn(B, 1, EMBED).astype(np.float32))

    out = pd.flash_sdpa_decode(q, kp, vp, jnp.asarray(table), lens,
                               num_heads=HEADS, interpret=True)
    ref = _einsum_paged(q, kp, vp, jnp.asarray(table), lens, HEADS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)

    full = jnp.asarray([m * pt, m * pt], dtype=jnp.int32)
    out2 = pd.flash_sdpa_decode(q, kp, vp, jnp.asarray(table), full,
                                num_heads=HEADS, interpret=True)
    ref2 = _einsum_paged(q, kp, vp, jnp.asarray(table), full, HEADS)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               rtol=1e-5, atol=1e-6)


def test_paged_verify_parity_k_plus_1_window():
    """tq=k+1 (the speculative verify window): each query row masks to
    its own prefix exactly like ``sdpa_verify`` over the gathered view."""
    rng = np.random.RandomState(1)
    m, pt, k = 4, 4, 3
    kp, vp = _pools(rng, 1 + B * m, pt, EMBED)
    table = jnp.asarray(rng.randint(0, 1 + B * m, size=(B, m)), jnp.int32)
    q = jnp.asarray(rng.randn(B, k + 1, EMBED).astype(np.float32))
    for lens in ([k + 2, 9], [m * pt, 7]):
        lens = jnp.asarray(lens, dtype=jnp.int32)
        out = pd.flash_sdpa_verify(q, kp, vp, table, lens,
                                   num_heads=HEADS, interpret=True)
        ref = _einsum_paged(q, kp, vp, table, lens, HEADS)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["int8", "float8_e4m3fn"])
def test_quantized_pool_parity_in_kernel_dequant(dtype):
    """int8 / fp8 pools dequantize per (token, head) INSIDE the kernel and
    match the einsum path (which dequantizes the gathered view in HBM)
    within streaming-accumulation tolerance."""
    rng = np.random.RandomState(2)
    m, pt = 4, 8
    kp, vp = _pools(rng, 1 + B * m, pt, EMBED, dtype=dtype)
    table = jnp.asarray(rng.randint(0, 1 + B * m, size=(B, m)), jnp.int32)
    lens = jnp.asarray([6, m * pt + 3], dtype=jnp.int32)
    for tq in (1, 3):
        q = jnp.asarray(rng.randn(B, tq, EMBED).astype(np.float32))
        fn = pd.flash_sdpa_decode if tq == 1 else pd.flash_sdpa_verify
        # the kernel reads a scale plane a pool: the door splits the rows
        out = fn(q, *attn._kernel_pools(kp, vp), table, lens,
                 num_heads=HEADS, interpret=True)
        ref = _einsum_paged(q, kp, vp, table, lens, HEADS)
        assert np.asarray(out).dtype == np.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_dense_ring_identity_table_parity():
    """The non-paged ring buffers ride the SAME kernel through an
    identity page table — parity with ``sdpa_decode`` incl. wrap."""
    rng = np.random.RandomState(3)
    c = 24  # not a power of two: _dense_block must still tile it
    kc = jnp.asarray(rng.randn(B, c, EMBED).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, c, EMBED).astype(np.float32))
    q = jnp.asarray(rng.randn(B, 1, EMBED).astype(np.float32))
    for lens in ([4, c], [c + 9, c + 1]):
        lens = jnp.asarray(lens, dtype=jnp.int32)
        out = pd.dense_ring_attend(q, kc, vc, lens, num_heads=HEADS,
                                   interpret=True)
        ref = attn.sdpa_decode(q, kc, vc, lens, num_heads=HEADS)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_split_k_sizing():
    """The split axis takes the largest dividing power of two <= 8 and
    degrades to 1 on odd page counts."""
    assert pd._num_splits(8) == 8
    assert pd._num_splits(6) == 2
    assert pd._num_splits(12) == 4
    assert pd._num_splits(7) == 1
    assert pd._num_splits(1) == 1


# ---------------------------------------------------------------------------
# dispatch gating
# ---------------------------------------------------------------------------
def test_dispatch_takes_kernel_and_falls_back(kernel_on):
    """``paged_attend`` takes the kernel when armed and supported
    (DECODE_PATH='pallas', same numbers as einsum), and falls back —
    visibly — for unsupported heads, under a mesh, and with the knob
    off."""
    rng = np.random.RandomState(4)
    m, pt = 4, 4
    kp, vp = _pools(rng, 1 + B * m, pt, EMBED)
    table = jnp.asarray(rng.randint(0, 1 + B * m, size=(B, m)), jnp.int32)
    lens = jnp.asarray([5, 9], dtype=jnp.int32)
    q = jnp.asarray(rng.randn(B, 1, EMBED).astype(np.float32))

    out = attn.paged_attend(q, kp, vp, table, lens, num_heads=HEADS)
    assert attn.DECODE_PATH["last"] == "pallas"
    ref = _einsum_paged(q, kp, vp, table, lens, HEADS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)

    # shapes the gate refuses (heads not dividing E, empty tables) never
    # reach the kernel
    assert not pd.supported(q.shape, kp, vp, table.shape, 3,
                            interpret=True)
    assert not pd.supported(q.shape, kp, vp, (B, 0), HEADS,
                            interpret=True)

    # a mesh-sharded pool is opaque to Pallas: fallback
    attn.paged_attend(q, kp, vp, table, lens, num_heads=HEADS,
                      mesh_active=True)
    assert attn.DECODE_PATH["last"] == "einsum"


def test_dispatch_marks_shape_gated_fallback(kernel_on, monkeypatch):
    """An ARMED dispatch whose shape gate refuses records the distinct
    'einsum-gated' marker (vs plain 'einsum' for knob-off/mesh) — the
    artifact meta uses it to withdraw the kernel promise, so a
    legitimate gated fallback (e.g. head dims off the Mosaic tile on
    TPU) is never a pallas-fallback lint error."""
    rng = np.random.RandomState(9)
    m, pt = 4, 4
    kp, vp = _pools(rng, 1 + B * m, pt, EMBED)
    table = jnp.asarray(rng.randint(0, 1 + B * m, size=(B, m)), jnp.int32)
    lens = jnp.asarray([5, 9], dtype=jnp.int32)
    q = jnp.asarray(rng.randn(B, 1, EMBED).astype(np.float32))

    monkeypatch.setattr(pd, "supported", lambda *a, **k: False)
    out = attn.paged_attend(q, kp, vp, table, lens, num_heads=HEADS)
    assert attn.DECODE_PATH["last"] == "einsum-gated"
    ref = _einsum_paged(q, kp, vp, table, lens, HEADS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=0)

    monkeypatch.setattr(pd, "supported_dense", lambda *a, **k: False)
    kc = jnp.asarray(rng.randn(B, 8, EMBED).astype(np.float32))
    attn.cache_attend(q, kc, kc, jnp.asarray([3, 3], dtype=jnp.int32),
                      num_heads=HEADS)
    assert attn.DECODE_PATH["last"] == "einsum-gated"


def test_gated_fallback_withdraws_artifact_promise(kernel_on, monkeypatch):
    """A predictor whose decode programs were shape-gated away from the
    kernel must NOT carry meta['pallas_decode'] — the flop-dtype
    tripwire targets silent regressions, not visible gate refusals."""
    from mxnet_tpu.analysis import run_passes
    from mxnet_tpu.analysis.passes import FlopDtypePass
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.models import attention_lm

    monkeypatch.setattr(pd, "supported", lambda *a, **k: False)
    sym = attention_lm.get_symbol(VOCAB, T, num_layers=1, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(10)
    arg_shapes, _, _ = sym.infer_shape(data=(B, T), softmax_label=(B, T))
    params = {n: rng.normal(0, 0.5, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    pred = DecodePredictor(sym, params, cache_len=T, temperature=0.0,
                           paged=True, page_tokens=4)
    art = pred.decode_artifact(pred.paged_batch_state(B))
    assert art.meta["pallas_decode"] is False
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert not any(f.code == "pallas-fallback" for f in rep.findings)


def test_dispatch_off_by_default():
    assert not attn.decode_kernel_mode()[0]
    rng = np.random.RandomState(5)
    kc = jnp.asarray(rng.randn(B, 8, EMBED).astype(np.float32))
    attn.cache_attend(jnp.ones((B, 1, EMBED), jnp.float32), kc, kc,
                      jnp.asarray([3, 3], dtype=jnp.int32),
                      num_heads=HEADS)
    assert attn.DECODE_PATH["last"] == "einsum"


# ---------------------------------------------------------------------------
# end-to-end: the paged speculative server, kernel on vs off
# ---------------------------------------------------------------------------
def _serve_tokens(rng_seed, arm):
    from mxnet_tpu.decode import DecodePredictor, DecodeServer
    from mxnet_tpu.models import attention_lm

    sym = attention_lm.get_symbol(VOCAB, T, num_layers=2, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(rng_seed)
    arg_shapes, _, _ = sym.infer_shape(data=(B, T), softmax_label=(B, T))
    params = {n: rng.normal(0, 0.5, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    pred = DecodePredictor(sym, params, cache_len=T, temperature=0.0,
                           paged=True, page_tokens=4, prefill_chunk=4)
    server = DecodeServer(pred, max_prefill=10, slots=B,
                          max_new_tokens=4, spec_k=2)
    prefix = rng.randint(0, VOCAB, size=(4,))
    ids = [server.submit(np.concatenate(
        [prefix, rng.randint(0, VOCAB, size=(n,))])) for n in (2, 4, 3)]
    results = server.run()
    assert attn.DECODE_PATH["last"] == ("pallas" if arm else "einsum")
    return [np.asarray(results[i]) for i in ids]


def test_paged_spec_serve_token_identical_kernel_on_off():
    """The acceptance line: the paged speculative server emits EXACTLY
    the same tokens with the fused kernel on and off (greedy serve,
    shared prefix, chunked prefill, spec verify, retirement)."""
    off = _serve_tokens(11, arm=False)
    with config.overrides(MXNET_PALLAS_DECODE="1",
                          MXNET_PALLAS_INTERPRET="1"):
        on = _serve_tokens(11, arm=True)
    assert len(on) == len(off)
    for i, (a, b) in enumerate(zip(on, off)):
        assert np.array_equal(a, b), \
            "request %d diverged: kernel-on %s vs kernel-off %s" % (i, a, b)


# ---------------------------------------------------------------------------
# pricing: the einsum path's gather view is no longer invisible
# ---------------------------------------------------------------------------
def test_gather_stats_price_paged_view():
    from mxnet_tpu.analysis.hlo_parse import stablehlo_gather_stats

    rng = np.random.RandomState(6)
    kp, _ = _pools(rng, 9, 4, EMBED)
    table = jnp.zeros((B, 4), jnp.int32)
    low = jax.jit(attn.paged_gather).lower(kp, table).as_text()
    stats = stablehlo_gather_stats(low)
    view_bytes = B * 4 * 4 * EMBED * 4
    assert stats["count"] >= 1
    assert stats["bytes"] >= 2 * view_bytes  # write + re-read floor


def test_program_cost_attn_bytes_drop_with_kernel():
    """program_cost over the real paged decode-step program: the fused
    path's priced attention bytes (pool pass + gathers) are <= 0.5x the
    einsum path's — the mfu_table row the ISSUE-11 acceptance pins."""
    from mxnet_tpu.analysis.cost import program_cost
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.models import attention_lm

    sym = attention_lm.get_symbol(VOCAB, T, num_layers=1, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(7)
    arg_shapes, _, _ = sym.infer_shape(data=(B, T), softmax_label=(B, T))
    params = {n: rng.normal(0, 0.5, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}

    def price(arm):
        val = "1" if arm else None
        with config.overrides(MXNET_PALLAS_DECODE=val,
                              MXNET_PALLAS_INTERPRET=val):
            pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                                   page_tokens=4)
            state = pred.paged_batch_state(B)
            tables, active = pred._paged_probe_args(state)
            pred._probing = True
            try:
                cost = program_cost(
                    pred._decode_fn,
                    (pred._env, state, tables, active,
                     jax.random.PRNGKey(0)))
            finally:
                pred._probing = False
            return pred.pool_bytes() + cost["gather_bytes"], cost

    attn_einsum, ce = price(False)
    attn_fused, cf = price(True)
    assert ce["gather_bytes"] > cf["gather_bytes"]
    assert attn_fused <= 0.5 * attn_einsum, \
        "fused attention bytes %d not <= 0.5x einsum %d" \
        % (attn_fused, attn_einsum)
    assert cf["bytes"] < ce["bytes"]


# ---------------------------------------------------------------------------
# the artifact-level lint tripwire
# ---------------------------------------------------------------------------
def test_flop_pass_pallas_tripwire(kernel_on):
    """A decode artifact built under MXNET_PALLAS_DECODE carries the
    promise; the flop-dtype pass blesses a program with a pallas_call and
    errors on one that silently fell back to einsum."""
    from mxnet_tpu.analysis import run_passes
    from mxnet_tpu.analysis.artifact import ProgramArtifact
    from mxnet_tpu.analysis.passes import FlopDtypePass
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.models import attention_lm

    sym = attention_lm.get_symbol(VOCAB, T, num_layers=1, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(8)
    arg_shapes, _, _ = sym.infer_shape(data=(B, T), softmax_label=(B, T))
    params = {n: rng.normal(0, 0.5, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    pred = DecodePredictor(sym, params, cache_len=T, temperature=0.0,
                           paged=True, page_tokens=4)
    state = pred.paged_batch_state(B)
    art = pred.decode_artifact(state)
    assert art.meta["pallas_decode"] is True
    assert "pallas_call" in art.jaxpr_text
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert any(f.code == "pallas-decode" for f in rep.findings)
    assert not any(f.code == "pallas-fallback" for f in rep.findings)

    # a program that PROMISED the kernel but lowered einsum: lint error
    fallback = ProgramArtifact(
        name="paged_decode_step", jaxpr_text="no kernels here",
        stablehlo_text="", compiled_text="HloModule stub\n",
        meta={"pallas_decode": True})
    rep = run_passes([fallback], passes=[FlopDtypePass()])
    assert any(f.code == "pallas-fallback" for f in rep.errors)


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
