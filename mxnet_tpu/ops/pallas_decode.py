"""Pallas flash-decoding kernels over paged KV pools (TPU) — gather +
dequant + attention fused into ONE HBM pass.

The serving path's einsum formulation walks the largest tensor in the
system twice per generated token: ``ops.attention.paged_gather``
materializes a full (B, M*pt, E) dense-ring view of the shared page pool
in HBM, and ``sdpa_decode`` streams that view again for the score/value
matmuls (an int8/fp8 view as it is stored; no f32 copy is built).
Decode attention is bandwidth-bound on exactly those bytes, so the two
passes ARE the step time.

These kernels implement the two fixes the literature names, together:

* **PagedAttention** (Kwon et al., SOSP 2023): the page-table gather
  moves *inside* the kernel.  The (B, M) table rides in as a
  scalar-prefetch argument (``pltpu.PrefetchScalarGridSpec``) and every
  pool BlockSpec's index map reads it — ``(table[b, m], 0, h)`` — so each
  grid step DMAs one page's one head-slice straight from the pool.  No
  gathered view, no dequantized copy: int8/fp8 pages dequantize in VMEM
  (per-(token, head) scales, a (P, page_tokens, H) plane a pool: the
  paged pools' shared plane of rows is split into two such at this
  kernel's door, ``ops.attention._kernel_pools``) on their way into the
  score matmul.
* **Flash-Decoding** (Dao et al., 2023): the grid parallelizes over the
  CACHE-LENGTH axis, not just (batch, head).  At decode (tq=1) with
  batch = serving slots, a (B, H) grid strands the chip when B*H is
  small; a split-K axis of S splits walks M/S pages each, maintaining
  the running (max, sum, acc) flash softmax per split, and a small
  cross-split logsumexp combine (host-side jnp over (B, H, S, tq)-shaped
  partials — tiny) reduces them exactly.  The (b, h, s) grid prefix is
  marked ``parallel`` toward Mosaic (each instance owns its scratch
  lifetime) so it fans across cores; only the within-split page walk
  ``ms`` is ``arbitrary`` (sequential softmax accumulation).

Three entry points share one kernel core:

* :func:`flash_sdpa_decode` — tq == 1, the decode hot path;
* :func:`flash_sdpa_verify` — tq == k+1, the speculative verify window
  (and the chunked-prefill window: any tq with per-query length masks);
* :func:`dense_ring_attend` — the non-paged ring buffers take the same
  kernel through an identity page table: a (B, C, E) cache reshapes
  (free, row-major split) into a (B*Mb, bs, E) pool and
  ``table[b, m] = b*Mb + m``.

All are length-masked and wrap-aware exactly like
``ops.attention._sdpa_cache``: query i of a window whose total appended
length is ``total`` sees view slots v < min(total - (tq-1) + i, C), so a
wrapped ring (total > C) attends all C live slots.  Numerics follow the
einsum path (f32 logits, f32 softmax accumulation); streaming
accumulation reorders the sums, so parity is tolerance-tested
(documented in docs/inference.md), not bit-asserted.

Dispatch lives in ``ops.attention.paged_attend`` / ``cache_attend``,
gated by ``MXNET_PALLAS_DECODE`` with shape fallback to the einsum path;
``interpret=True`` runs the same kernels on CPU (the tier-1 parity
suite, tests/test_pallas_decode.py).
"""
from __future__ import annotations

import functools

import numpy as np

# Split-K sizing: at most MAX_SPLITS splits over the view's M pages (the
# largest power of two <= min(M, MAX_SPLITS) dividing M).  More splits =
# more cross-core parallelism on the cache-length axis but more combine
# partials; 8 covers a v5e megacore with headroom.
MAX_SPLITS = 8
# Residual lane width for the per-split (max, sum) partials — matches the
# (rows, lanes) layout pallas_attention.py uses for its logsumexp
# residuals, so no kernel ever writes a 1-lane vector.
LANES = 128
# TPU (non-interpret) gates: Mosaic wants the lane (last) dim a multiple
# of 128 and the sublane dim a multiple of 8; interpret mode has no tile
# constraints and takes any positive shape.
_TPU_LANE = 128
_TPU_SUBLANE = 8


def _num_splits(m, cap=None, groups=1):
    """Largest power-of-two split count <= min(m, cap) that divides m
    (1 when m is odd — the split axis degrades gracefully).  ``cap``
    defaults to the tuning cache's ``max_splits`` for this view width
    (the :data:`MAX_SPLITS` constant when cold and no sweep armed)."""
    if cap is None:
        cap = _tuned_split_cap(m, groups=groups)
    s = 1
    while s * 2 <= min(m, cap) and m % (s * 2) == 0:
        s *= 2
    return s


_STALE_GROUP_CHECKED = set()


def _tuned_split_cap(m, groups=1):
    from . import tuning

    # split width is a parallelism knob, not a dtype-layout one: one
    # decision per view width serves every pool dtype
    if groups <= 1:
        return int(tuning.resolve("pallas_decode",
                                  tuning.shape_class_for(m=m),
                                  "any").get("max_splits", MAX_SPLITS))
    # grouped K/V shapes get their own content-addressed tune key (the
    # kv-head group class rides in the shape class) so a GQA sweep never
    # collides with an MHA winner for the same view width
    sc = tuning.shape_class_for(m=m, g=groups)
    if sc not in _STALE_GROUP_CHECKED:
        _STALE_GROUP_CHECKED.add(sc)
        mha_sc = tuning.shape_class_for(m=m)
        if (tuning.get("pallas_decode", sc, "any", version=1) is None
                and tuning.get("pallas_decode", mha_sc, "any",
                               version=1) is not None):
            import warnings

            warnings.warn(
                "tuning cache holds an MHA-keyed pallas_decode record for "
                "m=%d but the shape is grouped (G=%d); the MHA winner "
                "does not apply — treating as a miss" % (m, groups))
    return int(tuning.resolve("pallas_decode", sc,
                              "any").get("max_splits", MAX_SPLITS))


def _is_quant(pool):
    from .attention import QuantKV

    return isinstance(pool, QuantKV)


def supported(q_shape, k_pool, v_pool, table_shape, num_heads,
              interpret=False, num_kv_heads=0):
    """Whether the fused kernel handles this paged-decode shape.

    Correctness constraints always: heads divide both embed dims and the
    (quantized) scale planes carry exactly the K/V head count.  Grouped
    configs (``num_kv_heads < num_heads``) require the pools to be
    physically H_kv heads wide — the kernel maps q-head h to pool slice
    ``h // G``.  On a real TPU (``interpret=False``) the Mosaic tile
    constraints add: per-head dims and page_tokens aligned to the
    (8, 128) tile.  Anything else falls back to the einsum path — same
    numerics, three HBM passes.
    """
    kd = k_pool.data if _is_quant(k_pool) else k_pool
    vd = v_pool.data if _is_quant(v_pool) else v_pool
    b, tq, e = q_shape
    kvh = int(num_kv_heads) or int(num_heads)
    if num_heads <= 0 or kvh <= 0 or num_heads % kvh:
        return False
    if e % num_heads or vd.shape[2] % kvh:
        return False
    if kd.shape[2] != kvh * (e // num_heads):
        return False
    if _is_quant(k_pool) and k_pool.scale.shape[-1] != kvh:
        return False
    if _is_quant(v_pool) and v_pool.scale.shape[-1] != kvh:
        return False
    pt = kd.shape[1]
    if pt <= 0 or table_shape[1] <= 0:
        return False
    if not interpret:
        hd_k = e // num_heads
        hd_v = vd.shape[2] // kvh
        if hd_k % _TPU_LANE or hd_v % _TPU_LANE:
            return False
        if pt % _TPU_SUBLANE:
            return False
    return True


def _kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *, scale, tq,
            page_tokens, pages_per_split, view_pages, quant, group):
    """One (b, h, s, ms) grid step: fold page ``s*pages_per_split + ms``
    of slot b's view into the running flash softmax for head h.

    ``ks_ref``/``vs_ref`` are the per-(token, head) scale pages of a
    quantized pool (None otherwise) — dequantization happens HERE, on
    the (pt, hd) tile in VMEM, never in HBM.  A scale page arrives with
    ALL its kv heads — Mosaic refuses a 1-wide lane block of the
    (P, pt, H_kv) plane — and this head's column is picked by a one-hot
    reduction (``group`` q-heads share kv-head ``h // group``).  At the
    split's last page
    the UNNORMALIZED partial (acc, max, sum) is written out; the caller
    combines splits with a logsumexp reduction.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    kv_head = pl.program_id(1) // group
    ms = pl.program_id(3)
    nms = pl.num_programs(3)
    s = pl.program_id(2)

    @pl.when(ms == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    m_view = s * pages_per_split + ms          # view page index in [0, M)
    total = lens_ref[b]
    cap = view_pages * page_tokens             # C, the ring capacity
    visible = jnp.minimum(total, cap)          # live view slots

    def _update():
        q = q_ref[0, 0].astype(jnp.float32)                 # (tq, hd_k)
        k = k_ref[0].astype(jnp.float32)                    # (pt, hd_k)
        v = v_ref[0].astype(jnp.float32)                    # (pt, hd_v)
        if quant:
            def head_scale(ref):                            # -> (pt, 1)
                page = ref[0]                               # (pt, H_kv)
                col = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
                return jnp.sum(jnp.where(col == kv_head, page, 0.0),
                               axis=1, keepdims=True)

            k = k * head_scale(ks_ref)
            v = v * head_scale(vs_ref)
        logits = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        # view slot v = m_view*pt + j; query i sees v < min(total-(tq-1)+i, C)
        vpos = m_view * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (tq, page_tokens), 1)
        limit = jnp.minimum(
            total - (tq - 1) + jax.lax.broadcasted_iota(
                jnp.int32, (tq, page_tokens), 0), cap)
        logits = jnp.where(vpos < limit, logits, -jnp.inf)

        m_prev = m_scr[:, :1]                               # (tq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(logits - m_safe)
        p = jnp.where(logits == -jnp.inf, 0.0, p)
        corr = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))
        l_scr[:] = l_scr[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    # pages wholly past the live window contribute nothing — skip their
    # compute entirely (their DMA still lands, the index map ran)
    @pl.when(m_view * page_tokens < visible)
    def _masked_update():
        _update()

    @pl.when(ms == nms - 1)
    def _finish():
        acc_ref[0, 0, 0] = acc_scr[:]
        m_ref[0, 0, 0] = m_scr[:]
        l_ref[0, 0, 0] = l_scr[:]


def _paged_flash_call(q, k_pool, v_pool, table, lens, num_heads, scale,
                      interpret, split_cap=None, num_kv_heads=0):
    """Launch the kernel and combine split partials; returns (B, tq, Ev)
    in the V pool's compute dtype (f32 for quantized pools, matching the
    einsum path's dequantized output).

    Grouped pools (``num_kv_heads < num_heads``) keep the (b, h, s, ms)
    q-head grid; the pool/scale BlockSpec index maps gather ONE kv-head
    slice per G q-heads (``hi // G`` — the group id), so the pool is
    never widened to H_q."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quant = _is_quant(k_pool)
    kd = k_pool.data if quant else k_pool
    vd = v_pool.data if quant else v_pool
    b, tq, e = q.shape
    h = num_heads
    kvh = int(num_kv_heads) or int(h)
    g = h // kvh
    hd_k = e // h
    hd_v = vd.shape[2] // kvh
    pt = kd.shape[1]
    m = table.shape[1]
    s = _num_splits(m, split_cap, groups=g)
    ms = m // s
    scale = float(scale or 1.0 / np.sqrt(hd_k))

    qh = q.reshape(b, tq, h, hd_k).transpose(0, 2, 1, 3)  # (B, H, tq, hd)
    table = jnp.asarray(table, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(
        _kernel, scale=scale, tq=tq, page_tokens=pt, pages_per_split=ms,
        view_pages=m, quant=quant, group=g)

    # index maps: every pool block is one page's one head-slice, located
    # through the scalar-prefetched table — the in-kernel gather
    def _q_map(bi, hi, si, mi, tr, lr):
        return (bi, hi, 0, 0)

    if g == 1:
        def _page_map(bi, hi, si, mi, tr, lr):
            return (tr[bi, si * ms + mi], 0, hi)
    else:
        # pool blocks keyed by GROUP id: q-heads hi in [gi*G, (gi+1)*G)
        # all DMA kv-head slice gi = hi // G of the physically-grouped pool
        def _page_map(bi, hi, si, mi, tr, lr):
            return (tr[bi, si * ms + mi], 0, hi // g)

    def _out_map(bi, hi, si, mi, tr, lr):
        return (bi, hi, si, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, tq, hd_k), _q_map),
        pl.BlockSpec((1, pt, hd_k), _page_map),
        pl.BlockSpec((1, pt, hd_v), _page_map),
    ]
    args = [qh, kd, vd]
    if quant:
        def _scale_map(bi, hi, si, mi, tr, lr):
            return (tr[bi, si * ms + mi], 0, 0)

        in_specs += [pl.BlockSpec((1, pt, kvh), _scale_map)] * 2
        args += [k_pool.scale, v_pool.scale]
    else:
        # keep ONE kernel signature: unquantized pools ride a zero-cost
        # dummy scale page (never read — quant=False skips it)
        dummy = jnp.zeros((1, pt, 1), jnp.float32)
        in_specs += [pl.BlockSpec((1, pt, 1),
                                  lambda bi, hi, si, mi, tr, lr: (0, 0, 0))] \
            * 2
        args += [dummy, dummy]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, s, ms),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, tq, hd_v), _out_map),
            pl.BlockSpec((1, 1, 1, tq, LANES), _out_map),
            pl.BlockSpec((1, 1, 1, tq, LANES), _out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, LANES), jnp.float32),   # running max
            pltpu.VMEM((tq, LANES), jnp.float32),   # running sum
            pltpu.VMEM((tq, hd_v), jnp.float32),    # output accumulator
        ],
    )
    # (b, h, s) are independent — each owns its scratch lifetime via the
    # ms==0 init — so Mosaic may fan them across cores (the split-K
    # parallelism that fills the chip at batch=slots); only ms, the
    # running-softmax accumulation over a split's pages, is sequential.
    # Without this, all four grid dims default to 'arbitrary' and the
    # whole grid serializes on one core.
    acc, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, tq, hd_v), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, tq, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, tq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(table, lens, *args)

    # cross-split logsumexp combine (Flash-Decoding's reduction): tiny
    # (B, H, S, tq)-shaped partials, exact in f32
    m_p = m_p[..., 0]                                   # (B, H, S, tq)
    l_p = l_p[..., 0]
    m_star = jnp.max(m_p, axis=2, keepdims=True)
    m_star = jnp.where(m_star == -jnp.inf, 0.0, m_star)
    alpha = jnp.where(m_p == -jnp.inf, 0.0, jnp.exp(m_p - m_star))
    l_tot = jnp.sum(alpha * l_p, axis=2)                # (B, H, tq)
    acc = jnp.sum(alpha[..., None] * acc, axis=2)       # (B, H, tq, hd_v)
    denom = jnp.where(l_tot == 0.0, 1.0, l_tot)
    out = acc / denom[..., None]
    out = out.transpose(0, 2, 1, 3).reshape(b, tq, h * hd_v)
    out_dtype = jnp.float32 if quant else vd.dtype
    return out.astype(out_dtype)


def flash_sdpa_decode(q, k_pool, v_pool, table, total_len, num_heads=1,
                      scale=None, interpret=False, split_cap=None,
                      num_kv_heads=0):
    """Fused paged decode attention: (B, 1, E) queries over (P, pt, E_kv)
    pools through (B, M) page tables -> (B, 1, Ev).

    ``total_len`` counts tokens appended INCLUDING the query position
    (the ``sdpa_decode`` contract); once the view ring has wrapped
    (total > M*pt) every slot is live.  Pools may be
    :class:`~mxnet_tpu.ops.attention.QuantKV` — dequantized per
    (token, kv-head) in VMEM.  One HBM pass over the live pool pages;
    grouped pools (``num_kv_heads``) are walked once per kv head group.
    """
    return _paged_flash_call(q, k_pool, v_pool, table, total_len,
                             num_heads, scale, interpret,
                             split_cap=split_cap,
                             num_kv_heads=num_kv_heads)


def flash_sdpa_verify(q, k_pool, v_pool, table, total_len, num_heads=1,
                      scale=None, interpret=False, split_cap=None,
                      num_kv_heads=0):
    """Fused paged multi-position cache attention — the speculative
    verify window (tq = k+1) and the chunked-prefill window (tq = chunk
    width) share it.  Query i masks to view slots
    v < min(total - (tq-1) + i, C), exactly ``sdpa_verify``'s rule, so
    each output row equals what a sequential decode chain would produce.
    """
    return _paged_flash_call(q, k_pool, v_pool, table, total_len,
                             num_heads, scale, interpret,
                             split_cap=split_cap,
                             num_kv_heads=num_kv_heads)


def _dense_block(c, pt_pref=128):
    """Page size for the dense-ring identity view: the largest
    power-of-two <= min(c, pt_pref) dividing c."""
    bs = min(pt_pref, c)
    while c % bs:
        bs //= 2
    return bs


class _Shape:
    """Shape/dtype carrier so the paged ``supported`` gate can vet a
    dense ring's pool view without reshaping real arrays."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.dtype = dtype


def supported_dense(q_shape, k_cache, v_cache, num_heads, interpret=False,
                    num_kv_heads=0):
    """Whether the dense-ring variant handles these cache shapes: the
    (B, C, E) ring must tile into identity pages the paged gate accepts."""
    from .attention import QuantKV

    kd = k_cache.data if _is_quant(k_cache) else k_cache
    c = kd.shape[1]
    bs = _dense_block(c)
    if bs < 1:
        return False
    mb = c // bs

    def as_pool(cache):
        if _is_quant(cache):
            return QuantKV(as_pool(cache.data), as_pool(cache.scale))
        return _Shape((cache.shape[0] * mb, bs, cache.shape[2]),
                      cache.dtype)

    return supported(q_shape, as_pool(k_cache), as_pool(v_cache),
                     (q_shape[0], mb), num_heads, interpret=interpret,
                     num_kv_heads=num_kv_heads)


def dense_ring_attend(q, k_cache, v_cache, total_len, num_heads=1,
                      scale=None, interpret=False, num_kv_heads=0):
    """The dense-ring variant: run the SAME fused kernel over a non-paged
    (B, C, E) ring buffer through an identity page table.

    The ring reshapes (free: a row-major split of C into Mb pages of bs
    tokens) into a (B*Mb, bs, E) pool and ``table[b, m] = b*Mb + m``;
    split-K then parallelizes the plain KV-cached decode path over cache
    length too.  Length masks/wrap behave exactly like ``_sdpa_cache``.
    """
    import jax.numpy as jnp

    from .attention import QuantKV

    kd = k_cache.data if _is_quant(k_cache) else k_cache
    b, c = kd.shape[0], kd.shape[1]
    bs = _dense_block(c)
    mb = c // bs

    def as_pool(cache):
        if _is_quant(cache):
            return QuantKV(as_pool(cache.data), as_pool(cache.scale))
        return cache.reshape(b * mb, bs, cache.shape[2])

    table = (jnp.arange(b, dtype=jnp.int32)[:, None] * mb
             + jnp.arange(mb, dtype=jnp.int32)[None, :])
    return _paged_flash_call(q, as_pool(k_cache), as_pool(v_cache), table,
                             total_len, num_heads, scale, interpret,
                             num_kv_heads=num_kv_heads)


# ---------------------------------------------------------------------------
# tunable space (ops/tuning.py): split-K width per view-width class
# ---------------------------------------------------------------------------

def _tuning_candidates(shape_class, interpret):
    if interpret:
        # 2-candidate toy space for the tier-1 CPU sweep
        return [{"max_splits": 2}, {"max_splits": 4}]
    return [{"max_splits": c} for c in (1, 2, 4, 8, 16)]


def _tuning_runner(params, shape_class, dtype, interpret):
    import jax
    import jax.numpy as jnp

    from . import tuning

    m = tuning.parse_shape_class(shape_class).get("m", 8)
    cap = params["max_splits"]
    if cap > m:
        raise tuning.SpaceError("max_splits %d exceeds view width m=%d"
                                % (cap, m))
    dt = jnp.float32 if dtype == "any" else jnp.dtype(dtype)
    pt, e, b = 16, 128, 4
    rng = jax.random.PRNGKey(0)
    kp = jax.random.normal(rng, (b * m + 1, pt, e), dt)
    vp = jax.random.normal(jax.random.fold_in(rng, 1), (b * m + 1, pt, e),
                           dt)
    q = jax.random.normal(jax.random.fold_in(rng, 2), (b, 1, e), dt)
    table = (jnp.arange(b * m, dtype=jnp.int32).reshape(b, m) + 1)
    lens = jnp.full((b,), m * pt, jnp.int32)

    @jax.jit
    def probe(q, kp, vp, table, lens):
        # explicit split_cap: the sweep must not re-enter resolve()
        return flash_sdpa_decode(q, kp, vp, table, lens, num_heads=1,
                                 interpret=interpret, split_cap=cap)

    def run():
        jax.block_until_ready(probe(q, kp, vp, table, lens))

    return run


def _register_space():
    from . import tuning

    tuning.register_space(
        "pallas_decode", version=1,
        defaults={"max_splits": MAX_SPLITS},
        constants=("MAX_SPLITS",),
        candidates=_tuning_candidates, runner=_tuning_runner)


_register_space()
