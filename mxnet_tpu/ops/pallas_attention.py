"""Pallas flash-attention kernels (TPU) — forward AND backward.

The hot-op kernel the einsum formulation can't match at long sequence:
``ops.attention.sdpa`` materializes the (T, T) logits in HBM — O(T²)
memory traffic — while these kernels stream K/V blocks through VMEM with
a running (max, sum, acc) softmax, O(T) memory, logits never leaving the
chip (flash-attention schedule; same numerics as the streaming
accumulator in ``parallel/ring.py``, here at the kernel level).

``flash_attention`` is differentiable: a ``jax.custom_vjp`` pairs the
forward kernel (which saves a per-row logsumexp residual) with one
backward kernel that recomputes each tile's probabilities from the
residual, once, and accumulates dQ, dK and dV from them (two kernels, one
for dQ and one for dK/dV, where K/V are grouped or one head's dQ outgrows
VMEM).  This is the TPU analog of the
reference's fused-kernel-that-trains precedent (its cuDNN RNN op
implements forward *and* backward in one fused device kernel,
``src/operator/cudnn_rnn-inl.h``): long-context *training* runs the fast
path, not just inference.

What a tile costs on the chip (TPU v5 lite, PR 31): with heads of 64 the
MXU runs these matmuls at half width, and beside it a tile pays per ROW for
every cross-lane reduction — so the forward sums its probabilities lane by
lane and reduces them once a query block, tiles are as tall and wide as
VMEM holds, and the tile loop runs inside the kernel, its bounds cut to the
causal diagonal (the mask is built only on tiles the diagonal crosses).

The fused backward works on TRANSPOSED score tiles (keys down the
sublanes, queries along the lanes): dK and dV are plain matmuls, dQ is the
one matmul with a transposed left operand, and the per-row residuals
(logsumexp, and delta = rowsum(dO·O)) are read as lane-major rows — so the
forward writes its logsumexp that way, (BH, T) float32, a 128th of the
(BH, T, LANES) broadcast the ring and the two-kernel backward still read.

Used by ``dot_product_attention`` where ``ops.attention.flash_selected``
says the call's shape wins with it (a TPU, no mesh, a supported shape, T at
or past the measured crossover for the head width); anything else takes the
einsum path.  ``interpret=True`` runs the same kernels on CPU for tests.
"""
from __future__ import annotations

import functools

import numpy as np

from ..obs.startup import pallas as _pallas

# Block sizes: constants, swept by hand on the chip.  A block is one TILE of
# scores; the kernels loop over tiles inside a grid step that holds
# MAJOR_ROWS rows of K/V (forward) or of Q/dO (backward).  Measured on TPU
# v5 lite, jax 0.9.0, bf16 causal, 8192 tokens, forward / backward in ms at
# (B, T) =
# (4, 2048) | (8, 1024) | (16, 512)
# (benchmarks/bench_flash_attention.py --crossover, PR 31):
#   32 heads of 64:  2.245 / 3.514 | 1.792 / 2.577 | 1.487 / 2.030
#                    (PR 26's kernels, timed alike: 2.99 / 6.51 | 1.99 / 4.19 | 1.61 / 3.21)
#   16 heads of 128: 0.889 / 1.395 | 0.676 / 0.890 | 0.512 / 0.616
#                    (PR 26's: 1.28 / 2.67 | 0.765 / 1.60 | 0.573 / 0.986)
# Swept at 32 heads of 64, (4, 2048) | (8, 1024), forward (block_q, block_k):
#   (512, 512) 2.46 | 1.82   (512, 1024) 2.30 | 1.84   (1024, 512) 2.59 | 1.99
#   (1024, 1024) 2.25 | 1.80   (256, 1024) 2.65 | 2.08
# backward (block_q_bwd, block_k_bwd), fused:
#   (256, 512) 3.69 | 2.68   (512, 512) 3.52 | 2.58   (512, 1024) 3.87 | 2.91
#   (1024, 512) 3.90 | 2.99   (256, 1024) 3.96 | 2.97
# Heads of 128 rank the same.  A tile pays per row as well as per score, so
# the tallest and widest tile VMEM holds wins the forward, though at T 1024
# it covers the whole square; the backward has no per-row reduction and
# takes the finer causal skip of 512 x 512.
BLOCK_Q = 1024
BLOCK_K = 1024
BLOCK_Q_BWD = 512
BLOCK_K_BWD = 512
# rows of K/V (forward) or Q/dO (backward) one grid step holds: what a DMA
# moves, not a tile of scores
MAJOR_ROWS = 2048
# the fused backward holds one head's dQ in VMEM, a (T, D) float32
# accumulator and the output block, double-buffered: at most this much
FUSED_BWD_BYTES = 8 << 20
LANES = 128
MIN_BLOCK = 8


def _pick_block(pref, t):
    """Largest power-of-two shrink of ``pref`` that divides ``t``, or 0
    when the shrink degenerates below :data:`MIN_BLOCK` (odd/prime T
    used to walk all the way to a pathological 1-row kernel, and a prime
    T <= pref used to come back verbatim as a tile-misaligned full-T
    block) — callers treat 0 as "unsupported, take the einsum path"."""
    b = min(pref, t)
    b = 1 << (b.bit_length() - 1)   # power-of-two floor, never t itself
    while b >= MIN_BLOCK and t % b:
        b //= 2
    return b if b >= MIN_BLOCK and t % b == 0 else 0


def _out_sds(shape, dtype, *inputs):
    """ShapeDtypeStruct for a pallas output, carrying the union of the
    inputs' varying-mesh-axes (vma) when tracing inside shard_map — the
    ring path calls these kernels per-device with 'seq'-varying blocks,
    and shard_map's vma checking requires outputs to declare it."""
    import jax

    try:
        vma = frozenset().union(*[jax.typeof(a).vma for a in inputs])
    except (AttributeError, TypeError):
        vma = frozenset()
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _lane_tile(x, n):
    """(rows, LANES) residual with all lanes equal -> (rows, n)."""
    import jax.numpy as jnp

    if n == LANES:
        return x
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :n]


def _scaled(x, scale):
    """``x * scale`` in ``x``'s dtype, through float32 (v5e's vector unit has
    no bf16): the softmax scale folded into an operand of the score matmul,
    a multiply per element of Q or K instead of one per score."""
    import jax.numpy as jnp

    return (x.astype(jnp.float32) * jnp.float32(scale)).astype(x.dtype)


def _scores(a, b):
    """``a @ b.T`` accumulated in float32 (the MXU takes a transposed right
    operand as it is)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tile_loop(lo, hi, update):
    """``update(u)`` for tile ``u`` in [lo, hi); the bounds may be traced."""
    import jax

    def body(u, carry):
        update(u)
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal, block_q,
            block_k, with_lse=False):
    """One (query tile, major K/V block) grid step: a loop over the major
    block's key tiles.  Under ``causal`` the loop stops at the diagonal and
    only the tiles it crosses build a mask; tile 0 of block 0 holds key 0,
    which every row sees, so the running maximum is finite from the first
    update on and nothing guards against -inf."""
    import jax
    import jax.numpy as jnp
    pl = _pallas()[0]

    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest

    major = k_ref.shape[1]
    ntiles = major // block_k
    jm = pl.program_id(2)
    row0 = pl.program_id(1) * block_q
    col0 = jm * major

    @pl.when(jm == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = _scaled(q_ref[0], scale)                    # (BQ, D)

    def update(u, masked):
        c = pl.multiple_of(u * block_k, block_k)
        k = k_ref[0, pl.ds(c, block_k), :]          # (BK, D)
        v = v_ref[0, pl.ds(c, block_k), :]
        s = _scores(q, k)
        if masked:
            qi = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kj = col0 + c + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qi >= kj, s, -jnp.inf)
        m_prev = m_scr[:, :1]                       # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        # the row sum stays spread over the lanes (vector adds only); the
        # cross-lane reduction, which costs per row, runs once in _finish
        lanes = l_scr.shape[1]
        p_lanes = p[:, :lanes]
        for n in range(1, block_k // lanes):
            p_lanes = p_lanes + p[:, n * lanes:(n + 1) * lanes]
        l_scr[:] = l_scr[:] * corr + p_lanes
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    if causal:
        # tiles [0, n_clear) lie wholly under the diagonal, [n_clear, n_run)
        # are crossed by it, the rest see nothing
        n_run = jnp.clip((row0 - col0 + block_q - 1) // block_k + 1, 0, ntiles)
        n_clear = jnp.clip((row0 - col0 + 1) // block_k, 0, ntiles)
        _tile_loop(0, n_clear, lambda u: update(u, False))
        _tile_loop(n_clear, n_run, lambda u: update(u, True))
    else:
        _tile_loop(0, ntiles, lambda u: update(u, False))

    @pl.when(jm == pl.num_programs(2) - 1)
    def _finish():
        denom = jnp.sum(l_scr[:], axis=1, keepdims=True)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            # (BQ, LANES) with all lanes equal -> one (1, BQ) row
            lse_ref[0, 0] = (m_scr[:] + jnp.log(denom)).T[:1]


@functools.lru_cache(maxsize=None)
def _traced_once(fn):
    """``fn`` under ``jax.jit``, its keyword-only arguments static.  A
    model's N layers call the kernels with one set of shapes; jit's cache
    then traces and lowers them once instead of N times (set-up time: the
    4 layers of one LM step trace + lower in 0.08 s instead of 0.35 s on
    the sandbox's CPU).  XLA inlines the call: the program is the same."""
    import inspect

    import jax

    return jax.jit(
        fn, static_argnames=inspect.getfullargspec(fn).kwonlyargs)


def _fwd_call(q, k, v, scale, causal, interpret, with_lse, block_q=None,
              block_k=None, groups=1):
    """``(out, lse)`` with the logsumexp broadcast across a 128-lane minor
    dimension, ``(BH, T, LANES)`` — the ring's format (it reads lane 0 and
    XLA folds the broadcast away); the custom_vjp keeps the (BH, T) rows
    of :func:`_fwd_rows` as its residual."""
    import jax.numpy as jnp

    out, lse = _fwd_rows(q, k, v, scale, causal, interpret, with_lse,
                         block_q, block_k, groups)
    if lse is not None:
        lse = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    return out, lse


def _fwd_rows(q, k, v, scale, causal, interpret, with_lse, block_q=None,
              block_k=None, groups=1):
    bh, t, d = q.shape
    g = int(groups)
    if k.shape[0] * g != bh:
        raise ValueError(
            "flash_attention fwd: folded K/V batch %d * groups=%d != "
            "folded Q batch %d" % (k.shape[0], g, bh))
    bq = _pick_block(block_q or BLOCK_Q, t)
    bk = _pick_block(block_k or BLOCK_K, t)
    if not bq or not bk:
        raise ValueError("flash_attention fwd blocks degenerate for T=%d "
                         "(callers must gate on supported())" % t)
    return _traced_once(_fwd_kernel)(
        q, k, v, scale=scale, causal=causal, interpret=interpret,
        with_lse=with_lse, bq=bq, bk=bk, major=_major(bk, t), g=g)


def _major(block, t):
    """Rows of the streamed operand one grid step holds: MAJOR_ROWS shrunk
    to divide ``t``, and never less than one tile."""
    return max(_pick_block(MAJOR_ROWS, t), block)


def _fwd_kernel(q, k, v, *, scale, causal, interpret, with_lse, bq, bk,
                major, g):
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    bh, t, d = q.shape
    grid = (bh, t // bq, t // major)

    # grouped K/V: folded Q batch index b encodes (batch, q-head) as
    # b = batch*H + h, so its kv block lives at folded index
    # batch*H_kv + h//G == b // G — the h // G group map, in the
    # BlockSpec index map (never a materialized broadcast).  A major block
    # wholly above the diagonal names the last one that is not: the
    # pipeline fetches nothing for a step whose block is already there.
    if causal:
        def kv_map(b, i, jm):
            return (b // g, jnp.minimum(jm, (i * bq + bq - 1) // major), 0)
    else:
        def kv_map(b, i, jm):
            return (b // g, jm, 0)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, with_lse=with_lse)
    out_shape = [_out_sds(q.shape, q.dtype, q, k, v)]
    out_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))]
    if with_lse:
        # one lane-major (1, bq) row of logsumexp a query tile: (BH, T) in
        # memory, what the fused backward reads as it is
        out_shape.append(
            _out_sds((bh, t // bq, 1, bq), jnp.float32, q, k, v))
        out_specs.append(
            pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)))
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, major, d), kv_map),
            pl.BlockSpec((1, major, d), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max, all lanes
            pltpu.VMEM((bq, min(bk, LANES)), jnp.float32),  # sum, by lane
            pltpu.VMEM((bq, d), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return (res[0], res[1].reshape(bh, t)) if with_lse else (res[0], None)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, dq_ref,
                dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, scale, causal,
                block_q, block_k):
    """One (key block, major Q/dO block) grid step of the fused backward: a
    loop over the major block's query tiles, each tile's p and ds computed
    once and used for dQ, dK and dV.  Tiles are TRANSPOSED, (BK, BQ): dK and
    dV are plain matmuls and dQ the one with a transposed left operand.
    dK/dV accumulate over a key block's grid steps; dQ over the whole head,
    in a (T, D) float32 scratch written out on the head's last step."""
    import jax
    import jax.numpy as jnp
    pl = _pallas()[0]

    major = q_ref.shape[1]
    ntiles = major // block_q
    j = pl.program_id(1)
    im = pl.program_id(2)
    last_im = im == pl.num_programs(2) - 1
    col0 = j * block_k
    row0 = im * major

    @pl.when((j == 0) & (im == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(im == 0)
    def _init_dkv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    v = v_ref[0]                                    # (BK, D)
    ks = _scaled(k_ref[0], scale)                   # scores AND dQ take it

    def update(u, masked):
        r = pl.multiple_of(u * block_q, block_q)
        q = q_ref[0, pl.ds(r, block_q), :]          # (BQ, D)
        do = do_ref[0, pl.ds(r, block_q), :]
        st = _scores(ks, q)                         # (BK, BQ)
        if masked:
            kj = col0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qi = row0 + r + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(qi >= kj, st, -jnp.inf)
        pt = jnp.exp(st - lse_ref[0, u])            # (1, BQ) down the rows
        dst = (pt * (_scores(v, do) - dta_ref[0, u])).astype(q.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(row0 + r, block_q), block_q)
        dq_scr[rows, :] += jax.lax.dot_general(
            dst, ks, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # query tiles [0, u_run) end above this key block, [u_run, u_clear)
        # are crossed by the diagonal, the rest lie wholly under it
        u_run = jnp.clip((col0 - row0) // block_q, 0, ntiles)
        u_clear = jnp.clip(
            (col0 - row0 + block_k - 1 + block_q - 1) // block_q,
            u_run, ntiles)
        _tile_loop(u_run, u_clear, lambda u: update(u, True))
        _tile_loop(u_clear, ntiles, lambda u: update(u, False))
    else:
        _tile_loop(0, ntiles, lambda u: update(u, False))

    @pl.when(last_im)
    def _finish_dkv():
        dk_ref[0] = (dk_scr[:] * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(last_im & (j == pl.num_programs(1) - 1))
    def _finish_dq():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _recompute_p_ds(refs, i, j, *, scale, causal, block_q, block_k):
    """Shared backward-recompute math: rebuild this (i, j) block's softmax
    probabilities p and the logit cotangent ds from the forward residuals.
    One copy keeps dQ's and dK/dV's numerics (mask convention, scale
    application) in lockstep with each other and with the forward."""
    import jax
    import jax.numpy as jnp

    q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref = refs
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.float32(scale)
    if causal:
        qi = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kj = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qi >= kj, s, -jnp.inf)
    lse = _lane_tile(lse_ref[0], block_k)
    p = jnp.exp(s - lse)                        # masked lanes -> 0
    dp = jax.lax.dot_general(
        do, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dta = _lane_tile(dta_ref[0], block_k)
    ds = p * (dp - dta) * jnp.float32(scale)
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k):
    import jax
    import jax.numpy as jnp
    pl = _pallas()[0]

    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _update():
        _, ds = _recompute_p_ds(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref), i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        k = k_ref[0]
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(j * block_k <= i * block_q + block_q - 1)
        def _masked_update():
            _update()
    else:
        _update()

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel_grouped(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                            dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                            causal, block_q, block_k):
    """dK/dV of the two-kernel backward, key block outer and query block
    accumulated: the grid ends in a group dim (B*H_kv, T/bk, T/bq, G) and
    the VMEM scratch accumulates every one of a kv head's G q-heads'
    contributions before the single write-back — dK/dV land at the GROUPED
    width, no q-width gradient is ever materialized."""
    import jax.numpy as jnp
    pl = _pallas()[0]

    j = pl.program_id(1)   # key block (outer)
    i = pl.program_id(2)   # query block (accumulated)
    gi = pl.program_id(3)  # q-head within the kv group (accumulated)
    ni = pl.num_programs(2)
    ng = pl.num_programs(3)

    @pl.when((i == 0) & (gi == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _update():
        import jax

        p, ds = _recompute_p_ds(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref), i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        q = q_ref[0]
        do = do_ref[0]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(i * block_q + block_q - 1 >= j * block_k)
        def _masked_update():
            _update()
    else:
        _update()

    @pl.when((i == ni - 1) & (gi == ng - 1))
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, scale, causal, interpret, block_q=None,
              block_k=None, groups=1):
    bh, t, d = q.shape
    g = int(groups)
    if k.shape[0] * g != bh:
        raise ValueError(
            "flash_attention bwd: folded K/V batch %d * groups=%d != "
            "folded Q batch %d" % (k.shape[0], g, bh))
    if lse.ndim == 3:           # the ring's (BH, T, LANES): lane 0
        lse = lse[:, :, 0]
    # one kernel where a head's dQ fits VMEM and K/V are not grouped (a kv
    # head's dK/dV would gather G q-heads' dQ scratches); else two
    fused = g == 1 and t * d * (4 + 2 * q.dtype.itemsize) <= FUSED_BWD_BYTES
    bq = _pick_block(block_q or BLOCK_Q_BWD, t)
    # the two kernels pay per key block as the forward does, and take
    # its key block (PR 26 measured 1024 for both)
    bk = _pick_block(block_k or (BLOCK_K_BWD if fused else BLOCK_K), t)
    if not bq or not bk:
        raise ValueError("flash_attention bwd blocks degenerate for T=%d "
                         "(callers must gate on supported())" % t)
    return _traced_once(_bwd_kernels)(
        q, k, v, o, lse, do, scale=scale, causal=causal,
        interpret=interpret, bq=bq, bk=bk,
        major=_major(bq, t) if fused else 0, g=g)


def _bwd_kernels(q, k, v, o, lse, do, *, scale, causal, interpret, bq, bk,
                 major, g):
    """``lse`` is (BH, T).  ``major`` > 0: the fused kernel over Q/dO blocks
    of that many rows; 0: the dQ kernel and the dK/dV kernel."""
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    bh, t, d = q.shape
    bh_kv = k.shape[0]

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)

    if major:
        # logsumexp and delta as lane-major rows, one (1, bq) row a tile
        rows = (bh, t // bq, 1, bq)
        if causal:
            # a major block wholly above this key block names the first
            # that is not, and is not fetched
            def first(j, im):
                return jnp.maximum(im, (j * bk) // major)
        else:
            def first(j, im):
                return im
        q_spec = pl.BlockSpec((1, major, d),
                              lambda b, j, im: (b, first(j, im), 0))
        row_spec = pl.BlockSpec((1, major // bq, 1, bq),
                                lambda b, j, im: (b, first(j, im), 0, 0))
        kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, im: (b, j, 0))
        return pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=bk),
            out_shape=[_out_sds(x.shape, x.dtype, q, k, v, do, lse)
                       for x in (q, k, v)],
            grid=(bh, t // bk, t // major),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[pl.BlockSpec((1, t, d), lambda b, j, im: (b, 0, 0)),
                       kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse.reshape(rows), delta.reshape(rows))

    # the two kernels read both per-row residuals broadcast over 128 lanes
    lse = jnp.broadcast_to(lse[..., None], (bh, t, LANES))
    delta = jnp.broadcast_to(delta[..., None], (bh, t, LANES))

    if g == 1:
        kv_map = lambda b, i, j: (b, j, 0)          # noqa: E731
    else:
        kv_map = lambda b, i, j: (b // g, j, 0)     # noqa: E731

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_q=bq, block_k=bk)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=_out_sds(q.shape, q.dtype, q, k, v, do, lse, delta),
        grid=(bh, t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),       # q
            pl.BlockSpec((1, bk, d), kv_map),                          # k
            pl.BlockSpec((1, bk, d), kv_map),                          # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),       # do
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),   # lse
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),   # dta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dK/dV: grid walks (kv batch, key block, query block, group member) —
    # the b axis is the FOLDED KV batch, q/do/residual blocks index q-head
    # b*G + gi, and the scratch accumulates across both i and gi before one
    # grouped-width write-back
    dkv_kernel = functools.partial(_bwd_dkv_kernel_grouped, scale=scale,
                                   causal=causal, block_q=bq, block_k=bk)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=[_out_sds(k.shape, k.dtype, q, k, v, do, lse, delta),
                   _out_sds(v.shape, v.dtype, q, k, v, do, lse, delta)],
        grid=(bh_kv, t // bk, t // bq, g),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda b, j, i, gi: (b * g + gi, i, 0)),      # q
            pl.BlockSpec((1, bk, d), lambda b, j, i, gi: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, j, i, gi: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, d),
                         lambda b, j, i, gi: (b * g + gi, i, 0)),      # do
            pl.BlockSpec((1, bq, LANES),
                         lambda b, j, i, gi: (b * g + gi, i, 0)),      # lse
            pl.BlockSpec((1, bq, LANES),
                         lambda b, j, i, gi: (b * g + gi, i, 0)),      # dta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i, gi: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i, gi: (b, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_VJP_CACHE = {}


def _flash_vjp():
    """Build (once) the custom_vjp-wrapped kernel entry point."""
    if "fn" in _VJP_CACHE:
        return _VJP_CACHE["fn"]
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
    def _flash(q, k, v, scale, causal, interpret, groups):
        out, _ = _fwd_rows(q, k, v, scale, causal, interpret,
                           with_lse=False, groups=groups)
        return out

    def _fwd_rule(q, k, v, scale, causal, interpret, groups):
        out, lse = _fwd_rows(q, k, v, scale, causal, interpret,
                             with_lse=True, groups=groups)
        return out, (q, k, v, out, lse)

    def _bwd_rule(scale, causal, interpret, groups, res, do):
        q, k, v, out, lse = res
        return _bwd_call(q, k, v, out, lse, do, scale, causal, interpret,
                         groups=groups)

    _flash.defvjp(_fwd_rule, _bwd_rule)
    _VJP_CACHE["fn"] = _flash
    return _flash


def _einsum_fallback(q, k, v, scale, causal, groups=1):
    """Plain-XLA attention with the kernel's numerics contract, for
    shapes whose blocks degenerate (odd/prime T); differentiable through
    ordinary autodiff.  ``groups`` > 1 maps folded q row ``b`` onto K/V
    row ``b // groups`` via reshape, like the kernel's index maps."""
    import jax
    import jax.numpy as jnp

    if groups > 1:
        bh, t, d = q.shape
        qg = q.reshape(bh // groups, groups, t, d)
        s = jnp.einsum("bgqd,bkd->bgqk", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            mask = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bgqk,bkd->bgqd", p, v.astype(jnp.float32))
        return out.reshape(bh, t, d).astype(q.dtype)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def flash_attention(q, k, v, scale, causal=False, interpret=False,
                    groups=1):
    """(BH, T, D) q vs (BH_kv, T, D) k/v -> (BH, T, D) attention output
    (``BH_kv == BH`` at ``groups=1``).  Differentiable (custom_vjp over
    the backward kernels — training runs the flash path).

    T whose block shrink degenerates below :data:`MIN_BLOCK` (odd or
    prime T — formerly a pathological 1-row kernel) takes the einsum
    fallback instead; tile-aligned T runs the kernels."""
    t = q.shape[1]
    if not (_pick_block(BLOCK_Q, t) and _pick_block(BLOCK_K, t)
            and _pick_block(BLOCK_Q_BWD, t)
            and _pick_block(BLOCK_K_BWD, t)):
        return _einsum_fallback(q, k, v, float(scale), bool(causal),
                                groups=int(groups))
    return _flash_vjp()(q, k, v, float(scale), bool(causal),
                        bool(interpret), int(groups))


def supported(q_shape, k_shape, causal, num_heads=1, num_kv_heads=0):
    """Whether the kernel handles these shapes (self-attention, T a
    multiple of the 128 sublane/lane tile, lane-friendly head dim).
    ``_pick_block`` shrinks the preferred block sizes to divide any such
    T, so 128-alignment is the only sequence-length constraint.  The lane
    check is on the PER-HEAD dim (E/num_heads) — the kernel operates on
    head-folded (B*H, T, E/H) blocks, so E=512/H=16 (head_dim 32) must
    fall back even though E itself is lane-aligned.  Grouped configs
    (``num_kv_heads < num_heads``) additionally require the K width to be
    exactly H_kv head slices."""
    bh, tq, d = q_shape
    tk = k_shape[1]
    if tq != tk:                       # cross-attention: fallback
        return False
    if tq % 128:                       # tile-aligned T only
        return False
    if num_heads <= 0 or d % num_heads:
        return False
    kvh = int(num_kv_heads) or int(num_heads)
    if kvh <= 0 or num_heads % kvh:
        return False
    if k_shape[2] != kvh * (d // num_heads):
        return False
    if (d // num_heads) % 64 != 0:     # lane-unfriendly heads: fallback
        return False
    # degenerate block shrink (odd/prime T below the tile check above
    # can't happen, but keep the gate self-sufficient for direct callers)
    if not (_pick_block(BLOCK_Q, tq) and _pick_block(BLOCK_K, tq)
            and _pick_block(BLOCK_Q_BWD, tq)
            and _pick_block(BLOCK_K_BWD, tq)):
        return False
    return True


def sdpa_flash(q, k, v, num_heads, causal, scale, interpret=False,
               num_kv_heads=0):
    """Multi-head wrapper matching ops.attention.sdpa's contract:
    (B, T, E) -> (B, T, E) with heads folded into the batch dim.
    Grouped configs fold K/V at their physical H_kv count — the kernels
    map q-head ``h`` to kv block ``h // G`` in their index maps."""
    b, t, e = q.shape
    kvh = int(num_kv_heads) or int(num_heads)
    g = num_heads // kvh
    hd = e // num_heads
    scale = scale or 1.0 / np.sqrt(hd)

    def fold(x, h):
        return x.reshape(b, t, h, x.shape[2] // h).transpose(0, 2, 1, 3) \
            .reshape(b * h, t, x.shape[2] // h)

    out = flash_attention(fold(q, num_heads), fold(k, kvh), fold(v, kvh),
                          scale=float(scale), causal=bool(causal),
                          interpret=bool(interpret), groups=g)
    return out.reshape(b, num_heads, t, hd).transpose(0, 2, 1, 3) \
        .reshape(b, t, e)
