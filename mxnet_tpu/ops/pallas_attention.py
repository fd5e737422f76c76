"""Pallas flash-attention kernels (TPU) — forward AND backward.

The hot-op kernel the einsum formulation can't match at long sequence:
``ops.attention.sdpa`` materializes the (T, T) logits in HBM — O(T²)
memory traffic — while these kernels stream K/V blocks through VMEM with
a running (max, sum, acc) softmax, O(T) memory, logits never leaving the
chip (flash-attention schedule; same numerics as the streaming
accumulator in ``parallel/ring.py``, here at the kernel level).

``flash_attention`` is differentiable: a ``jax.custom_vjp`` pairs the
forward kernel (which saves a per-row logsumexp residual) with two
backward kernels — one accumulating dQ over key blocks, one accumulating
dK/dV over query blocks — recomputing the (T, T) probabilities blockwise
from the residual instead of storing them.  This is the TPU analog of the
reference's fused-kernel-that-trains precedent (its cuDNN RNN op
implements forward *and* backward in one fused device kernel,
``src/operator/cudnn_rnn-inl.h``): long-context *training* runs the fast
path, not just inference.

Per-row residuals (logsumexp, and delta = rowsum(dO·O)) are stored
broadcast across a 128-lane minor dimension — ``(BH, T, LANES)`` — so the
backward kernels consume them with the same (rows, lanes) layout the MXU
tiles want, and no kernel ever transposes a vector.

Used by ``dot_product_attention`` where ``ops.attention.flash_selected``
says the call's shape wins with it (a TPU, no mesh, a supported shape, T at
or past the measured crossover for the head width); anything else takes the
einsum path.  ``interpret=True`` runs the same kernels on CPU for tests.
"""
from __future__ import annotations

import functools

import numpy as np

# Block-size defaults, taken where the tuning cache (ops/tuning.py) holds no
# winner for the (generation, shape-class, dtype) — a fresh checkout holds
# none, .mxnet_programs/ is not in git.  Swept on TPU v5 lite, jax 0.9.0,
# bf16 causal, (B, T) = (4, 2048), forward at (block_q, block_k) / backward
# at (block_q_bwd, block_k_bwd) in ms
# (benchmarks/bench_flash_attention.py --crossover, PR 26):
#   32 heads of 64:  (128, 512) 6.51 / (256, 512) 9.07
#                    (512, 1024) 3.69 / 6.86   (512, 2048) 3.56 / 6.88
#   16 heads of 128: (128, 512) 2.77 / (256, 512) 3.86
#                    (512, 1024) 1.33 / 2.97   (512, 2048) 1.38 / 3.06
# (512, 1024) wins or ties at both widths, keeps the causal block skip at
# T 2048 and asks half the VMEM of (512, 2048).
BLOCK_Q = 512
BLOCK_K = 1024
BLOCK_Q_BWD = 512
BLOCK_K_BWD = 1024
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


# grouped shape classes whose stale-MHA-record check already ran (the
# warned-miss fires once per shape class per process, not per trace)
_STALE_GROUP_CHECKED = set()


def _tuned(t, d, dtype, groups=1):
    """Tuning-cache block resolution for this shape class ({"block_q",
    "block_k", "block_q_bwd", "block_k_bwd"}; the module constants when
    cold and no sweep armed).

    The kv-head group factor is part of the content-addressed key
    (``g<G>`` joins the shape class) — a grouped kernel's winning blocks
    see G× narrower K/V streams than the MHA kernel's at the same (t, d),
    so GQA shapes must never collide with MHA winners.  A persisted
    MHA-keyed record encountered for a grouped shape reads as a WARNED
    miss, never as a hit."""
    import jax.numpy as jnp

    from . import tuning

    name = jnp.dtype(dtype).name
    if groups <= 1:
        return tuning.resolve("pallas_attention",
                              tuning.shape_class_for(t=t, d=d), name)
    sc = tuning.shape_class_for(t=t, d=d, g=groups)
    if sc not in _STALE_GROUP_CHECKED:
        _STALE_GROUP_CHECKED.add(sc)
        if tuning.get("pallas_attention", sc, name, version=1) is None \
                and tuning.get("pallas_attention",
                               tuning.shape_class_for(t=t, d=d), name,
                               version=1) is not None:
            import warnings

            warnings.warn(
                "tuning cache holds an MHA-keyed pallas_attention record "
                "for t=%d d=%d but the shape is grouped (G=%d); the MHA "
                "winner does not apply — treating as a miss" %
                (t, d, groups))
    return tuning.resolve("pallas_attention", sc, name)


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


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal, block_q,
            block_k, with_lse=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest

    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _update():
        q = q_ref[0]                                # (BQ, D)
        k = k_ref[0]                                # (BK, D)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)

        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kj = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s_masked = jnp.where(qi >= kj, s, -jnp.inf)
        else:
            s_masked = s
        s = s_masked

        m_prev = m_scr[:, :1]                       # (BQ, 1)
        blk_m = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_m)
        # rows with every key masked so far keep m = -inf; normalize safely
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s == -jnp.inf, 0.0, p)
        corr = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))

        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[:] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc

    if causal:
        # skip K/V blocks entirely above the diagonal (~2x on long T)
        @pl.when(j * block_k <= i * block_q + block_q - 1)
        def _masked_update():
            _update()
    else:
        _update()

    @pl.when(j == nj - 1)
    def _finish():
        denom = l_scr[:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            m_fin = jnp.where(m_scr[:] == -jnp.inf, 0.0, m_scr[:])
            d_fin = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
            lse_ref[0] = m_fin + jnp.log(d_fin)


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
    bh, t, d = q.shape
    g = int(groups)
    if k.shape[0] * g != bh:
        raise ValueError(
            "flash_attention fwd: folded K/V batch %d * groups=%d != "
            "folded Q batch %d" % (k.shape[0], g, bh))
    if block_q is None or block_k is None:
        cfg = _tuned(t, d, q.dtype, groups=g)
        block_q = block_q or cfg.get("block_q", BLOCK_Q)
        block_k = block_k or cfg.get("block_k", BLOCK_K)
    bq = _pick_block(block_q, t)
    bk = _pick_block(block_k, t)
    if not bq or not bk:
        raise ValueError("flash_attention fwd blocks degenerate for T=%d "
                         "(callers must gate on supported())" % t)
    return _traced_once(_fwd_kernel)(
        q, k, v, scale=scale, causal=causal, interpret=interpret,
        with_lse=with_lse, bq=bq, bk=bk, g=g)


def _fwd_kernel(q, k, v, *, scale, causal, interpret, with_lse, bq, bk, g):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    grid = (bh, t // bq, t // bk)

    # grouped K/V: folded Q batch index b encodes (batch, q-head) as
    # b = batch*H + h, so its kv block lives at folded index
    # batch*H_kv + h//G == b // G — the h // G group map, in the
    # BlockSpec index map (never a materialized broadcast)
    if g == 1:
        kv_map = lambda b, i, j: (b, j, 0)          # noqa: E731
    else:
        kv_map = lambda b, i, j: (b // g, j, 0)     # noqa: E731

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, with_lse=with_lse)
    out_shape = [_out_sds(q.shape, q.dtype, q, k, v)]
    out_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))]
    if with_lse:
        out_shape.append(
            _out_sds((bh, t, LANES), jnp.float32, q, k, v))
        out_specs.append(
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)))
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return (res[0], res[1]) if with_lse else (res[0], None)


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
    from jax.experimental import pallas as pl

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


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, dk_ref,
                    dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                    block_k):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)   # key block (outer)
    i = pl.program_id(2)   # query block (inner, accumulated)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _update():
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
        # query blocks strictly above this key block see none of it
        @pl.when(i * block_q + block_q - 1 >= j * block_k)
        def _masked_update():
            _update()
    else:
        _update()

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dkv_kernel_grouped(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                            dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                            causal, block_q, block_k):
    """Grouped twin of :func:`_bwd_dkv_kernel`: the grid grows a trailing
    group dim (B*H_kv, T/bk, T/bq, G) and the VMEM scratch accumulates
    every one of a kv head's G q-heads' contributions before the single
    write-back — dK/dV land at the GROUPED width, no q-width gradient is
    ever materialized."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

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
    if block_q is None or block_k is None:
        cfg = _tuned(t, d, q.dtype, groups=g)
        block_q = block_q or cfg.get("block_q_bwd", BLOCK_Q_BWD)
        block_k = block_k or cfg.get("block_k_bwd", BLOCK_K_BWD)
    bq = _pick_block(block_q, t)
    bk = _pick_block(block_k, t)
    if not bq or not bk:
        raise ValueError("flash_attention bwd blocks degenerate for T=%d "
                         "(callers must gate on supported())" % t)
    return _traced_once(_bwd_kernels)(
        q, k, v, o, lse, do, scale=scale, causal=causal,
        interpret=interpret, bq=bq, bk=bk, g=g)


def _bwd_kernels(q, k, v, o, lse, do, *, scale, causal, interpret, bq, bk,
                 g):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    bh_kv = k.shape[0]

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
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

    if g == 1:
        dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                       causal=causal, block_q=bq,
                                       block_k=bk)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            out_shape=[
                _out_sds(k.shape, k.dtype, q, k, v, do, lse, delta),
                _out_sds(v.shape, v.dtype, q, k, v, do, lse, delta)],
            grid=(bh, t // bk, t // bq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, bq, LANES),
                             lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, bq, LANES),
                             lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)
        return dq, dk, dv

    # grouped dK/dV: grid walks (kv batch, key block, query block, group
    # member) — the b axis is the FOLDED KV batch, q/do/residual blocks
    # index q-head b*G + gi, and the scratch accumulates across both i
    # and gi before one grouped-width write-back
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
        out, _ = _fwd_call(q, k, v, scale, causal, interpret,
                           with_lse=False, groups=groups)
        return out

    def _fwd_rule(q, k, v, scale, causal, interpret, groups):
        out, lse = _fwd_call(q, k, v, scale, causal, interpret,
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


# ---------------------------------------------------------------------------
# tunable space (ops/tuning.py): fwd/bwd Q/K blocks per shape class
# ---------------------------------------------------------------------------

def _tuning_candidates(shape_class, interpret):
    if interpret:
        # 2-candidate toy space: tier-1 exercises the sweep machinery on
        # CPU without a grid search
        return [{"block_q": 128, "block_k": 128},
                {"block_q": 128, "block_k": 256}]
    out = []
    for bq in (128, 256, 512):
        for bk in (512, 1024):
            for bqb, bkb in ((256, 512), (512, 1024)):
                out.append({"block_q": bq, "block_k": bk,
                            "block_q_bwd": bqb, "block_k_bwd": bkb})
    return out


def _tuning_runner(params, shape_class, dtype, interpret):
    import jax
    import jax.numpy as jnp

    from . import tuning

    dims = tuning.parse_shape_class(shape_class)
    t, d = dims["t"], dims["d"]
    for key in ("block_q", "block_k", "block_q_bwd", "block_k_bwd"):
        if not _pick_block(params[key], t):
            raise tuning.SpaceError("%s=%d degenerates for T=%d"
                                    % (key, params[key], t))
    dt = jnp.dtype(dtype)
    rng = jax.random.PRNGKey(0)
    bh = 4
    q = jax.random.normal(rng, (bh, t, d), dt)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (bh, t, d), dt)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (bh, t, d), dt)
    do = jnp.ones((bh, t, d), dt)
    scale = 1.0 / float(np.sqrt(d))

    bq, bk = params["block_q"], params["block_k"]
    bqb, bkb = params["block_q_bwd"], params["block_k_bwd"]

    @jax.jit
    def probe(q, k, v, do):
        o, lse = _fwd_call(q, k, v, scale, True, interpret, with_lse=True,
                           block_q=bq, block_k=bk)
        grads = _bwd_call(q, k, v, o, lse, do, scale, True, interpret,
                          block_q=bqb, block_k=bkb)
        return (o,) + tuple(grads)

    def run():
        jax.block_until_ready(probe(q, k, v, do))

    return run


def _register_space():
    from . import tuning

    tuning.register_space(
        "pallas_attention", version=1,
        defaults={"block_q": BLOCK_Q, "block_k": BLOCK_K,
                  "block_q_bwd": BLOCK_Q_BWD, "block_k_bwd": BLOCK_K_BWD},
        constants=("BLOCK_Q", "BLOCK_K", "BLOCK_Q_BWD", "BLOCK_K_BWD"),
        candidates=_tuning_candidates, runner=_tuning_runner)


_register_space()
