"""Prototype: fused BN-apply + ReLU + 1x1-conv (matmul) Pallas kernel.

Measures the fused kernel against the XLA chain it replaces:

    stats(x) -> a = relu(x*scale+shift) -> y = a @ W (+residual) -> stats(y)

The fused kernel reads x once and writes y once, applying scale/shift/relu
in the matmul prologue and emitting the *output's* per-channel (sum, sumsq)
in the epilogue — so the next BN's statistics pass never re-reads y.
XLA's chain materializes `a` (write+read) and re-reads y for stats.

Run on the bench chip: `python benchmarks/proto_fused.py`.
"""
import functools
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_kernel(x_ref, scale_ref, shift_ref, w_ref, r_ref, y_ref,
                  s1_ref, s2_ref, *, relu_in, nsteps_i):
    i = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)
    a = x * scale_ref[...] + shift_ref[...]
    if relu_in:
        a = jnp.maximum(a, 0.0)
    acc = jax.lax.dot_general(
        a.astype(jnp.bfloat16), w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if r_ref is not None:
        acc = acc + r_ref[...].astype(jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    s1_ref[...] += jnp.sum(acc, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(jnp.square(acc), axis=0, keepdims=True)


def fused_bn_matmul(x, scale, shift, w, residual=None, relu_in=True,
                    block_m=512, block_n=256, interpret=False):
    """relu(x*scale+shift) @ w (+residual) with output (sum, sumsq) epilogue.

    x: (M, K) bf16; scale/shift: (K,) f32; w: (K, N) bf16.
    Returns y (M, N), ysum (N,), ysumsq (N,) in f32.
    """
    m, k = x.shape
    n = w.shape[1]
    bm = min(block_m, m)
    bn = min(block_n, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (n // bn, m // bm)  # i (rows) innermost so stats stay resident

    in_specs = [
        pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
        pl.BlockSpec((1, k), lambda j, i: (0, 0)),
        pl.BlockSpec((1, k), lambda j, i: (0, 0)),
        pl.BlockSpec((k, bn), lambda j, i: (0, j)),
    ]
    args = [x, scale.reshape(1, k), shift.reshape(1, k), w]
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda j, i: (i, j)))
        args.append(residual)

    kernel = functools.partial(
        _fused_kernel if residual is not None else
        functools.partial(_wrap_no_res, _fused_kernel),
        relu_in=relu_in, nsteps_i=m // bm)

    y, s1, s2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return y, s1[0], s2[0]


def _wrap_no_res(kern, x_ref, scale_ref, shift_ref, w_ref, y_ref,
                 s1_ref, s2_ref, **kw):
    kern(x_ref, scale_ref, shift_ref, w_ref, None, y_ref, s1_ref, s2_ref, **kw)


def xla_chain(x, scale, shift, w, residual=None, relu_in=True):
    a = x.astype(jnp.float32) * scale + shift
    if relu_in:
        a = jnp.maximum(a, 0.0)
    y = jax.lax.dot_general(
        a.astype(jnp.bfloat16), w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    y = y.astype(x.dtype)
    y32 = y.astype(jnp.float32)
    return y, jnp.sum(y32, axis=0), jnp.sum(jnp.square(y32), axis=0)


def _sync(v):
    return float(jnp.sum(v[-1].astype(jnp.float32) if isinstance(v, tuple)
                         else v.astype(jnp.float32)))


def bench(fn, args, iters=20):
    f = jax.jit(fn)
    out = f(*args)
    _sync(out)
    best = np.inf
    for _ in range(3):
        t0 = time.time()
        for _ in range(iters):
            out = f(*args)
        _sync(out)
        best = min(best, (time.time() - t0) / iters)
    return best * 1e3, out


def main():
    rng = np.random.RandomState(0)
    # (M, K, N, residual?) — ResNet-50 bs256 NHWC stage shapes
    cases = [
        ("s1 c1 56x56 256->64 ", 256 * 56 * 56, 256, 64, False),
        ("s1 c3 56x56 64->256 +r", 256 * 56 * 56, 64, 256, True),
        ("s2 c3 28x28 128->512 +r", 256 * 28 * 28, 128, 512, True),
        ("s3 c1 14x14 1024->256", 256 * 14 * 14, 1024, 256, False),
        ("s4 c3 7x7 512->2048 +r", 256 * 7 * 7, 512, 2048, True),
    ]
    for name, m, k, n, has_res in cases:
        x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(0, 0.05, (k, n)), jnp.bfloat16)
        scale = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.normal(0, 0.1, k), jnp.float32)
        res = (jnp.asarray(rng.normal(0, 1, (m, n)), jnp.bfloat16)
               if has_res else None)
        args = (x, scale, shift, w) + ((res,) if has_res else ())

        fused = (lambda *a: fused_bn_matmul(*a)) if has_res else \
                (lambda x_, s_, b_, w_: fused_bn_matmul(x_, s_, b_, w_))
        ref = (lambda *a: xla_chain(*a))

        t_x, out_x = bench(ref, args)
        t_p, out_p = bench(fused, args)
        # numerics
        err = float(jnp.max(jnp.abs(out_p[0].astype(jnp.float32)
                                    - out_x[0].astype(jnp.float32))))
        serr = float(jnp.max(jnp.abs(out_p[1] - out_x[1]) /
                             (jnp.abs(out_x[1]) + 1)))
        gbytes = (m * k + m * n + k * n) * 2 / 1e9
        print(f"{name}: xla {t_x:6.2f} ms  pallas {t_p:6.2f} ms  "
              f"speedup {t_x / t_p:4.2f}x  minGB {gbytes:.2f} "
              f"({gbytes / t_p:.0f} GB/s eff)  maxerr {err:.3f} srel {serr:.1e}",
              flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
