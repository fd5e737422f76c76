"""Block-size sweep for the fused BN-matmul kernel vs XLA floors."""
import functools
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def kern(x_ref, s_ref, b_ref, w_ref, y_ref, s1_ref, s2_ref, *, stats, nk):
    i = pl.program_id(1)
    a = x_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...]
    a = jnp.maximum(a, 0.0)
    acc = jax.lax.dot_general(a.astype(jnp.bfloat16), w_ref[...],
                              dimension_numbers=(((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(jnp.bfloat16)
    if stats:
        @pl.when(i == 0)
        def _():
            s1_ref[...] = jnp.zeros_like(s1_ref)
            s2_ref[...] = jnp.zeros_like(s2_ref)
        s1_ref[...] += jnp.sum(acc, axis=0, keepdims=True)
        s2_ref[...] += jnp.sum(jnp.square(acc), axis=0, keepdims=True)


def fused(x, s, b, w, bm, bn, stats):
    m, k = x.shape
    n = w.shape[1]
    grid = (n // bn, m // bm)
    outs = [jax.ShapeDtypeStruct((m, n), jnp.bfloat16)]
    ospecs = [pl.BlockSpec((bm, bn), lambda j, i: (i, j))]
    if stats:
        outs += [jax.ShapeDtypeStruct((1, n), jnp.float32)] * 2
        ospecs += [pl.BlockSpec((1, bn), lambda j, i: (0, j))] * 2
    else:
        outs += [jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 2
        ospecs += [pl.BlockSpec((1, 1), lambda j, i: (0, 0))] * 2
    r = pl.pallas_call(
        functools.partial(kern, stats=stats, nk=1),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
                  pl.BlockSpec((1, k), lambda j, i: (0, 0)),
                  pl.BlockSpec((1, k), lambda j, i: (0, 0)),
                  pl.BlockSpec((k, bn), lambda j, i: (0, j))],
        out_specs=ospecs, out_shape=outs)(x, s.reshape(1, k),
                                          b.reshape(1, k), w)
    return r[0]


def sync(v):
    return float(jnp.sum(v[:8, :8].astype(jnp.float32)))


def bench(f, args, iters=30):
    jf = jax.jit(f)
    sync(jf(*args))
    best = np.inf
    for _ in range(3):
        t0 = time.time()
        for _ in range(iters):
            o = jf(*args)
        sync(o)
        best = min(best, (time.time() - t0) / iters)
    return best * 1e3


def main():
    rng = np.random.RandomState(0)
    cases = [("s1c1", 802816, 256, 64), ("s1c3", 802816, 64, 256),
             ("s4c1", 12544, 2048, 512)]
    for name, m, k, n in cases:
        x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(0, 0.05, (k, n)), jnp.bfloat16)
        s = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
        b = jnp.asarray(rng.normal(0, 0.1, k), jnp.float32)

        t = bench(lambda x, w: jax.lax.dot_general(
            x, w, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.bfloat16), (x, w))
        print(f"{name}: xla-matmul-only {t:6.2f} ms", flush=True)

        def chain(x, s, b, w):
            a = jnp.maximum(x.astype(jnp.float32) * s + b, 0.0)
            y = jax.lax.dot_general(a.astype(jnp.bfloat16), w,
                                    dimension_numbers=(((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32
                                    ).astype(jnp.bfloat16)
            return y
        t = bench(chain, (x, s, b, w))
        print(f"{name}: xla-chain(no stats) {t:6.2f} ms", flush=True)

        for bm in (512, 1024, 2048, 4096):
            for bn in (128, 256, 512):
                bn_ = min(bn, n)
                if m % bm or n % bn_:
                    continue
                for stats in (False, True):
                    try:
                        t = bench(lambda x, s, b, w: fused(
                            x, s, b, w, bm, bn_, stats), (x, s, b, w))
                    except Exception as e:
                        print(f"{name}: bm={bm} bn={bn_} stats={stats} "
                              f"FAIL {type(e).__name__}", flush=True)
                        continue
                    print(f"{name}: bm={bm} bn={bn_} stats={int(stats)} "
                          f"{t:6.2f} ms", flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
