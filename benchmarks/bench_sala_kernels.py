"""Kernel-alone readings of what ``minicpm-sala`` added, at the cell's
shapes (``sala_serve_longctx``: 24 slots, 32 heads of 128, 2 KV heads, int8
pools of 16-token pages, chunks of 2048): the lightning step and chunk
(``ops.linattn``), and the sparse attention's decode step and chunk
(``ops.attention.paged_attend_sparse`` with its appends) at contexts spread
as the traffic's.  One process, the chip's; prints one JSON line a reading.

    chiprun -- python3 benchmarks/bench_sala_kernels.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import attention as attn, linattn

SLOTS, H, KVH, D, PT, CACHE, CHUNK = 24, 32, 2, 128, 16, 66560, 2048
SPEC = attn.SparseSpec(64, 64, 32, 16, 1, 2048, 8192)
LIN = dict(num_heads=H, head_dim=D, slope_scale=0.7)


def timed(fn, *args, iters=20, donate=None):
    fn = jax.jit(fn, donate_argnums=donate or ())
    out = fn(*args)
    jax.block_until_ready(out)
    carried = lambda out: tuple(out[i] if i < len(out) else a
                                for i, a in enumerate(args)) \
        if donate else args
    args = carried(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        args = carried(out)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def say(**kw):
    print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in kw.items()}), flush=True)


def lightning():
    key = jax.random.PRNGKey(0)
    w = (jnp.ones(D), jnp.ones(D), jnp.ones(H * D))
    for rows in (SLOTS,):
        xs = tuple(jax.random.normal(jax.random.fold_in(key, i),
                                     (rows, 1, H * D), jnp.bfloat16)
                   for i in range(4))
        state = jnp.zeros((rows, H, D, D), jnp.float32)
        pos = jnp.full((rows,), 20000, jnp.int32)
        on = jnp.ones((rows,), jnp.int32)

        def step(state):
            out, (new,), _ = linattn.mix(LIN, *xs, *w, state=(state,),
                                         pos0=pos, active=on)
            return new, out

        ms, _ = timed(step, state, donate=(0,))
        moved = rows * 2 * H * D * D * 4
        say(kernel="linattn_step", rows=rows, ms=ms,
            hbm_pct=100 * moved / (ms / 1e3) / 819e9)
    for t in (CHUNK,):
        xs = tuple(jax.random.normal(jax.random.fold_in(key, 10 + i),
                                     (1, t, H * D), jnp.bfloat16)
                   for i in range(4))
        state = jnp.zeros((1, H, D, D), jnp.float32)

        def chunk(state):
            out, (new,), _ = linattn.mix(
                LIN, *xs, *w, state=(state,),
                pos0=jnp.asarray([4096], jnp.int32),
                nvalid=jnp.asarray([t], jnp.int32))
            return new, out

        say(kernel="linattn_chunk", tokens=t,
            ms=timed(chunk, state, donate=(0,))[0])


def pools(key):
    pages = SLOTS * CACHE // PT + 1
    k = jax.random.randint(key, (pages, PT, KVH * D), -127, 128, jnp.int8)
    v = jax.random.randint(jax.random.fold_in(key, 1), (pages, PT, KVH * D),
                           -127, 128, jnp.int8)
    scale = jnp.full((pages, PT * 2 * KVH), 0.01, jnp.float32)
    index = jax.random.normal(jax.random.fold_in(key, 2),
                              (pages, KVH * D), jnp.bfloat16)
    table = 1 + jnp.arange(SLOTS * (CACHE // PT), dtype=jnp.int32).reshape(
        SLOTS, CACHE // PT)
    table = jax.random.permutation(jax.random.fold_in(key, 3),
                                   table.reshape(-1)).reshape(table.shape)
    return attn.QuantKV(k, scale), attn.QuantKV(v, None), index, table


def sparse():
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(jax.random.fold_in(key, 4), (SLOTS, 1, H * D),
                          jnp.bfloat16)
    kv = jax.random.normal(jax.random.fold_in(key, 5), (SLOTS, 1, KVH * D),
                           jnp.bfloat16)
    on = jnp.ones((SLOTS,), jnp.int32)
    spread = np.exp(np.linspace(np.log(16384), np.log(65536), SLOTS))
    for name, lens in (("16k", np.full(SLOTS, 16384)),
                       ("64k", np.full(SLOTS, 65535)),
                       ("spread", spread), ("8k_dense", np.full(SLOTS, 8000))):
        lens = jnp.asarray(lens, jnp.int32)
        kc, vc, index, table = pools(key)

        def step(kc, vc, index):
            kc, vc = attn.paged_append_kv(kc, vc, table, kv, kv, lens,
                                          num_heads=KVH, active=on,
                                          layer="attn_sparse")
            index = attn.paged_append_index(index, kc, table, lens, 1, SPEC,
                                            active=on)
            out, counts = attn.paged_attend_sparse(
                q, kc, vc, index, table, lens + 1, SPEC, num_heads=H,
                num_kv_heads=KVH, active=on)
            return kc, vc, index, out, counts

        ms, out = timed(step, kc, vc, index, donate=(0, 1, 2))
        chosen, live = out[4]
        say(kernel="attn_sparse_decode", context=name, ms=ms,
            chosen=int(chosen), live=int(live))
        del kc, vc, index, out
    qc = jax.random.normal(jax.random.fold_in(key, 6), (1, CHUNK, H * D),
                           jnp.bfloat16)
    kvc = jax.random.normal(jax.random.fold_in(key, 7), (1, CHUNK, KVH * D),
                            jnp.bfloat16)
    for ctx in (0, 16384, 45056, 63488):
        pos = jnp.asarray([ctx], jnp.int32)
        n = jnp.asarray([CHUNK], jnp.int32)
        kc, vc, index, table = pools(key)

        def chunk(kc, vc, index):
            kc, vc = attn.paged_append_kv(kc, vc, table[:1], kvc, kvc, pos,
                                          num_heads=KVH, valid=n,
                                          layer="attn_sparse")
            index = attn.paged_append_index(index, kc, table[:1], pos, CHUNK,
                                            SPEC, valid=n)
            out, counts = attn.paged_attend_sparse(
                qc, kc, vc, index, table[:1], pos + CHUNK, SPEC, num_heads=H,
                num_kv_heads=KVH)
            return kc, vc, index, out, counts

        say(kernel="attn_sparse_chunk", first_position=ctx,
            ms=timed(chunk, kc, vc, index, iters=5, donate=(0, 1, 2))[0])
        del kc, vc, index


if __name__ == "__main__":
    dev = jax.devices()[0]
    say(device=dev.platform, kind=dev.device_kind)
    which = sys.argv[1:] or ["lightning", "sparse"]
    if "lightning" in which:
        lightning()
    if "sparse" in which:
        sparse()
