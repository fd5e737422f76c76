"""``ops.kda`` alone at ``solar2_serve_agent``'s shapes on the chip: what the
chunk form (one 2048-token chunk of a carried state, 64 heads of 128) and the
step form (96 rows in place) read against the recurrence a token at a time in
float32 at ``HIGHEST``, and what each takes; the step in both of its forms,
the kernel alone swept over its head block (``probe_delta_step.py`` prints
the step's lines alone).  One process, the chip's: it refuses to start
without one and names the device in every line; nothing of the benchmark
calls this.

    chiprun -- python3 benchmarks/probe_kda_forms.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import probe_delta_step
from chipbench.reference import solar_open2 as ref
from mxnet_tpu.ops import kda

H, D, K, T, SLOTS = 64, 128, 4, 2048, 96
ATTRS = dict(num_heads=H, head_dim=D, conv_kernel=K, eps=1e-5)
# what probe_delta_step.step_and_sweep reads: the op, its heads, a decay a
# channel, and the head blocks swept
MIX, HEADS, PER_HEAD = kda.mix, (H, D, D), False
BLOCKS = (1, 2, 4, 8, 16, 32, 64)


def inputs(key, b, t, dtype):
    ks = jax.random.split(key, 10)
    w = H * D
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)
    streams = [n(0, b, t, w), n(1, b, t, w), n(2, b, t, w),
               0.3 * n(3, b, t, w), 1.3 * n(4, b, t, H), 0.3 * n(5, b, t, w)]
    weights = [0.5 * n(6, 3 * w, K),
               jax.random.uniform(ks[7], (H,), jnp.float32, -0.7, 0.0),
               jax.random.uniform(ks[8], (w,), jnp.float32, -5.0, -2.3),
               1.0 + 0.02 * n(9, D)]
    return [x.astype(dtype) for x in streams], weights


def by_token(streams, weights, s0):
    """The reference's pieces over one chunk from a carried matrix state
    (and a zero tail: its convolution pads with zeros)."""
    q, k, v, f, beta, gate = (x.astype(jnp.float32) for x in streams)
    conv_w, a_log, dt_bias, gamma = weights
    b, t, w = q.shape
    heads = lambda x: x.reshape(b, t, H, D)
    qc, kc, vc = (heads(ref._conv_silu(x, conv_w[i * w:(i + 1) * w]))
                  for i, x in enumerate((q, k, v)))
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + ref.L2_EPS)
    g = -jnp.exp(a_log)[:, None] * heads(jax.nn.softplus(f + dt_bias))
    with jax.default_matmul_precision("highest"):
        o, s = ref.delta_rule(unit(qc), unit(kc), vc, g,
                              2 * jax.nn.sigmoid(beta), s0)
    return ref._rms(o, gamma, 1e-5).reshape(b, t, w) \
        * jax.nn.sigmoid(gate), s


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    began = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - began) / n


def main():
    say = probe_delta_step.chip_or_exit("probe_kda_forms")
    key = jax.random.PRNGKey(53)
    for dtype in ("float32", "bfloat16"):
        streams, weights = inputs(key, 1, T, dtype)
        # a carried state: what a first chunk leaves
        first = jax.jit(lambda s, w: kda.mix(ATTRS, *s, *w)[1])
        tail, s0 = first(inputs(jax.random.fold_in(key, 1), 1, T, dtype)[0],
                         weights)
        state = (jnp.zeros_like(tail), s0)
        pos0, n = jnp.full((1,), T, jnp.int32), jnp.full((1,), T, jnp.int32)
        chunk = jax.jit(lambda s, w, st: kda.mix(
            ATTRS, *s, *w, state=st, pos0=pos0, nvalid=n)[:2])
        (got, (_, s_got)), ms = timed(chunk, streams, weights, state)
        (want, s_want), ms_ref = timed(jax.jit(by_token), streams, weights,
                                       s0, n=1)
        d = got.astype(jnp.float32) - want
        say(form="chunk", streams=dtype, tokens=T, ms=ms, by_token_ms=ms_ref,
            out_rms=float(jnp.sqrt(jnp.mean(want ** 2))),
            diff_rms=float(jnp.sqrt(jnp.mean(d ** 2))),
            diff_max=float(jnp.max(jnp.abs(d))),
            state_rms=float(jnp.sqrt(jnp.mean(s_want ** 2))),
            state_diff_max=float(jnp.max(jnp.abs(s_got - s_want))))
    probe_delta_step.step_and_sweep(say, sys.modules[__name__], key,
                                    s_want)


if __name__ == "__main__":
    sys.exit(main())
