"""``ops.gdn`` alone at ``olmoh_serve_rollouts``' shapes on the chip: what the
chunk form (one 512-token chunk of a carried state, 30 heads of 96 x 192) reads
against the recurrence a token at a time in float32 at ``HIGHEST``, at each
precision its products could take (``ops.gdn.PRECISION`` and the two below
it), what each takes, and the step form (96 rows in place) in both of its
forms, the kernel alone swept over its head block (``probe_delta_step.py``
prints the step's lines alone).  One process, the chip's: it refuses to start
without one and names the device in every line; nothing of the benchmark
calls this.

    chiprun -- python3 benchmarks/probe_gdn_forms.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import probe_delta_step
from chipbench.reference import olmo_hybrid as ref
from mxnet_tpu.ops import gdn

H, DK, DV, K, T, SLOTS = 30, 96, 192, 4, 512, 96
KW, VW = H * DK, H * DV
ATTRS = dict(num_heads=H, key_head_dim=DK, value_head_dim=DV, conv_kernel=K,
             eps=1e-6)
# what probe_delta_step.step_and_sweep reads: the op, its heads, one decay a
# head, and the head blocks swept
MIX, HEADS, PER_HEAD = gdn.mix, (H, DK, DV), True
BLOCKS = (1, 2, 3, 5, 6, 10, 15, 30)


def inputs(key, b, t, dtype):
    ks = jax.random.split(key, 10)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)
    streams = [n(0, b, t, KW), n(1, b, t, KW), n(2, b, t, VW),
               0.3 * n(3, b, t, H), 1.3 * n(4, b, t, H), n(5, b, t, VW)]
    weights = [0.5 * n(6, 2 * KW + VW, K),
               jax.random.uniform(ks[7], (H,), jnp.float32, -0.7, 0.0),
               jax.random.uniform(ks[8], (H,), jnp.float32, -5.0, -2.3),
               1.0 + 0.02 * n(9, DV)]
    return [x.astype(dtype) for x in streams], weights


def by_token(streams, weights, s0):
    """The reference's pieces over one chunk from a carried matrix state
    (and a zero tail: its convolution pads with zeros)."""
    q, k, v, a, beta, gate = (x.astype(jnp.float32) for x in streams)
    conv_w, a_log, dt_bias, gamma = weights
    b, t, _ = q.shape
    qc = ref._conv_silu(q, conv_w[:KW]).reshape(b, t, H, DK)
    kc = ref._conv_silu(k, conv_w[KW:2 * KW]).reshape(b, t, H, DK)
    vc = ref._conv_silu(v, conv_w[2 * KW:]).reshape(b, t, H, DV)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + ref.L2_EPS)
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    with jax.default_matmul_precision("highest"):
        o, s = ref.delta_rule(unit(qc), unit(kc), vc, g,
                              2 * jax.nn.sigmoid(beta), s0)
    return ref._rms(o, gamma, 1e-6).reshape(b, t, VW) * jax.nn.silu(gate), s


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    began = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - began) / n


def main():
    say = probe_delta_step.chip_or_exit("probe_gdn_forms")
    key = jax.random.PRNGKey(57)
    assert gdn.PRECISION == "highest"
    for dtype in ("float32", "bfloat16"):
        streams, weights = inputs(key, 1, T, dtype)
        # a carried state: what two earlier chunks leave
        first = jax.jit(lambda s, w: gdn.mix(ATTRS, *s, *w)[1])
        tail, s0 = first(inputs(jax.random.fold_in(key, 1), 1, 2 * T,
                                dtype)[0], weights)
        state = (jnp.zeros_like(tail), s0)
        pos0, n = jnp.full((1,), T, jnp.int32), jnp.full((1,), T, jnp.int32)
        (want, s_want), ms_ref = timed(jax.jit(by_token), streams, weights,
                                       s0, n=1)
        for precision in ("highest", "high", "default"):
            gdn.PRECISION = precision
            chunk = jax.jit(lambda s, w, st: gdn.mix(
                ATTRS, *s, *w, state=st, pos0=pos0, nvalid=n)[:2])
            (got, (_, s_got)), ms = timed(chunk, streams, weights, state)
            d = got.astype(jnp.float32) - want
            say(form="chunk", streams=dtype, precision=precision, tokens=T,
                ms=ms, by_token_ms=ms_ref,
                out_rms=float(jnp.sqrt(jnp.mean(want ** 2))),
                diff_rms=float(jnp.sqrt(jnp.mean(d ** 2))),
                diff_max=float(jnp.max(jnp.abs(d))),
                state_rms=float(jnp.sqrt(jnp.mean(s_want ** 2))),
                state_diff_rms=float(jnp.sqrt(jnp.mean(
                    (s_got - s_want) ** 2))),
                state_diff_max=float(jnp.max(jnp.abs(s_got - s_want))))
        gdn.PRECISION = "highest"
    probe_delta_step.step_and_sweep(say, sys.modules[__name__], key,
                                    s_want)


if __name__ == "__main__":
    sys.exit(main())
