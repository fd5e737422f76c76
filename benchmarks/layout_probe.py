"""Probe: conv layout strategies on the TPU chip.

Times fwd+bwd of a ResNet-50-ish conv/BN/relu stack under three layouts:
  nchw      - lax.conv with NCHW/OIHW dims (current ops/nn.py behavior)
  nhwc_wrap - NCHW graph, each conv locally transposes to NHWC and back
  nhwc_full - whole stack natively NHWC/HWIO

Run on the bench chip to decide how ops/nn.py should lay out convs.

``--kv`` probes KV CACHE POOL layouts instead (the ROADMAP's
wire-the-probe clause): decode attention over a paged (P, page_tokens,
E) pool is timed with the pool ``device_put`` under each candidate
``major_to_minor`` permutation, and the winner prints as the
``MXNET_KV_LAYOUT`` value to export — decode.DecodePredictor applies it
to every pool at allocation (``ops.attention.apply_kv_layout``).
Backends that refuse a layout request (XLA:CPU) report it and keep the
native row-major; the knob is then best left empty.

Output contract: ONE ``tools.mxlint.contract_line`` json per probed
layout on stdout (winner flagged with ``"winner": true``); the human-readable
table goes to stderr.
"""
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tools.mxlint import contract_line

# (in_ch, out_ch, spatial, stride, n_blocks) rough resnet50 stage shapes
STAGES = [
    (64, 64, 56, 1, 3),
    (256, 128, 28, 2, 4),
    (512, 256, 14, 2, 6),
    (1024, 512, 7, 2, 3),
]
BATCH = 256
DTYPE = jnp.bfloat16


def make_params(mode, key):
    params = []
    prev = STAGES[0][0]
    for (cin, cout, sp, st, nb) in STAGES:
        for b in range(nb):
            ci = prev
            prev = cout
            if mode == "nhwc_full":
                w = jax.random.normal(key, (3, 3, ci, cout), DTYPE) * 0.05
            else:
                w = jax.random.normal(key, (cout, ci, 3, 3), DTYPE) * 0.05
            gamma = jnp.ones((cout,), jnp.float32)
            beta = jnp.zeros((cout,), jnp.float32)
            params.append((w, gamma, beta))
    return params


def bn(x, gamma, beta, caxis):
    red = tuple(i for i in range(x.ndim) if i != caxis)
    bshape = tuple(x.shape[caxis] if i == caxis else 1 for i in range(x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=red)
    var = jnp.var(x32, axis=red)
    inv = lax.rsqrt(var.reshape(bshape) + 1e-5)
    out = (x32 - mean.reshape(bshape)) * inv * gamma.reshape(bshape) \
        + beta.reshape(bshape)
    return out.astype(x.dtype)


def stack(mode, params, x):
    i = 0
    for (cin, cout, sp, st, nb) in STAGES:
        for b in range(nb):
            w, gamma, beta = params[i]
            i += 1
            stride = (st, st) if b == 0 else (1, 1)
            if mode == "nchw":
                x = lax.conv_general_dilated(
                    x, w, stride, ((1, 1), (1, 1)),
                    dimension_numbers=("NCHW", "OIHW", "NCHW"))
                x = bn(x, gamma, beta, 1)
            elif mode == "nhwc_wrap":
                xt = jnp.transpose(x, (0, 2, 3, 1))
                wt = jnp.transpose(w, (2, 3, 1, 0))
                xt = lax.conv_general_dilated(
                    xt, wt, stride, ((1, 1), (1, 1)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                x = jnp.transpose(xt, (0, 3, 1, 2))
                x = bn(x, gamma, beta, 1)
            else:  # nhwc_full
                x = lax.conv_general_dilated(
                    x, w, stride, ((1, 1), (1, 1)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                x = bn(x, gamma, beta, 3)
            x = jnp.maximum(x, 0)
    return x


def loss_fn(mode, params, x):
    out = stack(mode, params, x)
    return jnp.sum(out.astype(jnp.float32))


def bench(mode, iters=10):
    key = jax.random.PRNGKey(0)
    params = make_params(mode, key)
    if mode == "nhwc_full":
        x = jax.random.normal(key, (BATCH, 56, 56, 64), DTYPE)
    else:
        x = jax.random.normal(key, (BATCH, 64, 56, 56), DTYPE)

    grad = jax.jit(jax.grad(functools.partial(loss_fn, mode), argnums=0))

    def fence(g):
        # a value fetch as the sync point
        return float(jnp.sum(g[0][0].astype(jnp.float32)))

    g = grad(params, x)
    fence(g)
    tic = time.time()
    for _ in range(iters):
        g = grad(params, x)
    fence(g)
    dt = (time.time() - tic) / iters
    print("%-10s %7.2f ms/step  %7.1f img/s" % (mode, dt * 1e3, BATCH / dt),
          file=sys.stderr)
    return dt


def _kv_place(buf, order):
    """device_put ``buf`` with the requested major_to_minor order (None =
    backend native).  Raises if the backend refuses the layout."""
    if order is None:
        return jax.device_put(buf, jax.devices()[0])
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    return jax.device_put(buf, Format(
        Layout(major_to_minor=tuple(order)),
        SingleDeviceSharding(jax.devices()[0])))


def bench_kv(iters=30):
    """Time one paged decode-attention step per candidate pool layout.

    Serving-shaped dims: B slots of a T-token cache in page_tokens pages,
    decode batch = slots (the bandwidth-bound shape the fused kernel and
    the einsum path both stream).  The SAME jitted program runs for every
    candidate; only the pool's device layout changes, so the delta IS the
    layout.  Prints the winner as an ``export MXNET_KV_LAYOUT=...`` line
    (empty = native wins or the backend refuses overrides) and emits one
    contract_line json per candidate on stdout."""
    from mxnet_tpu.ops import attention as attn

    b, t_cache, e, heads, pt = 8, 2048, 1024, 8, 16
    m = t_cache // pt
    pages = b * m + 1
    rng = np.random.RandomState(0)
    kp = jnp.asarray(rng.randn(pages, pt, e).astype(np.float32))
    vp = jnp.asarray(rng.randn(pages, pt, e).astype(np.float32))
    table = jnp.asarray(
        1 + (np.arange(b)[:, None] * m + np.arange(m)[None, :]), jnp.int32)
    lens = jnp.full((b,), t_cache, jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, e).astype(np.float32))

    fn = jax.jit(lambda q_, k_, v_, t_, l_: attn.paged_attend(
        q_, k_, v_, t_, l_, num_heads=heads))

    candidates = [("native", None), ("0,1,2", (0, 1, 2)),
                  ("1,0,2", (1, 0, 2)), ("2,1,0", (2, 1, 0)),
                  ("0,2,1", (0, 2, 1))]
    results = []
    for name, order in candidates:
        try:
            kpl, vpl = _kv_place(kp, order), _kv_place(vp, order)
        except Exception as exc:
            print("%-8s unsupported on this backend (%s)"
                  % (name, str(exc)[:80]), file=sys.stderr)
            continue
        out = fn(q, kpl, vpl, table, lens)
        float(jnp.sum(out))                       # sync fence
        tic = time.time()
        for _ in range(iters):
            out = fn(q, kpl, vpl, table, lens)
        float(jnp.sum(out))
        dt = (time.time() - tic) / iters
        gbps = 2 * pages * pt * e * 4 / dt / 1e9
        print("%-8s %8.3f ms/step  %8.1f GB/s pool-stream"
              % (name, dt * 1e3, gbps), file=sys.stderr)
        results.append((dt, name, gbps))
    if results:
        base_dt = results[0][0]
        best_dt, best, _ = min(results)
        for dt, name, gbps in results:
            print(contract_line(
                "kv_layout_%s_ms" % name.replace(",", ""),
                round(dt * 1e3, 4), "ms", round(base_dt / dt, 3),
                layout=name, pool_stream_gbps=round(gbps, 1),
                winner=name == best))
        print("winner: %s" % best, file=sys.stderr)
        print("export MXNET_KV_LAYOUT=%s"
              % ("" if best == "native" else best), file=sys.stderr)


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    print("device:", jax.devices()[0].device_kind, file=sys.stderr)
    if "--kv" in sys.argv:
        bench_kv()
    else:
        timings = [(bench(mode), mode)
                   for mode in ("nchw", "nhwc_wrap", "nhwc_full")]
        base_dt = timings[0][0]
        best = min(timings)[1]
        for dt, mode in timings:
            print(contract_line(
                "conv_layout_%s_ms" % mode, round(dt * 1e3, 2), "ms",
                round(base_dt / dt, 3), layout=mode,
                images_per_sec=round(BATCH / dt, 1),
                winner=mode == best))
