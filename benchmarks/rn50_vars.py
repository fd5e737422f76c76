"""Framework-ism isolation probe for the framework-vs-raw step residual.

Round-4 located a ~9% gap between the executor-generated fused step and
``rn50_raw.py`` and bisected what it is NOT (wd, bn_data alone, layout,
dispatch).  This probe isolates it the other way: start from the raw
program and ADD each framework behavior — input BatchNorm with trainable
beta (bn_data), BN moving-stat aux updates, SoftmaxOutput semantics (full
probability output + custom (p-onehot) backward), the framework's
custom_vjp BN (centered one-pass stats + cond cancellation guard + hand
backward) — measuring each addition's cost in the same clean program.

Usage: python rn50_vars.py [variant ...]   (default: the full matrix)
Variants: base, bn_data, aux, smout, bn_custom, all
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

N = int(os.environ.get("N", "256"))
UNITS = [3, 4, 6, 3]
FILTERS = [256, 512, 1024, 2048]
EPS = 2e-5

rng = np.random.RandomState(0)


def build_params(bn_data):
    params = {}
    aux = {}

    def conv_w(name, cin, cout, k):
        params[name] = jnp.asarray(
            rng.normal(0, 0.05, (cout, cin, k, k)), jnp.float32)

    def bn_w(name, c):
        params[name + "_g"] = jnp.ones((c,), jnp.float32)
        params[name + "_b"] = jnp.zeros((c,), jnp.float32)
        aux[name + "_mm"] = jnp.zeros((c,), jnp.float32)
        aux[name + "_mv"] = jnp.ones((c,), jnp.float32)

    if bn_data:
        bn_w("bn_data", 3)
    conv_w("conv0", 3, 64, 7)
    bn_w("bn0", 64)
    cin = 64
    for si, (u, f) in enumerate(zip(UNITS, FILTERS)):
        mid = f // 4
        for ui in range(u):
            nm = f"s{si}u{ui}"
            bn_w(nm + "_bn1", cin)
            conv_w(nm + "_c1", cin, mid, 1)
            bn_w(nm + "_bn2", mid)
            conv_w(nm + "_c2", mid, mid, 3)
            bn_w(nm + "_bn3", mid)
            conv_w(nm + "_c3", mid, f, 1)
            if ui == 0:
                conv_w(nm + "_sc", cin, f, 1)
            cin = f
    bn_w("bn_final", 2048)
    params["fc_w"] = jnp.asarray(rng.normal(0, 0.01, (2048, 1000)),
                                 jnp.float32)
    params["fc_b"] = jnp.zeros((1000,), jnp.float32)
    return params, aux


def conv(p, name, x, k, s):
    w = p[name].astype(jnp.bfloat16)
    pad = k // 2
    return lax.conv_general_dilated(
        x, w, (s, s), [(pad, pad)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _stats_onepass(x32):
    m = jnp.mean(x32, axis=(0, 2, 3))
    v = jnp.maximum(jnp.mean(jnp.square(x32), axis=(0, 2, 3))
                    - jnp.square(m), 0.0)
    return m, v


def _bn_custom_core(nocond=False, nocenter=False, autodiff=False):
    """The framework's _bn_train_core formulation (ops/nn.py): centered
    one-pass stats + cond cancellation guard, hand-written backward.
    ``nocond`` drops the guard, ``nocenter`` additionally drops the
    center subtraction, ``autodiff`` keeps the stats formulation but lets
    XLA derive the backward — cost-isolation knobs.  The SGCOND env flag
    is a separate whole-variant override (centered stats + stop-gradient
    cond correction + autodiff backward); combining it with
    nocond/nocenter would measure the sg path under those rows' labels,
    so that combination raises — run SGCOND=1 only against plain
    ``bn_custom`` rows.  ``autodiff`` takes precedence over SGCOND (its
    branch returns first) and keeps its own correct label."""

    if SGCOND and (nocond or nocenter):
        raise ValueError("SGCOND=1 replaces the whole stats/backward "
                         "formulation; combining it with nocond/nocenter "
                         "variants would print mislabeled rows")

    def centered_stats(x, center):
        """Shared one-pass centered moments + cancellation predicate —
        ONE copy, so sg-cond rows measure the same formulation as the
        custom-vjp rows."""
        bshape = (1, x.shape[1], 1, 1)
        x32 = x.astype(jnp.float32)
        if nocenter:
            xc = x32
            center = jnp.zeros_like(center)
        else:
            xc = x32 - center.reshape(bshape)
        mc = jnp.mean(xc, axis=(0, 2, 3))
        var_fast = jnp.maximum(jnp.mean(jnp.square(xc), axis=(0, 2, 3))
                               - jnp.square(mc), 0.0)
        mc2 = jnp.square(mc)
        bad = jnp.any((var_fast <= 1e-5 * mc2) & (1e-7 * mc2 > EPS))
        return mc + center, var_fast, bad

    def stats(x, center):
        bshape = (1, x.shape[1], 1, 1)
        mean, var_fast, bad = centered_stats(x, center)
        if nocond:
            return mean, var_fast

        def refine(_):
            m = jax.lax.stop_gradient(mean).reshape(bshape)
            return jnp.mean(jnp.square(x.astype(jnp.float32) - m),
                            axis=(0, 2, 3))

        var = jax.lax.cond(bad, refine, lambda _: var_fast, None)
        return mean, var

    def apply(x, gamma, beta, mean, inv):
        bshape = (1, x.shape[1], 1, 1)
        scale = (inv * gamma).astype(x.dtype)
        shift = (beta - mean * inv * gamma).astype(x.dtype)
        return x * scale.reshape(bshape) + shift.reshape(bshape)

    if autodiff:
        # same stats formulation, XLA-derived backward (full BN
        # semantics: gradients flow through mean/var like the base path)
        def bn_ad(x, gamma, beta, center):
            mean, var = stats(x, center)
            inv = jax.lax.rsqrt(var + EPS)
            return apply(x, gamma, beta, mean, inv), mean, var
        return bn_ad

    if SGCOND:
        # autodiff-safe guard: the cond only contributes a STOP-GRADIENT
        # value correction, so differentiation never enters the branches
        # (no giant branch residuals -> no OOM) while the primal value is
        # still refined on cancellation
        def bn_sg(x, gamma, beta, center):
            bshape = (1, x.shape[1], 1, 1)
            mean, var_fast, bad = centered_stats(x, center)

            def corr(_):
                m = jax.lax.stop_gradient(mean).reshape(bshape)
                true = jnp.mean(
                    jnp.square(x.astype(jnp.float32) - m), axis=(0, 2, 3))
                return jax.lax.stop_gradient(true - var_fast)

            var = var_fast + jax.lax.cond(
                bad, corr, lambda _: jnp.zeros_like(var_fast), None)
            inv = jax.lax.rsqrt(var + EPS)
            return apply(x, gamma, beta, mean, inv), mean, var
        return bn_sg

    @jax.custom_vjp
    def bn(x, gamma, beta, center):
        mean, var = stats(x, center)
        inv = jax.lax.rsqrt(var + EPS)
        return apply(x, gamma, beta, mean, inv), mean, var

    def bn_fwd(x, gamma, beta, center):
        mean, var = stats(x, center)
        inv = jax.lax.rsqrt(var + EPS)
        return (apply(x, gamma, beta, mean, inv), mean, var), \
            (x, gamma, mean, inv)

    def bn_bwd(res, cts):
        x, gamma, mean, inv = res
        dy, dmean_ct, dvar_ct = cts
        bshape = (1, x.shape[1], 1, 1)
        n = x.shape[0] * x.shape[2] * x.shape[3]
        if LEANBWD:
            # dx = A*dy + B*x + C with per-channel coefficients from TWO
            # fused reductions (sum dy, sum dy*x) — no full-size f32
            # xmu/xhat temporaries, dx emitted in the compute dtype
            dy32 = dy.astype(jnp.float32)
            sum_dy = jnp.sum(dy32, axis=(0, 2, 3))
            sum_dyx = jnp.sum(dy32 * x.astype(jnp.float32), axis=(0, 2, 3))
            dbeta = sum_dy
            dgamma = inv * (sum_dyx - mean * sum_dy)
            a = inv * gamma
            b = -(inv * inv) * gamma * dgamma / n + 2.0 * dvar_ct / n
            c = -a * dbeta / n + (inv * inv) * gamma * mean * dgamma / n \
                + dmean_ct / n - 2.0 * dvar_ct * mean / n
            dx = (a.reshape(bshape).astype(x.dtype) * dy
                  + b.reshape(bshape).astype(x.dtype) * x
                  + c.reshape(bshape).astype(x.dtype))
            return dx, dgamma, dbeta, jnp.zeros_like(mean)
        xmu = x.astype(jnp.float32) - mean.reshape(bshape)
        xhat = xmu * inv.reshape(bshape)
        dy32 = dy.astype(jnp.float32)
        dbeta = jnp.sum(dy32, axis=(0, 2, 3))
        dgamma = jnp.sum(dy32 * xhat, axis=(0, 2, 3))
        dx = (inv * gamma).reshape(bshape) \
            * (dy32 - (dbeta / n).reshape(bshape)
               - xhat * (dgamma / n).reshape(bshape))
        dx = dx + (dmean_ct / n).reshape(bshape) \
            + (dvar_ct * 2.0 / n).reshape(bshape) * xmu
        return dx.astype(x.dtype), dgamma, dbeta, jnp.zeros_like(mean)

    bn.defvjp(bn_fwd, bn_bwd)
    return bn


LEANBWD = os.environ.get("LEANBWD", "0") == "1"
SGCOND = os.environ.get("SGCOND", "0") == "1"


def make_forward(cfg):
    bn_data, with_aux, smout, bn_custom = (
        cfg["bn_data"], cfg["aux"], cfg["smout"], cfg["bn_custom"])
    bn_core = _bn_custom_core(cfg.get("nocond", False),
                              cfg.get("nocenter", False),
                              cfg.get("autodiff", False)) \
        if bn_custom else None

    def bn_relu(p, aux_in, aux_out, name, x, relu=True):
        if bn_custom:
            center = jax.lax.stop_gradient(aux_in[name + "_mm"]) \
                if with_aux else jnp.zeros((x.shape[1],), jnp.float32)
            y, m, v = bn_core(x, p[name + "_g"], p[name + "_b"], center)
        else:
            m, v = _stats_onepass(x.astype(jnp.float32))
            inv = lax.rsqrt(v + EPS)
            scale = (inv * p[name + "_g"]).astype(x.dtype)
            shift = (p[name + "_b"] - m * inv * p[name + "_g"]) \
                .astype(x.dtype)
            y = x * scale.reshape(1, -1, 1, 1) + shift.reshape(1, -1, 1, 1)
        if with_aux:
            aux_out[name + "_mm"] = 0.9 * aux_in[name + "_mm"] \
                + 0.1 * jax.lax.stop_gradient(m)
            aux_out[name + "_mv"] = 0.9 * aux_in[name + "_mv"] \
                + 0.1 * jax.lax.stop_gradient(v)
        return jnp.maximum(y, 0) if relu else y

    def forward(p, aux_in, x, y):
        aux_out = {}
        h = x
        if bn_data:
            h = bn_relu(p, aux_in, aux_out, "bn_data", h, relu=False)
        h = conv(p, "conv0", h, 7, 2)
        h = bn_relu(p, aux_in, aux_out, "bn0", h)
        h = lax.reduce_window(h, -jnp.inf, lax.max, [1, 1, 3, 3],
                              [1, 1, 2, 2],
                              [(0, 0), (0, 0), (1, 1), (1, 1)])

        def unit(h, nm, s, first):
            a1 = bn_relu(p, aux_in, aux_out, nm + "_bn1", h)
            c1 = conv(p, nm + "_c1", a1, 1, 1)
            a2 = bn_relu(p, aux_in, aux_out, nm + "_bn2", c1)
            c2 = conv(p, nm + "_c2", a2, 3, s)
            a3 = bn_relu(p, aux_in, aux_out, nm + "_bn3", c2)
            c3 = conv(p, nm + "_c3", a3, 1, 1)
            sc = conv(p, nm + "_sc", a1, 1, s) if first else h
            return c3 + sc

        for si, (u, f) in enumerate(zip(UNITS, FILTERS)):
            for ui in range(u):
                nm = f"s{si}u{ui}"
                s = 2 if (ui == 0 and si > 0) else 1
                h = unit(h, nm, s, ui == 0)
        h = bn_relu(p, aux_in, aux_out, "bn_final", h)
        h = jnp.mean(h.astype(jnp.float32), axis=(2, 3))
        logits = h @ p["fc_w"] + p["fc_b"]
        if smout:
            probs = jax.nn.softmax(logits, axis=-1)
            onehot = jax.nn.one_hot(y, 1000, dtype=jnp.float32)
            # SoftmaxOutput semantics: loss whose dlogits == (p - onehot)/N
            # (valid-normalized), probs staged as a step output
            ll = jnp.take_along_axis(
                jnp.log(jnp.maximum(probs, 1e-30)), y[:, None], axis=1)
            loss = -jnp.mean(ll)
            return loss, (aux_out, probs)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
        return jnp.mean(lse - ll), (aux_out, None)

    return forward


def run(tag, cfg, iters=15):
    params, aux = build_params(cfg["bn_data"])
    if not cfg["aux"]:
        aux = {}
    forward = make_forward(cfg)
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    x = jnp.asarray(rng.rand(N, 3, 224, 224), jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 1000, (N,)), jnp.int32)

    def train(p, mom, aux_in, x, y):
        (loss, (aux_out, probs)), g = jax.value_and_grad(
            forward, has_aux=True)(p, aux_in, x, y)
        newp, newm = {}, {}
        for k in p:
            m = 0.9 * mom[k] + g[k]
            newm[k] = m
            newp[k] = p[k] - 0.1 * m
        return newp, newm, aux_out, loss, probs

    f = jax.jit(train, donate_argnums=(0, 1, 2))
    params, mom, aux, loss, probs = f(params, mom, aux, x, y)
    float(loss)
    t0 = time.time()
    for _ in range(iters):
        params, mom, aux, loss, probs = f(params, mom, aux, x, y)
    float(loss)
    dt = (time.time() - t0) / iters
    print("%-26s %.1f ms/step  %.0f img/s" % (tag, dt * 1e3, N / dt),
          flush=True)
    return dt


BASE = {"bn_data": False, "aux": False, "smout": False, "bn_custom": False,
        "nocond": False, "nocenter": False, "autodiff": False}

VARIANTS = {
    "base": {},
    "bn_data": {"bn_data": True},
    "aux": {"aux": True},
    "smout": {"smout": True},
    "bn_custom": {"bn_custom": True},
    "bn_custom+aux": {"bn_custom": True, "aux": True},
    "bn_custom_nocond": {"bn_custom": True, "nocond": True},
    "bn_custom_nocenter": {"bn_custom": True, "nocond": True,
                           "nocenter": True},
    "bn_centered_autodiff": {"bn_custom": True, "autodiff": True},
    "all": {"bn_data": True, "aux": True, "smout": True,
            "bn_custom": True},
}

if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    names = sys.argv[1:] or list(VARIANTS)
    for name in names:
        cfg = dict(BASE)
        cfg.update(VARIANTS[name])
        run(name, cfg)
