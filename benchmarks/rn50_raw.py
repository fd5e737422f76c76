"""Raw-JAX ResNet-50 v2 fwd+bwd+SGD, NCHW vs NHWC, to find the chip ceiling."""
import os
import sys
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from mxnet_tpu.cache_dirs import arm_compile_cache  # noqa: E402

arm_compile_cache()

N = int(os.environ.get("N", "256"))
LAYOUT = os.environ.get("LAYOUT", "NHWC")
CAXIS = 1 if LAYOUT == "NCHW" else 3
DN = ("NCHW", "OIHW", "NCHW") if LAYOUT == "NCHW" else ("NHWC", "HWIO", "NHWC")

S2D = os.environ.get("S2D", "0") == "1"  # space-to-depth conv0 (MLPerf trick)
# pad conv0's input channels 3 -> PAD0 with zeros (weights for the pad
# channels are zero and see zero inputs, so the math is exact); isolated
# per-shape timing says the 3-channel conv0 underfills the MXU
PAD0 = int(os.environ.get("PAD0", "0"))

rng = np.random.RandomState(0)
params = {}
FLOPS = [0]


def conv_w(name, cin, cout, k):
    shape = (cout, cin, k, k) if LAYOUT == "NCHW" else (k, k, cin, cout)
    params[name] = jnp.asarray(rng.normal(0, 0.05, shape), jnp.float32)


def bn_w(name, c):
    params[name + "_g"] = jnp.ones((c,), jnp.float32)
    params[name + "_b"] = jnp.zeros((c,), jnp.float32)


def conv(p, name, x, k, s):
    w = p[name].astype(jnp.bfloat16)
    pad = k // 2
    cin = w.shape[1] if LAYOUT == "NCHW" else w.shape[2]
    cout = w.shape[0] if LAYOUT == "NCHW" else w.shape[3]
    h = x.shape[2 if LAYOUT == "NCHW" else 1]
    ho = (h + 2 * pad - k) // s + 1
    FLOPS[0] += 2 * N * cout * cin * k * k * ho * ho
    return lax.conv_general_dilated(x, w, (s, s), [(pad, pad)] * 2,
                                    dimension_numbers=DN)


BN_MODE = os.environ.get("BN", "naive")
REMAT = os.environ.get("REMAT", "0") == "1"


def bn_relu(p, name, x, relu=True):
    if BN_MODE == "none":
        return jnp.maximum(x, 0) if relu else x
    red = tuple(i for i in range(4) if i != CAXIS)
    bshape = tuple(x.shape[CAXIS] if i == CAXIS else 1 for i in range(4))
    if BN_MODE == "onepass":
        # sum and sumsq in one fused reduction pass (var = E[x^2]-E[x]^2)
        x32 = x.astype(jnp.float32)
        m = jnp.mean(x32, axis=red)
        v = jnp.maximum(jnp.mean(jnp.square(x32), axis=red) - jnp.square(m),
                        0.0)
    else:
        x32 = x.astype(jnp.float32) if BN_MODE != "bf16" else x
        m = jnp.mean(x32, axis=red)
        v = jnp.var(x32, axis=red)
        if BN_MODE == "bf16":
            m, v = m.astype(jnp.float32), v.astype(jnp.float32)
    inv = lax.rsqrt(v + 2e-5)
    scale = (inv * p[name + "_g"]).astype(x.dtype).reshape(bshape)
    shift = (p[name + "_b"] - m * inv * p[name + "_g"]).astype(x.dtype).reshape(bshape)
    y = x * scale + shift
    return jnp.maximum(y, 0) if relu else y


UNITS = [3, 4, 6, 3]
FILTERS = [256, 512, 1024, 2048]

# build params
if S2D:
    conv_w("conv0", 12, 64, 4)  # 2x2 space-to-depth: 224x224x3 -> 112x112x12
elif PAD0:
    conv_w("conv0", PAD0, 64, 7)
else:
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
params["fc_w"] = jnp.asarray(rng.normal(0, 0.01, (2048, 1000)), jnp.float32)
params["fc_b"] = jnp.zeros((1000,), jnp.float32)


def forward(p, x, y):
    if S2D:
        # x arrives pre-space-to-depth'd as (N,112,112,12); 4x4/s2 conv == 7x7/s2
        # on the original image up to the (negligible) 8th tap row/col
        h = conv(p, "conv0", x, 4, 1)
    else:
        h = conv(p, "conv0", x, 7, 2)
    h = bn_relu(p, "bn0", h)
    # maxpool 3x3 s2
    pads = [(0, 0)] * 4
    pads[2 if LAYOUT == "NCHW" else 1] = (1, 1)
    pads[3 if LAYOUT == "NCHW" else 2] = (1, 1)
    win = [1, 1, 3, 3] if LAYOUT == "NCHW" else [1, 3, 3, 1]
    st = [1, 1, 2, 2] if LAYOUT == "NCHW" else [1, 2, 2, 1]
    h = lax.reduce_window(h, -jnp.inf, lax.max, win, st, pads)
    from jax.ad_checkpoint import checkpoint_name

    def unit(h, nm, s, first):
        a1 = bn_relu(p, nm + "_bn1", h)
        c1 = checkpoint_name(conv(p, nm + "_c1", a1, 1, 1), "conv")
        a2 = bn_relu(p, nm + "_bn2", c1)
        c2 = checkpoint_name(conv(p, nm + "_c2", a2, 3, s), "conv")
        a3 = bn_relu(p, nm + "_bn3", c2)
        c3 = conv(p, nm + "_c3", a3, 1, 1)
        sc = conv(p, nm + "_sc", a1, 1, s) if first else h
        return c3 + sc

    if REMAT:
        unit = jax.checkpoint(
            unit, policy=jax.checkpoint_policies.save_only_these_names("conv"),
            static_argnums=(1, 2, 3))

    cin = 64
    for si, (u, f) in enumerate(zip(UNITS, FILTERS)):
        mid = f // 4
        for ui in range(u):
            nm = f"s{si}u{ui}"
            s = 2 if (ui == 0 and si > 0) else 1
            h = unit(h, nm, s, ui == 0)
            cin = f
    h = bn_relu(p, "bn_final", h)
    h = jnp.mean(h.astype(jnp.float32), axis=tuple(i for i in range(1, 4) if i != CAXIS))
    logits = h @ p["fc_w"] + p["fc_b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    return jnp.mean(lse - ll)


MODE = os.environ.get("MODE", "train")
FUSED = os.environ.get("FUSED", "0") == "1"  # pallas fused BN+ReLU+1x1conv


def _channel_stats(x2d):
    x32 = x2d.astype(jnp.float32)
    return jnp.sum(x32, axis=0), jnp.sum(jnp.square(x32), axis=0)


def _bn_coeffs(p, name, s1, s2, count):
    mean = s1 / count
    var = jnp.maximum(s2 / count - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + 2e-5)
    g = p[name + "_g"]
    return inv * g, p[name + "_b"] - mean * inv * g


def forward_fused(p, x, y):
    """NHWC trunk where BN statistics flow through matmul epilogues and
    BN-apply+ReLU rides the 1x1-conv prologues (ops/pallas_fused kernels)."""
    from mxnet_tpu.ops import pallas_fused as pf

    assert LAYOUT == "NHWC" and not S2D
    h = conv(p, "conv0", x, 7, 2)
    h = bn_relu(p, "bn0", h)
    h = lax.reduce_window(h, -jnp.inf, lax.max, [1, 3, 3, 1], [1, 2, 2, 1],
                          [(0, 0), (1, 1), (1, 1), (0, 0)])

    hs1, hs2 = _channel_stats(h.reshape(-1, h.shape[-1]))
    for si, (u, f) in enumerate(zip(UNITS, FILTERS)):
        mid = f // 4
        for ui in range(u):
            nm = f"s{si}u{ui}"
            s = 2 if (ui == 0 and si > 0) else 1
            b, hh, ww, c = h.shape
            m = b * hh * ww
            sc1, sh1 = _bn_coeffs(p, nm + "_bn1", hs1, hs2, m)
            h2d = h.reshape(m, c)
            w1 = p[nm + "_c1"].reshape(c, mid).astype(jnp.bfloat16)
            c1, c1s1, c1s2 = pf.fused_scale_relu_matmul(h2d, sc1, sh1, w1)
            sc2, sh2 = _bn_coeffs(p, nm + "_bn2", c1s1, c1s2, m)
            a2 = jnp.maximum(c1.astype(jnp.float32) * sc2 + sh2, 0.0)
            a2 = a2.astype(h.dtype).reshape(b, hh, ww, mid)
            c2 = conv(p, nm + "_c2", a2, 3, s)
            ho, wo = c2.shape[1], c2.shape[2]
            m2 = b * ho * wo
            c2d = c2.reshape(m2, mid)
            c2s1, c2s2 = _channel_stats(c2d)
            sc3, sh3 = _bn_coeffs(p, nm + "_bn3", c2s1, c2s2, m2)
            if ui == 0:
                scd = h2d if s == 1 else h[:, ::2, ::2, :].reshape(m2, c)
                wsc = p[nm + "_sc"].reshape(c, f).astype(jnp.bfloat16)
                res, _, _ = pf.fused_scale_relu_matmul(scd, sc1, sh1, wsc)
            else:
                res = h2d
            w3 = p[nm + "_c3"].reshape(mid, f).astype(jnp.bfloat16)
            out, hs1, hs2 = pf.fused_scale_relu_matmul(
                c2d, sc3, sh3, w3, residual=res)
            h = out.reshape(b, ho, wo, f)
    scf, shf = _bn_coeffs(p, "bn_final", hs1, hs2,
                          h.shape[0] * h.shape[1] * h.shape[2])
    hf = jnp.maximum(h.astype(jnp.float32) * scf + shf, 0.0)
    hv = jnp.mean(hf, axis=(1, 2))
    logits = hv @ p["fc_w"] + p["fc_b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    return jnp.mean(lse - ll)


def train(p, mom, x, y):
    fwd = forward_fused if FUSED else forward
    if MODE == "fwd":
        return p, mom, fwd(p, x, y)
    loss, g = jax.value_and_grad(fwd)(p, x, y)
    newp, newm = {}, {}
    for k in p:
        m = 0.9 * mom[k] + g[k]
        newm[k] = m
        newp[k] = p[k] - 0.1 * m
    return newp, newm, loss


mom = {k: jnp.zeros_like(v) for k, v in params.items()}
cin0 = PAD0 if PAD0 else 3
if LAYOUT == "NCHW":
    x = np.zeros((N, cin0, 224, 224), np.float32)
    x[:, :3] = rng.rand(N, 3, 224, 224)
    x = jnp.asarray(x, jnp.bfloat16)
elif S2D:
    x = jnp.asarray(rng.rand(N, 112, 112, 12), jnp.bfloat16)
else:
    x = np.zeros((N, 224, 224, cin0), np.float32)
    x[..., :3] = rng.rand(N, 224, 224, 3)
    x = jnp.asarray(x, jnp.bfloat16)
y = jnp.asarray(rng.randint(0, 1000, (N,)), jnp.int32)

f = jax.jit(train, donate_argnums=(0, 1))
if os.environ.get("COST", "0") == "1":
    compiled = f.lower(params, mom, x, y).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    print("raw", {k: ca.get(k) for k in ("flops", "bytes accessed")},
          flush=True)
    hlo_out = os.environ.get("HLO_OUT")
    if hlo_out:
        with open(hlo_out, "w") as fh:
            fh.write(compiled.as_text())
    raise SystemExit
t0 = time.time()
params, mom, loss = f(params, mom, x, y)
float(loss)
print(f"compile+first: {time.time()-t0:.1f}s, flops/step counted={FLOPS[0]/1e12:.2f}T (fwd only)", flush=True)
t0 = time.time()
iters = 20
for _ in range(iters):
    params, mom, loss = f(params, mom, x, y)
float(loss)
dt = (time.time() - t0) / iters
tf = 3 * FLOPS[0] / dt / 1e12
print(f"{LAYOUT} N={N}: {dt*1e3:.1f} ms/step, {N/dt:.0f} img/s, "
      f"{tf:.1f} TFLOP/s, MFU {tf/197*100:.1f}%", flush=True)
