"""The decoder of ``model_type`` ``falcon_h1`` (Falcon-H1 0.5B to 34B), as its
``config.json`` sizes it: in every block a Mamba-2 mixer and grouped-query
attention read one normed input and are summed into the residual stream,
then a gated MLP; muP multipliers throughout.

``RMS(x; g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``.  Per token, hidden
``d``:

    h  = E[token] * embedding_multiplier
    block:  r = h;  u = RMS(h)
        m = Mixer(u * ssm_in_multiplier) * ssm_out_multiplier
        a = Attn(u * attention_in_multiplier) * attention_out_multiplier
        h = r + m + a
        u2 = RMS(h)
        h = h + Wd(Wu u2 * silu(Wg u2 * mlp_multipliers[0]))
                * mlp_multipliers[1]
    logits = Wh RMS(h) * lm_head_multiplier          (untied head)

Attn: ``num_attention_heads`` query heads and ``num_key_value_heads`` KV
heads of ``head_dim``, no biases; keys x ``key_multiplier``; rotary over the
whole head at ``rope_theta``, half-split pairing; logits / sqrt(head_dim);
causal; head h reads KV head h // (H / H_kv).

Mixer (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, N =
``mamba_d_state``, G = ``mamba_n_groups``, K = ``mamba_d_conv``):

    [z | x | B | C | dt] = (W_in x) * mup     mup = ``ssm_multipliers`` over
                                              the five segments
    xBC = silu(conv1d_causal_depthwise([x | B | C]; K taps, bias))
    dt_k = softplus(dt_k + dt_bias_k);  A_k = -exp(A_log_k)
    S_t,k = exp(dt_t,k A_k) S_t-1,k + dt_t,k x_t,k (x) B_t,g      g = k // (H/G)
    y_t,k = S_t,k C_t,g + D_k x_t,k
    y = GroupRMS(y * silu(z); G groups, gamma)      (``mamba_rms_norm``,
                                                    ``mamba_norm_before_gate`` false)
    out = W_out y

The recurrence is a ``lax.scan`` over single tokens from zero state: no
chunked algorithm, no cache.  Departures from the published form, each
because the configuration's file ``assumed`` it (the catalog does not
settle them): the order of the segments of ``W_in`` and of ``xBC``; the
gated norm grouped by ``mamba_n_groups``; no clamp on dt; half-split rotary.

``params`` come in the type the cell serves in (bfloat16, 10.5 GB at the
cell's size) and stay so: each matrix and each block of the vocabulary is
widened to float32 where it is used, never the tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_BLOCK = 16384


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def _rotate(x, theta):
    """x (B, T, H, D): half-split rotary over the whole head."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, n, cfg, x):
    b, t, _ = x.shape
    heads, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    q = (x @ _f32(p[n + "q_weight"]).T).reshape(b, t, heads, hd)
    k = (x @ _f32(p[n + "k_weight"]).T).reshape(b, t, kvh, hd)
    v = (x @ _f32(p[n + "v_weight"]).T).reshape(b, t, kvh, hd)
    k = k * cfg["key_multiplier"]               # before the rotation
    q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=2)
    v = jnp.repeat(v, heads // kvh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where(j <= i, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, heads * hd) @ _f32(p[n + "attout_weight"]).T


def mup_vector(cfg):
    """``ssm_multipliers`` spread over the segments z, x, B, C, dt."""
    d_ssm = cfg["mamba_d_ssm"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    widths = (d_ssm, d_ssm, bc, bc, cfg["mamba_n_heads"])
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, cfg["ssm_multipliers"])])


def _mixer(p, n, cfg, x):
    b, t, _ = x.shape
    h, pd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    ns, g, k = (cfg["mamba_d_state"], cfg["mamba_n_groups"],
                cfg["mamba_d_conv"])
    d_ssm, bc = cfg["mamba_d_ssm"], g * ns
    proj = (x @ _f32(p[n + "ssm_in_weight"]).T) * mup_vector(cfg)
    z, xbc, dt = (proj[..., :d_ssm], proj[..., d_ssm:2 * d_ssm + 2 * bc],
                  proj[..., 2 * d_ssm + 2 * bc:])
    # causal depthwise convolution: tap i reads the row K - 1 - i back
    w = _f32(p[n + "ssm_conv_weight"])                      # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + t] * w[:, i] for i in range(k))
    if cfg.get("mamba_conv_bias", True):
        xbc = xbc + _f32(p[n + "ssm_conv_bias"])
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_ssm].reshape(b, t, h, pd)
    bm = jnp.repeat(xbc[..., d_ssm:d_ssm + bc].reshape(b, t, g, ns),
                    h // g, axis=2)
    cm = jnp.repeat(xbc[..., d_ssm + bc:].reshape(b, t, g, ns),
                    h // g, axis=2)
    dt = jax.nn.softplus(dt + _f32(p[n + "ssm_dt_bias"]))   # no clamp
    a = -jnp.exp(_f32(p[n + "ssm_A_log"]))

    def token(s, inp):
        x_t, b_t, c_t, dt_t = inp                   # (B, H, ...) of one token
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((b, h, pd, ns), jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (xs, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + _f32(p[n + "ssm_D"])[:, None] * xs
    y = y.reshape(b, t, d_ssm) * jax.nn.silu(z)     # the gate, then the norm
    y = y.reshape(b, t, g, d_ssm // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = y.reshape(b, t, d_ssm) * _f32(p[n + "ssm_norm_gamma"])
    return y @ _f32(p[n + "ssm_out_weight"]).T


def forward(params, cfg, tokens, layers=None):
    """Logits ``(B, T, vocab)`` of integer ``tokens (B, T)``; float32."""
    p = params
    layers = cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]) \
        if layers is None else layers
    eps = cfg["rms_norm_eps"]
    gate_m, down_m = cfg["mlp_multipliers"]
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(jnp.take(p["embed_weight"], tokens, axis=0)) \
            * cfg["embedding_multiplier"]
        for l in range(layers):
            n = "layer%d_" % l
            u = _rms(h, p[n + "att_norm_gamma"], eps)
            m = _mixer(p, n, cfg, u * cfg["ssm_in_multiplier"]) \
                * cfg["ssm_out_multiplier"]
            a = _attention(p, n, cfg, u * cfg["attention_in_multiplier"]) \
                * cfg["attention_out_multiplier"]
            h = h + m + a
            u = _rms(h, p[n + "ffn_norm_gamma"], eps)
            gate = (u @ _f32(p[n + "ffn_gate_weight"]).T) * gate_m
            up = u @ _f32(p[n + "ffn_up_weight"]).T
            h = h + ((jax.nn.silu(gate) * up)
                     @ _f32(p[n + "ffn_down_weight"]).T) * down_m
        h = _rms(h, p["final_norm_gamma"], eps)
        head, v = p["head_weight"], p["head_weight"].shape[0]
        # the head over blocks of the vocabulary: whole in float32 it is
        # 5.35 GB beside the serving state
        return jnp.concatenate(
            [h @ _f32(head[i:i + VOCAB_BLOCK]).T
             for i in range(0, v, VOCAB_BLOCK)], -1) \
            * cfg["lm_head_multiplier"]


def loss(params, cfg, tokens, labels, layers=None):
    """Mean next-token cross-entropy over every position."""
    logp = jax.nn.log_softmax(forward(params, cfg, tokens, layers), axis=-1)
    labels = jnp.asarray(labels, jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
