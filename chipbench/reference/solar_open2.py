"""The decoder of ``model_type`` ``solar_open2`` (Solar-Open2-250B), as its
``config.json`` sizes it, at one chip's share of each expert layer.  What the
``config.json`` does not spell out is listed under ``assumed`` in the
configuration's file, each with its reason.

Pre-norm residual stream of width ``hidden_size`` (d); ``RMS(x; g) = x *
rsqrt(mean(x^2) + rms_norm_eps) * g``; no matrix has a bias; no positions
anywhere (``use_rope`` false): the convolution and the decay carry order.

Block l:  h = x + Mixer_l(RMS(x));  x' = h + MoE_l(RMS(h)): every layer is an
    expert layer (``first_k_dense_replace`` 0).  Mixer_l is gated attention
    where l is in ``gqa_layers``, Kimi delta attention elsewhere.
Kimi delta attention (Kimi Linear, arXiv:2510.26692; ``linear_attn_config``:
    H ``num_heads`` of D ``head_dim`` for keys and values alike, a kernel of
    ``short_conv_kernel_size``), token t, head h:
    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        (causal, depthwise, a kernel a channel, no bias)
    q_h, k_h = q_h / sqrt(|q_h|^2 + 1e-6), k_h / sqrt(|k_h|^2 + 1e-6)
    g_t = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)   (H D log-decays)
    beta_t,h = 2 sigmoid(x W_beta)_h  (``kda_allow_neg_eigval``; else x 1)
    S <- Diag(exp g_t) S;  nu = beta (v_t - S^T k_t);  S <- S + k_t nu^T;
    o_t,h = S^T q_t / sqrt(D)      (S: D x D a head, float32, from zero)
    out = (RMS_D(o_t,h; one gain of D) * sigmoid((x W_ga) W_gb)) W_o
    The recurrence runs a token at a time (``lax.scan``): no chunk form, no
    carried state, no convolution tail.
Gated attention (``gqa_layers``): ``num_attention_heads`` query heads and
    ``num_key_value_heads`` KV heads of ``head_dim``, causal softmax at scale
    head_dim^(-1/2), no rotation, no q/k norm;  out = (softmax(q k^T) v *
    sigmoid(x W_g)) W_o, W_g: d -> heads x head_dim (``use_gqa_gate``).
MoE_l:  s = sigmoid(W_r u) over all ``n_routed_experts`` in float32; chosen =
    the ``num_experts_per_tok`` largest of s + b (a selection-only bias a
    layer); w = ``routed_scaling_factor`` s[chosen] / sum s[chosen]
    (``norm_topk_prob``);  y = sum over chosen e in [first, first + held) of
    w_e E_e(u)  +  S(u), every E_e and S a gated silu MLP of
    ``moe_intermediate_size`` (S: ``n_shared_experts`` x that).  The experts
    outside the share live on other chips: their part is left out here as in
    the program (a departure from the published whole, stated in the
    configuration's ``deployment``); S is on every chip.
logits = RMS(x_L) W_h^T  (untied head).

Attention is computed in blocks of ``QUERY_BLOCK`` queries against all the
keys, the experts one at a time and the head in blocks of the vocabulary,
each as a scan (unrolled, the compiler widens every matrix to float32 at
once).  ``params`` are handed over in the type the cell serves in and stay
so: each matrix is widened to float32 where it is used, never the tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_BLOCK = 16384
HEAD_ROWS = 256
QUERY_BLOCK = 512
L2_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def share(cfg):
    """``(first, held)``: the routed experts of each layer on this chip."""
    held = cfg.get("held_n_routed_experts") or cfg["n_routed_experts"]
    return int(cfg.get("first_held_expert", 0)), int(held)


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def _fc(x, p, name):
    return x @ _f32(p[name + "_weight"]).T


def _conv_silu(x, w):
    """Causal depthwise convolution over time, no bias, then silu: ``x``
    (B, T, C), ``w`` (C, K); position t reads t - K + 1 .. t, zeros before
    the sequence."""
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, i:i + t] * _f32(w)[:, i] for i in range(k)))


def delta_rule(q, k, v, g, beta, s0=None):
    """The recurrence a token at a time: ``q``, ``k``, ``v``, ``g`` (B, T,
    H, D), ``beta`` (B, T, H) -> ``(o (B, T, H, D), S_T (B, H, D, D))``,
    from ``s0`` (zero where None: the model's pass never carries one)."""
    b, _, h, d = q.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, d, d), jnp.float32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., :, None]
        nu = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))
        s = s + k_t[..., :, None] * nu[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s) * d ** -0.5

    s, o = jax.lax.scan(step, s0,
                        tuple(jnp.moveaxis(x, 1, 0)
                              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _kda(p, n, cfg, x):
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    b, t, _ = x.shape
    n = n + "kda_"
    conv_w = p[n + "conv_weight"]                       # [q | k | v] rows
    q, k, v = (_conv_silu(_fc(x, p, n + part),
                          conv_w[i * h * d:(i + 1) * h * d]
                          ).reshape(b, t, h, d)
               for i, part in enumerate("qkv"))
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, -1, keepdims=True) + L2_EPS)
    pair = lambda part: (_fc(x, p, n + part + "_a")
                         @ _f32(p[n + part + "_b_weight"]).T)
    g = -jnp.exp(_f32(p[n + "A_log"]))[:, None] * jax.nn.softplus(
        pair("f") + _f32(p[n + "dt_bias"])).reshape(b, t, h, d)
    beta = jax.nn.sigmoid(_fc(x, p, n + "beta")) \
        * (2.0 if cfg.get("kda_allow_neg_eigval") else 1.0)
    o, _ = delta_rule(unit(q), unit(k), v, g, beta)
    o = _rms(o, p[n + "out_norm_gamma"], cfg["rms_norm_eps"])
    return _fc(o.reshape(b, t, h * d) * jax.nn.sigmoid(pair("g")), p,
               n + "out")


def _attention(p, n, cfg, x):
    b, t, _ = x.shape
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kvh = cfg["num_key_value_heads"]
    q = _fc(x, p, n + "q").reshape(b, t, heads, hd)
    k = jnp.repeat(_fc(x, p, n + "k").reshape(b, t, kvh, hd),
                   heads // kvh, axis=2)
    v = jnp.repeat(_fc(x, p, n + "v").reshape(b, t, kvh, hd),
                   heads // kvh, axis=2)
    pad = -t % QUERY_BLOCK
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    at = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)

    def rows(args):
        q_blk, i = args                     # (B, Q, H, D), (Q,)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * hd ** -0.5
        s = jnp.where(jnp.arange(t)[None, :] <= i[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, (jnp.moveaxis(
        qp.reshape(b, -1, QUERY_BLOCK, heads, hd), 1, 0), at))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    if cfg.get("use_gqa_gate"):
        o = o * jax.nn.sigmoid(_fc(x, p, n + "gate"))
    return _fc(o, p, n + "attout")


def _gated(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def _experts(p, n, cfg, x):
    first, held = share(cfg)
    s = jax.nn.sigmoid(x @ _f32(p[n + "moe_gate_weight"]))
    _, chosen = jax.lax.top_k(s + _f32(p[n + "moe_gate_bias"]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = float(cfg.get("routed_scaling_factor") or 1.0) * w

    def one(y, e):                              # one expert at a time
        gate, up, down = (jax.lax.dynamic_index_in_dim(
            p[n + "moe_expert_%s_weight" % part], e, keepdims=False)
            for part in ("gate", "up", "down"))
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1,
                     keepdims=True)
        return y + we * _gated(x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if cfg.get("n_shared_experts"):
        y = y + _gated(x, p[n + "moe_shared_gate_weight"],
                       p[n + "moe_shared_up_weight"],
                       p[n + "moe_shared_down_weight"])
    return y


def _block(p, l, cfg, h):
    n, eps = "layer%d_" % l, cfg["rms_norm_eps"]
    mixer = _attention if l in cfg["gqa_layers"] else _kda
    h = h + mixer(p, n, cfg, _rms(h, p[n + "att_norm_gamma"], eps))
    return h + _experts(p, n, cfg, _rms(h, p[n + "ffn_norm_gamma"], eps))


def _head(p, h):
    """Over blocks of rows and of the vocabulary, a block at a time: a
    caller that reads the last few rows of six thousand computes only their
    blocks."""
    head = p["head_weight"]
    v, d = head.shape
    block = VOCAB_BLOCK if v % VOCAB_BLOCK == 0 else v
    blocks = head.reshape(v // block, block, d)

    def rows(x):
        out = jax.lax.map(lambda w: x @ _f32(w).T, blocks)  # (nb, B, R, blk)
        return jnp.moveaxis(out, 0, -2).reshape(x.shape[:-1] + (v,))

    return jnp.concatenate([rows(h[:, r:r + HEAD_ROWS])
                            for r in range(0, h.shape[1], HEAD_ROWS)], 1)


def hidden(params, cfg, tokens, layers=None):
    """The stack's last hidden state ``(B, T, d)``, before the final norm."""
    layers = layers_run(cfg) if layers is None else layers
    h = _f32(jnp.take(params["embed_weight"], jnp.asarray(tokens, jnp.int32),
                      axis=0))
    for l in range(layers):
        h = _block(params, l, cfg, h)
    return h


def forward(params, cfg, tokens, layers=None, since=0):
    """Logits ``(B, T - since, vocab)`` of integer ``tokens (B, T)``, float32,
    at positions ``since ..``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, cfg, tokens, layers)[:, since:]
        return _head(params, _rms(h, params["final_norm_gamma"],
                                  cfg["rms_norm_eps"]))
