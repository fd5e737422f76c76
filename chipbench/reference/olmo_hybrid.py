"""The decoder of ``model_type`` ``olmo_hybrid`` (Olmo-Hybrid-7B), as its
``config.json`` sizes it.  What the ``config.json`` does not spell out is
listed under ``assumed`` in the configuration's file, each with its reason.

Stream of width ``hidden_size`` (d = 3840); ``RMS(x; g) = x * rsqrt(mean(x^2)
+ rms_norm_eps) * g`` (1e-6); no matrix has a bias; no positions anywhere
(``rope_parameters.rope_theta`` null: no base, so no angle): the convolutions
and the decays carry order.

Block l (OLMo 2 / OLMo 3's reordered norm: the norm follows the sublayer):
    h = x + RMS(Mixer_l(x); g_att);  x' = h + RMS(MLP(h); g_ffn);
    MLP(u) = (silu(u W_gate) * (u W_up)) W_down, width ``intermediate_size``.
    Mixer_l is attention where ``layer_types[l]`` is ``"full_attention"`` (l %
    4 == 3), Gated DeltaNet where it is ``"linear_attention"``.
Gated DeltaNet (Yang, Kautz, Hatamizadeh, arXiv:2412.06464): H =
    ``linear_num_key_heads`` heads, d_k = ``linear_key_head_dim``, d_v =
    ``linear_value_head_dim``, a kernel of K = ``linear_conv_kernel_dim``;
    token t, head h:
    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        (W_q, W_k: d -> H d_k; W_v: d -> H d_v; causal, depthwise, K taps a
        channel, no bias)
    q_h, k_h = q_h / sqrt(|q_h|^2 + 1e-6), k_h / sqrt(|k_h|^2 + 1e-6);
        q_h times d_k^(-1/2)
    g_t,h = -exp(A_log_h) softplus((x W_a)_h + dt_bias_h)
        (ONE log-decay a head; W_a: d -> H)
    beta_t,h = 2 sigmoid((x W_b)_h)  (``linear_allow_neg_eigval``; else x 1)
    S <- exp(g_t,h) S;  nu = beta (v_t - S^T k_t);  S <- S + k_t nu^T;
    o_t,h = S^T q_t                (S: d_k x d_v a head, float32, from zero)
    out = (RMS_dv(o_t,h; one gain of d_v a layer) * silu(x W_g)) W_o
        (W_g: d -> H d_v; W_o: H d_v -> d)
    The recurrence runs a token at a time (``lax.scan``): no chunk form, no
    carried state, no convolution tail.
Attention (l % 4 == 3): q = RMS_d(x W_q; g_q), k = RMS_d(x W_k; g_k) over the
    WHOLE projection before the heads are cut (a gain of heads x head_dim
    each), v = x W_v; ``num_attention_heads`` query heads and
    ``num_key_value_heads`` KV heads of ``hidden_size / num_attention_heads``
    = 128, causal softmax at 128^(-1/2), no rotation;  out = att W_o.
logits = RMS(x_L; g_f) W_head^T  (untied head).

Attention is computed in blocks of ``QUERY_BLOCK`` queries against all the
keys and the head in blocks of the vocabulary, each as a scan (unrolled, the
compiler widens every matrix to float32 at once).  ``params`` are handed over
in the type the cell serves in and stay so: each matrix is widened to float32
where it is used, never the tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_BLOCK = 12544     # 100352 = 8 x 12544
HEAD_ROWS = 256
QUERY_BLOCK = 512
L2_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def head_dim(cfg):
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


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
    """The recurrence a token at a time: ``q``, ``k`` (B, T, H, Dk), ``v``
    (B, T, H, Dv), ``g`` and ``beta`` (B, T, H) -> ``(o (B, T, H, Dv), S_T
    (B, H, Dk, Dv))``, from ``s0`` (zero where None: the model's pass never
    carries one)."""
    b, _, h, dk = q.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        nu = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))
        s = s + k_t[..., :, None] * nu[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s) * dk ** -0.5

    s, o = jax.lax.scan(step, s0,
                        tuple(jnp.moveaxis(x, 1, 0)
                              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _gdn(p, n, cfg, x):
    h = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    b, t, _ = x.shape
    n = n + "gdn_"
    conv_w = p[n + "conv_weight"]                       # [q | k | v] rows
    q = _conv_silu(_fc(x, p, n + "q"), conv_w[:h * dk]).reshape(b, t, h, dk)
    k = _conv_silu(_fc(x, p, n + "k"),
                   conv_w[h * dk:2 * h * dk]).reshape(b, t, h, dk)
    v = _conv_silu(_fc(x, p, n + "v"),
                   conv_w[2 * h * dk:]).reshape(b, t, h, dv)
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, -1, keepdims=True) + L2_EPS)
    g = -jnp.exp(_f32(p[n + "A_log"])) * jax.nn.softplus(
        _fc(x, p, n + "a") + _f32(p[n + "dt_bias"]))
    beta = jax.nn.sigmoid(_fc(x, p, n + "b")) \
        * (2.0 if cfg.get("linear_allow_neg_eigval") else 1.0)
    o, _ = delta_rule(unit(q), unit(k), v, g, beta)
    o = _rms(o, p[n + "out_norm_gamma"], cfg["rms_norm_eps"])
    return _fc(o.reshape(b, t, h * dv) * jax.nn.silu(_fc(x, p, n + "g")), p,
               n + "out")


def _attention(p, n, cfg, x):
    b, t, _ = x.shape
    heads, hd, eps = cfg["num_attention_heads"], head_dim(cfg), \
        cfg["rms_norm_eps"]
    kvh = cfg["num_key_value_heads"]
    q = _rms(_fc(x, p, n + "q"), p[n + "q_norm_gamma"], eps)
    k = _rms(_fc(x, p, n + "k"), p[n + "k_norm_gamma"], eps)
    q = q.reshape(b, t, heads, hd)
    k = jnp.repeat(k.reshape(b, t, kvh, hd), heads // kvh, axis=2)
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
    return _fc(o, p, n + "attout")


def _mlp(p, n, x):
    return _fc(jax.nn.silu(_fc(x, p, n + "ffn_gate"))
               * _fc(x, p, n + "ffn_up"), p, n + "ffn_down")


def _block(p, l, cfg, h):
    n, eps = "layer%d_" % l, cfg["rms_norm_eps"]
    mixer = _attention if cfg["layer_types"][l] == "full_attention" else _gdn
    h = h + _rms(mixer(p, n, cfg, h), p[n + "att_norm_gamma"], eps)
    return h + _rms(_mlp(p, n, h), p[n + "ffn_norm_gamma"], eps)


def _head(p, h):
    """Over blocks of rows and of the vocabulary, a block at a time: a
    caller that reads the last few rows of fifteen hundred computes only
    their blocks."""
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
