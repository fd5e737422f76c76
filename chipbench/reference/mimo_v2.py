"""The text decoder of ``model_type`` ``mimo_v2`` (MiMo-V2-Flash / MiMo-V2.5),
as its ``config.json`` sizes it, at one chip's share of each expert layer.

Pre-norm residual stream of width ``hidden_size``;
``RMS(x; g) = x * rsqrt(mean(x^2) + layernorm_epsilon) * g``.

Attention, layer l (``hybrid_layer_pattern[l]``: 0 full, 1 window):
    n = RMS(h);  q = n Wq^T as H heads of ``head_dim``;  k = n Wk^T as H_kv
    heads of ``head_dim``;  v = n Wv^T as H_kv heads of ``v_head_dim``;
    H_kv = ``num_key_value_heads`` (full) | ``swa_num_key_value_heads``
    (window);  head h reads KV head h // (H / H_kv).
    Rotary on dims [0, R) of each q and k head, R = ``partial_rotary_factor``
    x ``head_dim`` rounded down to even, half-split pairing (dim i with
    i + R/2), angle = position * theta^(-2i/R), theta = ``rope_theta``
    (full) | ``swa_rope_theta`` (window); dims [R, head_dim) pass.
    l_ij = q_i . k_j / sqrt(head_dim); allowed j <= i (full), and also
    j > i - ``sliding_window`` (window: the query's own position counts).
    A window layer (``add_swa_attention_sink_bias``) has a learned sink s_h:
    p_ij = exp(l_ij - m) / (sum_j' exp(l_ij' - m) + exp(s_h - m)),
    m = max(max_j l_ij, s_h); the sink has no value.
    o_i = ``attention_value_scale`` * sum_j p_ij v_j;  h += concat_h(o) Wo^T.
Dense MLP (``moe_layer_freq[l]`` 0):  h += (silu(n Wg^T) * n Wu^T) Wd^T.
Experts (``moe_layer_freq[l]`` 1):  s = sigmoid(n Wr) over all
    ``n_routed_experts``; chosen = top ``num_experts_per_tok`` of s + b (b
    selects and is not in the weight: ``topk_method`` ``noaux_tc``, one
    group); w_e = s_e / (sum_chosen s + 1e-20) (``norm_topk_prob``);
    h += sum over chosen e in [first, first + held) of
    w_e (silu(n Wg_e) * n Wu_e) Wd_e.  The experts outside the share live on
    other chips: their part is left out here as in the program.
logits = RMS(h_L) Wh^T  (untied head).

``params`` are handed over in the type the cell serves in (bfloat16, 9 GB at
the cell's size) and stay so: each matrix, each expert and each block of the
vocabulary is widened to float32 where it is used, never the tree.
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


def _rotate(x, theta, rot):
    """x (B, T, H, D): half-split rotary on dims [0, rot)."""
    t, half = x.shape[1], rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


def share(cfg):
    """``(first, held)``: the experts of each MoE layer on this chip."""
    held = cfg.get("held_n_routed_experts") or cfg["n_routed_experts"]
    return int(cfg.get("first_held_expert", 0)), int(held)


def _attention(p, n, cfg, l, x):
    b, t, _ = x.shape
    window = bool(cfg["hybrid_layer_pattern"][l])
    heads, hd, vd = (cfg["num_attention_heads"], cfg["head_dim"],
                     cfg["v_head_dim"])
    kvh = cfg["swa_num_key_value_heads" if window
              else "num_key_value_heads"]
    theta = float(cfg["swa_rope_theta" if window else "rope_theta"])
    rot = int(hd * cfg["partial_rotary_factor"]) // 2 * 2
    q = (x @ _f32(p[n + "q_weight"]).T).reshape(b, t, heads, hd)
    k = (x @ _f32(p[n + "k_weight"]).T).reshape(b, t, kvh, hd)
    v = (x @ _f32(p[n + "v_weight"]).T).reshape(b, t, kvh, vd)
    q, k = _rotate(q, theta, rot), _rotate(k, theta, rot)
    k = jnp.repeat(k, heads // kvh, axis=2)
    v = jnp.repeat(v, heads // kvh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allowed = j <= i
    if window:
        allowed &= j > i - cfg["sliding_window"]
    s = jnp.where(allowed, s, -jnp.inf)
    sink = cfg["add_swa_attention_sink_bias" if window
               else "add_full_attention_sink_bias"]
    m = jnp.max(s, -1, keepdims=True)
    if sink:
        sk = _f32(p[n + "att_sink"]).reshape(1, heads, 1, 1)
        m = jnp.maximum(m, sk)
    e = jnp.exp(s - m)
    den = jnp.sum(e, -1, keepdims=True)
    if sink:
        den = den + jnp.exp(sk - m)
    o = cfg["attention_value_scale"] * jnp.einsum("bhqk,bkhd->bqhd",
                                                   e / den, v)
    return o.reshape(b, t, heads * vd) @ _f32(p[n + "attout_weight"]).T


def _experts(p, n, cfg, x):
    first, held = share(cfg)
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _f32(p[n + "moe_gate_weight"]))
    _, chosen = jax.lax.top_k(s + _f32(p[n + "moe_gate_bias"]), k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for e in range(held):                       # one expert at a time
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1,
                     keepdims=True)
        g = x @ _f32(p[n + "moe_expert_gate_weight"][e])
        u = x @ _f32(p[n + "moe_expert_up_weight"][e])
        y = y + we * ((jax.nn.silu(g) * u)
                      @ _f32(p[n + "moe_expert_down_weight"][e]))
    return y


def forward(params, cfg, tokens, layers=None, training=False):
    """Logits ``(B, T, vocab)`` of integer ``tokens (B, T)``; float32."""
    p = params
    layers = cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]) \
        if layers is None else layers
    eps = cfg["layernorm_epsilon"]
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(jnp.take(p["embed_weight"], tokens, axis=0))
        for l in range(layers):
            n = "layer%d_" % l
            h = h + _attention(p, n, cfg, l,
                               _rms(h, p[n + "att_norm_gamma"], eps))
            x = _rms(h, p[n + "ffn_norm_gamma"], eps)
            if cfg["moe_layer_freq"][l]:
                h = h + _experts(p, n, cfg, x)
            else:
                g = x @ _f32(p[n + "ffn_gate_weight"]).T
                u = x @ _f32(p[n + "ffn_up_weight"]).T
                h = h + (jax.nn.silu(g) * u) \
                    @ _f32(p[n + "ffn_down_weight"]).T
        h = _rms(h, p["final_norm_gamma"], eps)
        head, v = p["head_weight"], p["head_weight"].shape[0]
        return jnp.concatenate(
            [h @ _f32(head[i:i + VOCAB_BLOCK]).T
             for i in range(0, v, VOCAB_BLOCK)], -1)


def loss(params, cfg, tokens, labels, layers=None):
    """Mean next-token cross-entropy over every position."""
    logp = jax.nn.log_softmax(forward(params, cfg, tokens, layers), axis=-1)
    labels = jnp.asarray(labels, jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
