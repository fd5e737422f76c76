"""The decoder of ``model_type`` ``exaone_moe`` (K-EXAONE-236B-A23B) with its
multi-token-prediction block, as its ``config.json`` sizes it, at one chip's
share of each expert layer.  What the ``config.json`` does not say is listed
under ``assumed`` in the configuration's file, each line with its source.

Pre-norm residual stream of width ``hidden_size`` (d);
``RMS(x; g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``; no matrix has a bias.

Block l:  h = x + Attn_l(RMS(x));  x' = h + FFN_l(RMS(h)).
Attn_l:  q = n Wq^T as ``num_attention_heads`` heads of ``head_dim``, k and v
    as ``num_key_value_heads`` heads; every q head and every k head is normed
    by an RMS with one learned gain of ``head_dim`` (``q_norm`` / ``k_norm``).
    ``layer_types[l] == "sliding_attention"``: rotary over all ``head_dim``
    dims, theta ``rope_parameters.rope_theta``, half-split pairing (dim i
    with i + head_dim/2), and query i sees keys i - ``sliding_window`` + 1 ..
    i.  ``"full_attention"``: causal over the whole context, no rotary.
    Softmax of q . k / sqrt(head_dim); head h reads KV head h // (H / H_kv);
    output Wo.  No sink, no value scale.
FFN_l, ``mlp_layer_types[l] == "dense"``:  (silu(n Wg^T) * n Wu^T) Wd^T at
    ``intermediate_size``.
FFN_l, ``"sparse"``:  s = sigmoid(n Wr) over ``num_experts``; chosen = the
    ``num_experts_per_tok`` largest of s + b (b selects and is not in the
    weight; ``n_group`` 1: no group limit); w = ``routed_scaling_factor`` *
    s[chosen] / (sum s[chosen] + 1e-20);  y = sum over chosen e in [first,
    first + held) of w_e E_e(n)  +  S(n), every E_e and S a gated silu MLP of
    ``moe_intermediate_size`` (S: ``num_shared_experts`` x that).  The
    experts outside the share live on other chips: their part is left out
    here as in the program; S is on every chip.
logits = RMS(x_L) Wh^T  (untied head).
Multi-token prediction (``num_nextn_predict_layers`` 1, full attention): at
    position i, u = Wp [RMS_e(Emb[t[i+1]]) ; RMS_h(x_L[i])], one block of the
    sparse kind over u (its own keys and values: position i attends u's
    positions <= i), logits' = RMS_m(out) Wh^T: the distribution of t[i+2].
    Embedding and head are the main model's.

``params`` are handed over in the type the cell serves in (bfloat16, 12.4 GB
at the cell's size) and stay so: each matrix, each expert and each block of
the vocabulary is widened to float32 where it is used, never the tree.
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
    """x (B, T, H, D): half-split rotary over all D dims."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def share(cfg):
    """``(first, held)``: the routed experts of each layer on this chip."""
    held = cfg.get("held_num_experts") or cfg["num_experts"]
    return int(cfg.get("first_held_expert", 0)), int(held)


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def _attention(p, n, cfg, window, x):
    b, t, _ = x.shape
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kvh, eps = cfg["num_key_value_heads"], cfg["rms_norm_eps"]
    q = (x @ _f32(p[n + "q_weight"]).T).reshape(b, t, heads, hd)
    k = (x @ _f32(p[n + "k_weight"]).T).reshape(b, t, kvh, hd)
    v = (x @ _f32(p[n + "v_weight"]).T).reshape(b, t, kvh, hd)
    q = _rms(q, p[n + "q_norm_gamma"], eps)
    k = _rms(k, p[n + "k_norm_gamma"], eps)
    if window:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    k = jnp.repeat(k, heads // kvh, axis=2)
    v = jnp.repeat(v, heads // kvh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allowed = j <= i
    if window:
        allowed &= j > i - window
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), -1), v)
    return o.reshape(b, t, heads * hd) @ _f32(p[n + "attout_weight"]).T


def _gated(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def _experts(p, n, cfg, x):
    first, held = share(cfg)
    s = jax.nn.sigmoid(x @ _f32(p[n + "moe_gate_weight"]))
    _, chosen = jax.lax.top_k(s + _f32(p[n + "moe_gate_bias"]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, -1)
    w = float(cfg.get("routed_scaling_factor") or 1.0) * w \
        / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for e in range(held):                       # one expert at a time
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1,
                     keepdims=True)
        y = y + we * _gated(x, p[n + "moe_expert_gate_weight"][e],
                            p[n + "moe_expert_up_weight"][e],
                            p[n + "moe_expert_down_weight"][e])
    if cfg.get("num_shared_experts"):
        y = y + _gated(x, p[n + "moe_shared_gate_weight"],
                       p[n + "moe_shared_up_weight"],
                       p[n + "moe_shared_down_weight"])
    return y


def _block(p, n, cfg, window, sparse, h):
    eps = cfg["rms_norm_eps"]
    h = h + _attention(p, n, cfg, window,
                       _rms(h, p[n + "att_norm_gamma"], eps))
    x = _rms(h, p[n + "ffn_norm_gamma"], eps)
    if sparse:
        return h + _experts(p, n, cfg, x)
    return h + _gated(x, _f32(p[n + "ffn_gate_weight"]).T,
                      _f32(p[n + "ffn_up_weight"]).T,
                      _f32(p[n + "ffn_down_weight"]).T)


def _head(p, h):
    head, v = p["head_weight"], p["head_weight"].shape[0]
    return jnp.concatenate([h @ _f32(head[i:i + VOCAB_BLOCK]).T
                            for i in range(0, v, VOCAB_BLOCK)], -1)


def hidden(params, cfg, tokens, layers=None):
    """The stack's last hidden state ``(B, T, d)``, before the final norm."""
    layers = layers_run(cfg) if layers is None else layers
    h = _f32(jnp.take(params["embed_weight"], jnp.asarray(tokens, jnp.int32),
                      axis=0))
    for l in range(layers):
        window = cfg["sliding_window"] \
            if cfg["layer_types"][l] == "sliding_attention" else 0
        h = _block(params, "layer%d_" % l, cfg, window,
                   cfg["mlp_layer_types"][l] == "sparse", h)
    return h


def forward(params, cfg, tokens, layers=None, since=0):
    """Logits ``(B, T - since, vocab)`` of integer ``tokens (B, T)``, float32,
    at positions ``since ..``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, cfg, tokens, layers)[:, since:]
        return _head(params, _rms(h, params["final_norm_gamma"],
                                  cfg["rms_norm_eps"]))


def forward_both(params, cfg, tokens, layers=None, since=0):
    """``(forward(...), forward_mtp(...))`` from one pass over the stack."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, cfg, tokens, layers)
        main = _head(params, _rms(h[:, since:], params["final_norm_gamma"],
                                  cfg["rms_norm_eps"]))
        return main, _block_logits(params, cfg, tokens, h, since)


def forward_mtp(params, cfg, tokens, layers=None, since=0):
    """The prediction block's logits under teacher forcing: ``(B, T - 1 -
    since, vocab)``; row i - since is the distribution of token i + 2 given
    tokens 0 .. i + 1, for i = since .. T - 2."""
    with jax.default_matmul_precision("highest"):
        return _block_logits(params, cfg, tokens,
                             hidden(params, cfg, tokens, layers), since)


def _block_logits(p, cfg, tokens, h, since):
    eps = cfg["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    nxt = _f32(jnp.take(p["embed_weight"], tokens[:, 1:], axis=0))
    u = jnp.concatenate([_rms(nxt, p["mtp_enorm_gamma"], eps),
                         _rms(h[:, :-1], p["mtp_hnorm_gamma"], eps)], -1) \
        @ _f32(p["mtp_proj_weight"]).T
    u = _block(p, "mtp_", cfg, 0, True, u)[:, since:]
    return _head(p, _rms(u, p["mtp_final_norm_gamma"], eps))
