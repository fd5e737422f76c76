"""The decoder of ``model_type`` ``mistral4`` (Mistral-Small-4-119B-2603, the
language model), as its ``config.json`` sizes it, at one chip's share of each
expert layer.  The three choices the ``config.json`` does not spell out are
listed under ``assumed`` in the configuration's file, each with its source.

Pre-norm residual stream of width ``hidden_size`` (d); ``RMS(x; g) = x *
rsqrt(mean(x^2) + rms_norm_eps) * g``; no matrix has a bias.

Block l:  h = x + Attn_l(RMS(x));  x' = h + MoE_l(RMS(h)): every layer is an
    expert layer (``first_k_dense_replace`` 0).
Attn_l (multi-head latent attention, DeepSeek-V2 section 2.1), position p,
    head i of ``num_attention_heads``:
    c_q = RMS(W_qa u; one gain of ``q_lora_rank``);  q_i = W_qb,i c_q =
    [q_i^nope (``qk_nope_head_dim``) ; q_i^rope (``qk_rope_head_dim``)].
    [c ; k^rope] = W_kva u;  c <- RMS(c; one gain of ``kv_lora_rank``);
    k^rope is ONE vector a position, shared by all heads.
    [k_i^nope ; v_i (``v_head_dim``)] = W_kvb,i c.
    q_i^rope and k^rope are turned by p f_j on the pairs (2j, 2j + 1)
    (``rope_interleave``), f_j YaRN's blend of theta_j / factor and theta_j
    (:func:`frequencies`); cos and sin x m(factor, mscale) / m(factor,
    mscale_all_dim), m(s, a) = 0.1 a ln s + 1.
    The whole q_i, after the rotation, x 1 + beta ln(1 + floor(p /
    original_max_position_embeddings)), beta ``llama_4_scaling_beta``.
    score_i(p, s) = sigma (q_i^nope . k_i,s^nope + q_i^rope . k_s^rope), s <=
    p (``sliding_window`` null), sigma = qk_head_dim^(-1/2) m(factor,
    mscale_all_dim)^2;  o_i = sum_s softmax_s(score_i) v_i,s;  output W_o.
    The keys and values are EXPANDED here, position by position, as the
    paper's equations 9 to 11 write them; the absorbed form that serving's
    decode rows use is the same sum in another order and is not used here.
MoE_l:  s = softmax(W_g u) over all ``n_routed_experts`` in float32; chosen =
    the ``num_experts_per_tok`` largest (no selection bias, ``n_group`` 1: no
    group limit); w = ``routed_scaling_factor`` s[chosen] / sum s[chosen]
    (``norm_topk_prob``);  y = sum over chosen e in [first, first + held) of
    w_e E_e(u)  +  S(u), every E_e and S a gated silu MLP of
    ``moe_intermediate_size`` (S: ``n_shared_experts`` x that).  The experts
    outside the share live on other chips: their part is left out here as in
    the program (a departure from the published whole, stated in the
    configuration's ``deployment``); S is on every chip.
logits = RMS(x_L) W_h^T  (untied head).

Attention is computed in blocks of ``QUERY_BLOCK`` queries against all the
keys (whole, a head's scores over 12,296 positions are 0.6 GB and there are
32), the head in blocks of the vocabulary.  ``params`` are handed over in the
type the cell serves in and stay so: each matrix, each expert and each block
of the vocabulary is widened to float32 where it is used, never the tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384
HEAD_ROWS = 256
QUERY_BLOCK = 512


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def mscale(factor, a):
    """YaRN's m(s, a) = 0.1 a ln s + 1 (1 at s <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def ramp(rp, dim):
    """``(lo, hi)`` of YaRN's blend: floor(corr(beta_fast)), ceil(corr(
    beta_slow)), corr(n) = dim ln(original_max / (2 pi n)) / (2 ln theta)."""
    corr = lambda n: dim * math.log(
        rp["original_max_position_embeddings"] / (2 * math.pi * n)) \
        / (2 * math.log(rp["rope_theta"]))
    return (max(math.floor(corr(rp["beta_fast"])), 0),
            min(math.ceil(corr(rp["beta_slow"])), dim - 1))


def frequencies(cfg):
    """f_j, j = 0 .. qk_rope_head_dim / 2 - 1 (float64): theta_j =
    theta^(-2j / dim); under ``rope_type`` yarn f_j = (1 - g_j) theta_j /
    factor + g_j theta_j, g_j = 1 - clip((j - lo) / (hi - lo), 0, 1)."""
    rp, dim = cfg["rope_parameters"], cfg["qk_rope_head_dim"]
    j = np.arange(dim // 2, dtype=np.float64)
    theta = float(rp["rope_theta"]) ** (-2.0 * j / dim)
    if rp.get("rope_type") != "yarn":
        return theta
    lo, hi = ramp(rp, dim)
    g = 1.0 - np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - g) * theta / rp["factor"] + g * theta


def softmax_scale(cfg):
    """sigma: qk_head_dim^(-1/2), x m(factor, mscale_all_dim)^2 under yarn
    with ``mscale_all_dim``."""
    rp = cfg["rope_parameters"]
    sigma = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rp.get("rope_type") == "yarn" and rp.get("mscale_all_dim"):
        sigma *= mscale(rp["factor"], rp["mscale_all_dim"]) ** 2
    return sigma


def query_temperature(cfg, positions):
    """1 + beta ln(1 + floor(p / original_max)); 1 where no beta."""
    rp = cfg["rope_parameters"]
    beta = float(rp.get("llama_4_scaling_beta") or 0.0)
    if not beta:
        return jnp.ones(positions.shape, jnp.float32)
    steps = positions // int(rp["original_max_position_embeddings"])
    return 1.0 + beta * jnp.log1p(steps.astype(jnp.float32))


def _rotate(x, cfg):
    """x (B, T, H, rope): pairs (2j, 2j + 1) turned by p f_j (half-split
    pairs where ``rope_interleave`` is false)."""
    rp = cfg["rope_parameters"]
    t = x.shape[1]
    trig = 1.0
    if rp.get("rope_type") == "yarn":
        trig = mscale(rp["factor"], rp.get("mscale", 1)) \
            / mscale(rp["factor"], rp.get("mscale_all_dim", 0))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(frequencies(cfg), jnp.float32)[None, :]
    cos = (jnp.cos(ang) * trig)[None, :, None]
    sin = (jnp.sin(ang) * trig)[None, :, None]
    if cfg.get("rope_interleave", True):
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def share(cfg):
    """``(first, held)``: the routed experts of each layer on this chip."""
    held = cfg.get("held_n_routed_experts") or cfg["n_routed_experts"]
    return int(cfg.get("first_held_expert", 0)), int(held)


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def _attention(p, n, cfg, x):
    b, t, _ = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    if cfg.get("q_lora_rank"):
        cq = _rms(x @ _f32(p[n + "q_a_weight"]).T,
                  p[n + "q_a_norm_gamma"], eps)
        q = cq @ _f32(p[n + "q_b_weight"]).T
    else:
        q = x @ _f32(p[n + "q_weight"]).T
    q = q.reshape(b, t, heads, nope + rope)
    kva = x @ _f32(p[n + "kv_a_weight"]).T
    c = _rms(kva[..., :rank], p[n + "kv_a_norm_gamma"], eps)
    kv = (c @ _f32(p[n + "latt_kv_b_weight"]).T).reshape(
        b, t, heads, nope + vd)
    k_rope = _rotate(kva[..., None, rank:], cfg)        # one a position
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cfg)], -1) \
        * query_temperature(cfg, jnp.arange(t))[None, :, None, None]
    sigma = softmax_scale(cfg)
    pad = -t % QUERY_BLOCK
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    at = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)

    def rows(args):
        q_blk, i = args                     # (B, Q, H, D), (Q,)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * sigma
        s = jnp.where(jnp.arange(t)[None, :] <= i[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, (jnp.moveaxis(
        qp.reshape(b, -1, QUERY_BLOCK, heads, nope + rope), 1, 0), at))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, heads * vd)[:, :t]
    return o @ _f32(p[n + "attout_weight"]).T


def _gated(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def _experts(p, n, cfg, x):
    first, held = share(cfg)
    s = jax.nn.softmax(x @ _f32(p[n + "moe_gate_weight"]), -1)
    w, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])
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

    # a scan, not a Python loop: unrolled, the compiler widens every
    # expert's matrices to float32 at once (10 GB at the cell's size)
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if cfg.get("n_shared_experts"):
        y = y + _gated(x, p[n + "moe_shared_gate_weight"],
                       p[n + "moe_shared_up_weight"],
                       p[n + "moe_shared_down_weight"])
    return y


def _block(p, n, cfg, h):
    eps = cfg["rms_norm_eps"]
    h = h + _attention(p, n, cfg, _rms(h, p[n + "att_norm_gamma"], eps))
    return h + _experts(p, n, cfg, _rms(h, p[n + "ffn_norm_gamma"], eps))


def _head(p, h):
    """Over blocks of rows and of the vocabulary, a block at a time (a scan:
    whole, the logits are 6.4 GB and the matrix in float32 2.1 GB beside the
    weights): a caller that reads the last few rows of twelve thousand
    computes only their blocks."""
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
        h = _block(params, "layer%d_" % l, cfg, h)
    return h


def forward(params, cfg, tokens, layers=None, since=0):
    """Logits ``(B, T - since, vocab)`` of integer ``tokens (B, T)``, float32,
    at positions ``since ..``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, cfg, tokens, layers)[:, since:]
        return _head(params, _rms(h, params["final_norm_gamma"],
                                  cfg["rms_norm_eps"]))
