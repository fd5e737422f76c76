"""The decoder of ``model_type`` ``minicpm_sala`` (MiniCPM-SALA 9B), as its
``config.json`` and MiniCPM4's ``sparse_config`` size it: a quarter of the
layers (``mixer_types`` ``minicpm4``) attend blocks chosen from compressed
keys (InfLLM-v2), the rest (``lightning-attn``) are lightning linear
attention with a matrix state a head; a gated SiLU MLP in every layer;
MiniCPM's muP scalars.

``RMS(x; g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``.  Per token, hidden d,
``c = scale_depth / sqrt(num_hidden_layers)`` (the published depth, whatever
the cut):

    h = E[token] * scale_emb
    layer l:  h = h + c * Mixer_l(RMS(h));   h = h + c * MLP(RMS(h))
    logits = Wh (RMS(h) / (hidden_size / dim_model_base))       (untied head)

``lightning-attn`` (H = ``lightning_nh`` heads of D = ``lightning_head_dim``):

    q, k, v, g = Wq u, Wk u, Wv u, Wg u
    q, k = rope(RMS_D(q; gq)), rope(RMS_D(k; gk))     per head; rotary over the
                                                      whole head, half-split
    S_t,h = exp(-s_h f_l) S_t-1,h + k_t,h^T v_t,h     s_h = 2^(-8 (h + 1) / H)
    o_t,h = q_t,h S_t,h / sqrt(D)                     f_l = 1 - l / (L - 1) + 1e-5
    out = Wo (RMS(o_t; go) * sigmoid(g_t))            RMS over all H * D

The recurrence is a ``lax.scan`` over single tokens from zero state.

``minicpm4`` (H query heads, H_kv KV heads of D; no positions at all,
``attn_use_rope`` false): for the query at position t, n = t + 1.  With n <=
``dense_len`` it attends every position <= t.  Else the compressed keys of
the complete windows, ``kbar_j = mean(k[stride j : stride j + kernel])`` with
``stride j + kernel <= n``; ``p_h,j = softmax_j(q_h . kbar_j / sqrt(D))``;
summed over the query heads of a KV group, ``r_g,j``; block b (``block_size``
positions) scores ``max r_g,j`` over the windows that overlap it; the first
``init_blocks`` blocks and the ``window_size / block_size`` blocks that end at
the query's own are always taken, and the highest scores fill the list up to
``topk`` blocks a KV group; softmax over the positions <= t of the chosen
blocks; ``out = Wo (o_t * sigmoid(Wg u_t))``.

Departures from the published form, each because the configuration's file
``assumed`` it (the catalog does not settle them): ``qk_norm`` on the
lightning layers only; the output norm over all heads' outputs; the decay
rates and their scaling by depth; the block scoring by maximum; the always
taken blocks counted inside ``topk``.  Computed in blocks of queries (the
sparse layer's scores, the head's rows) so that twelve thousand positions
fit the chip: no cache, no paging, no chunked recurrence.

``params`` come in the type the cell serves in (bfloat16) and stay so: each
matrix is widened to float32 where it is used, never the tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_BLOCK = 16384
QUERY_BLOCK = 256
HEAD_ROWS = 1024


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


def layers_of(cfg):
    """The published indices of the layers run."""
    first = int(cfg.get("serve_first_layer", 0))
    return range(first, first + int(cfg.get("serve_num_hidden_layers",
                                            cfg["num_hidden_layers"])))


def decay_rates(cfg, l):
    """(H,) float32: head h's state is multiplied by exp(-rate_h) a token."""
    h, total = cfg["lightning_nh"], cfg["num_hidden_layers"]
    s = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    return s * (1.0 - l / max(total - 1, 1) + 1e-5)


def _lightning(p, n, cfg, x, l):
    b, t, _ = x.shape
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps = cfg["rms_norm_eps"]
    q = (x @ _f32(p[n + "lin_q_weight"]).T).reshape(b, t, h, d)
    k = (x @ _f32(p[n + "lin_k_weight"]).T).reshape(b, t, h, d)
    v = (x @ _f32(p[n + "lin_v_weight"]).T).reshape(b, t, h, d)
    if cfg.get("qk_norm", True):
        q = _rms(q, p[n + "lin_q_norm_gamma"], eps)
        k = _rms(k, p[n + "lin_k_norm_gamma"], eps)
    if cfg.get("lightning_use_rope", True):
        q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    lam = jnp.exp(-decay_rates(cfg, l))[None, :, None, None]

    def token(s, inp):
        q_t, k_t, v_t = inp                         # (B, H, D) of one token
        s = lam * s + k_t[..., :, None] * v_t[..., None, :]
        return s, jnp.einsum("bhd,bhde->bhe", q_t, s)

    _, o = jax.lax.scan(token, jnp.zeros((b, h, d, d), jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h * d) / jnp.sqrt(float(d))
    if cfg.get("use_output_norm", True):
        o = _rms(o, p[n + "lin_out_norm_gamma"], eps)
    if cfg.get("use_output_gate", True):
        o = o * jax.nn.sigmoid(x @ _f32(p[n + "lin_gate_weight"]).T)
    return o @ _f32(p[n + "lin_out_weight"]).T


def compressed_keys(k, cfg):
    """(B, W, H_kv, D): the mean of every complete window of ``k`` (B, T,
    H_kv, D), window j over positions ``stride j .. stride j + kernel - 1``."""
    sc = cfg["sparse_config"]
    kernel, stride = sc["kernel_size"], sc["kernel_stride"]
    nw = max(0, (k.shape[1] - kernel) // stride + 1)
    if not nw:
        return jnp.zeros((k.shape[0], 0) + k.shape[2:], jnp.float32)
    window = lambda s: jnp.mean(
        jax.lax.dynamic_slice_in_dim(k, s, kernel, axis=1), axis=1)
    return jnp.moveaxis(jax.vmap(window)(jnp.arange(nw) * stride), 0, 1)


def chosen_blocks(q, kbar, t, cfg, positions):
    """(B, H_kv, Q, blocks) bool: the blocks the queries ``q`` (B, Q, H, D)
    at ``positions`` (Q,) attend, given the compressed keys ``kbar`` of the
    whole sequence of ``t`` positions (:func:`compressed_keys`)."""
    sc = cfg["sparse_config"]
    b, nw, kvh, d = kbar.shape
    heads = q.shape[2]
    block, kernel, stride = sc["block_size"], sc["kernel_size"], \
        sc["kernel_stride"]
    nb = -(-t // block)
    n = jnp.asarray(positions, jnp.int32) + 1                    # (Q,)
    blk = jnp.arange(nb)
    own = (n - 1) // block
    visible = blk[None, :] <= own[:, None]                       # (Q, nb)
    if nw:
        starts = jnp.arange(nw) * stride
        complete = starts[None, :] + kernel <= n[:, None]        # (Q, nw)
        s = jnp.einsum("bqhgd,bwhd->bhgqw",
                       q.reshape(b, -1, kvh, heads // kvh, d), kbar) \
            / jnp.sqrt(float(d))
        s = jnp.where(complete, s, -jnp.inf)
        pr = jnp.where(complete, jax.nn.softmax(s, axis=-1), 0.0)
        r = jnp.sum(pr, axis=2)                                  # (B, kvh, Q, nw)
        overlap = (starts[:, None] < (blk[None, :] + 1) * block) \
            & (starts[:, None] + kernel > blk[None, :] * block)  # (nw, nb)
        score = jnp.max(jnp.where(overlap, r[..., None], 0.0), axis=-2)
    else:
        score = jnp.zeros((b, kvh, n.shape[0], nb), jnp.float32)
    forced = (blk[None, :] < sc["init_blocks"]) \
        | (blk[None, :] > own[:, None] - sc["window_size"] // block)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(visible, score, -jnp.inf)
    width = min(sc["topk"], nb)
    top, idx = jax.lax.top_k(score, width)
    picked = jnp.any((idx[..., None] == blk) & (top > -jnp.inf)[..., None],
                     axis=-2)
    dense = (n <= sc["dense_len"])[:, None]
    return jnp.where(dense, visible, picked)


def _sparse_attention(p, n, cfg, x):
    b, t, _ = x.shape
    heads, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    block = cfg["sparse_config"]["block_size"]
    q = (x @ _f32(p[n + "q_weight"]).T).reshape(b, t, heads, hd)
    k = (x @ _f32(p[n + "k_weight"]).T).reshape(b, t, kvh, hd)
    v = (x @ _f32(p[n + "v_weight"]).T).reshape(b, t, kvh, hd)
    if cfg.get("attn_use_rope", False):
        q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    g = heads // kvh
    pad = -t % QUERY_BLOCK
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos = jnp.arange(t + pad)
    kbar = compressed_keys(k, cfg)

    def rows(args):
        q_blk, pos_blk = args                      # (B, Q, H, D), (Q,)
        mask = chosen_blocks(q_blk, kbar, t, cfg, pos_blk)
        seen = jnp.repeat(mask, block, axis=-1)[..., :t] \
            & (jnp.arange(t)[None, :] <= pos_blk[:, None])
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q_blk.reshape(b, -1, kvh, g, hd), k) \
            / jnp.sqrt(float(hd))
        s = jnp.where(seen[:, :, None], s, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(rows, (
        jnp.moveaxis(qp.reshape(b, -1, QUERY_BLOCK, heads, hd), 1, 0),
        pos.reshape(-1, QUERY_BLOCK)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    if cfg.get("attn_use_output_gate", True):
        o = o * jax.nn.sigmoid(x @ _f32(p[n + "gate_weight"]).T)
    return o @ _f32(p[n + "attout_weight"]).T


def forward(params, cfg, tokens):
    """Logits ``(B, T, vocab)`` of integer ``tokens (B, T)``; float32."""
    p = params
    eps = cfg["rms_norm_eps"]
    c = cfg["scale_depth"] / cfg["num_hidden_layers"] ** 0.5
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(jnp.take(p["embed_weight"], tokens, axis=0)) \
            * cfg["scale_emb"]
        for l in layers_of(cfg):
            n = "layer%d_" % l
            u = _rms(h, p[n + "att_norm_gamma"], eps)
            if cfg["mixer_types"][l] == "lightning-attn":
                h = h + c * _lightning(p, n, cfg, u, l)
            else:
                h = h + c * _sparse_attention(p, n, cfg, u)
            u = _rms(h, p[n + "ffn_norm_gamma"], eps)
            gate = u @ _f32(p[n + "ffn_gate_weight"]).T
            up = u @ _f32(p[n + "ffn_up_weight"]).T
            h = h + c * ((jax.nn.silu(gate) * up)
                         @ _f32(p[n + "ffn_down_weight"]).T)
        h = _rms(h, p["final_norm_gamma"], eps) \
            / (cfg["hidden_size"] / cfg["dim_model_base"])
        head, v = p["head_weight"], p["head_weight"].shape[0]
        # the head over blocks of rows and of the vocabulary: a caller that
        # reads the last few rows of twelve thousand computes only their
        # blocks (whole, the logits are 3.6 GB beside the weights)
        return jnp.concatenate([
            jnp.concatenate([h[:, r:r + HEAD_ROWS]
                             @ _f32(head[i:i + VOCAB_BLOCK]).T
                             for i in range(0, v, VOCAB_BLOCK)], -1)
            for r in range(0, h.shape[1], HEAD_ROWS)], 1)
