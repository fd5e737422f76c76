"""The decoder of ``model_type`` ``nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-A3B),
as its ``config.json`` sizes it, at one chip's share of each expert layer.
What the ``config.json`` does not spell out is listed under ``assumed`` in the
configuration's file, each with its reason.

Residual stream of width ``hidden_size`` (d); ``RMS(x; g) = x * rsqrt(mean(x^2)
+ layer_norm_epsilon) * g``; no matrix has a bias; no positions anywhere: the
mixers' convolutions and decays carry order.

Block l:  x' = x + f_l(RMS_l(x)), ONE sublayer a layer, chosen by letter l of
    ``hybrid_override_pattern``; after the last layer RMS, then the untied
    head.  No multiplier, no norm after a sublayer.
``M``, the Mamba-2 mixer (H = ``mamba_num_heads`` heads of P =
    ``mamba_head_dim``, N = ``ssm_state_size``, G = ``n_groups``, K =
    ``conv_kernel``):
    [z | x | B | C | dt] = W_in u          (H P, H P, G N, G N, H wide)
    xBC = silu(conv1d_causal_depthwise([x | B | C]; K taps, bias))
    dt_k = softplus(dt_k + dt_bias_k)  (no clamp);  A_k = -exp(A_log_k)
    S_t,k = exp(dt_t,k A_k) S_t-1,k + dt_t,k x_t,k (x) B_t,g     g = k // (H/G)
    y_t,k = S_t,k C_t,g + D_k x_t,k
    out = W_out GroupRMS(y * silu(z); G groups of H P / G, one gain of H P)
    The recurrence is a ``lax.scan`` over single tokens from zero state: no
    chunked algorithm, no carried state, no convolution tail.
``E``, the experts:  s = sigmoid(u W_r) over all ``n_routed_experts`` in
    float32; chosen = the ``num_experts_per_tok`` largest of s + b (a
    selection-only bias a layer; ``n_group`` 1: no group limit); w =
    ``routed_scaling_factor`` s[chosen] / (sum s[chosen] + 1e-20)
    (``norm_topk_prob``);  y = sum over chosen e in [first, first + held) of
    w_e relu(u W_u,e)^2 W_d,e  +  relu(u S_u)^2 S_d, the shared expert
    unweighted, ``moe_shared_expert_intermediate_size`` wide.  Two matrices
    an expert, no gate (``mlp_hidden_act`` relu2); both stored a hidden unit
    a row, (h, d).  The experts outside the share live on other chips: their
    part is left out here as in the program (a departure from the published
    whole, stated in the configuration's ``deployment``); the shared expert
    is on every chip.
``*``, attention: ``num_attention_heads`` query heads and
    ``num_key_value_heads`` KV heads of ``head_dim``, causal softmax at scale
    head_dim^(-1/2), head h reads KV head h // (H / H_kv); no rotation, no
    q/k norm, no gate, no window.
``-``, a dense MLP (no layer of the published pattern): relu(u W_u)^2 W_d at
    ``intermediate_size``.

Departures from the published form, each because the configuration's file
``assumed`` it (the catalog does not settle them): the order of the segments
of ``W_in`` and of ``xBC``; the gated norm grouped by ``n_groups``, the gate
before the norm; no clamp on dt; no rotation though ``rope_theta`` stands in
the file.

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


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def _fc(x, p, name):
    return x @ _f32(p[name + "_weight"]).T


def share(cfg):
    """``(first, held)``: the routed experts of each layer on this chip."""
    held = cfg.get("held_n_routed_experts") or cfg["n_routed_experts"]
    return int(cfg.get("first_held_expert", 0)), int(held)


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def _mixer(p, n, cfg, u):
    b, t, _ = u.shape
    h, pd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    ns, g, k = cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]
    d_ssm, bc = h * pd, g * ns
    proj = _fc(u, p, n + "ssm_in")
    z, xbc, dt = (proj[..., :d_ssm], proj[..., d_ssm:2 * d_ssm + 2 * bc],
                  proj[..., 2 * d_ssm + 2 * bc:])
    # causal depthwise convolution: tap i reads the row K - 1 - i back
    w = _f32(p[n + "ssm_conv_weight"])                      # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + t] * w[:, i] for i in range(k))
    xbc = jax.nn.silu(xbc + _f32(p[n + "ssm_conv_bias"]))
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
                          + cfg["layer_norm_epsilon"])
    y = y.reshape(b, t, d_ssm) * _f32(p[n + "ssm_norm_gamma"])
    return _fc(y, p, n + "ssm_out")


def _attention(p, n, cfg, x):
    b, t, _ = x.shape
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kvh = cfg["num_key_value_heads"]
    q = _fc(x, p, n + "q").reshape(b, t, heads, hd)
    k = _fc(x, p, n + "k").reshape(b, t, kvh, hd)
    v = _fc(x, p, n + "v").reshape(b, t, kvh, hd)
    pad = -t % QUERY_BLOCK
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    at = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)

    def rows(args):
        q_blk, i = args                     # (B, Q, H, D), (Q,)
        # query head h reads KV head h // (heads / kvh)
        qg = q_blk.reshape(b, -1, kvh, heads // kvh, hd)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) * hd ** -0.5
        s = jnp.where(jnp.arange(t)[None, :] <= i[:, None], s, -jnp.inf)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1), v)
        return o.reshape(b, -1, heads * hd)

    o = jax.lax.map(rows, (jnp.moveaxis(
        qp.reshape(b, -1, QUERY_BLOCK, heads, hd), 1, 0), at))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    return _fc(o, p, n + "attout")


def _relu2(x, up, down):
    """``relu(x W_u)^2 W_d``, both matrices a hidden unit a row, (h, d)."""
    return jnp.square(jax.nn.relu(x @ _f32(up).T)) @ _f32(down)


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
        up, down = (jax.lax.dynamic_index_in_dim(
            p[n + "moe_expert_%s_weight" % part], e, keepdims=False)
            for part in ("up", "down"))
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1,
                     keepdims=True)
        return y + we * _relu2(x, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if cfg.get("n_shared_experts"):
        y = y + _relu2(x, p[n + "moe_shared_up_weight"],
                       p[n + "moe_shared_down_weight"])
    return y


def _dense(p, n, cfg, x):
    return jnp.square(jax.nn.relu(_fc(x, p, n + "ffn_up"))) \
        @ _f32(p[n + "ffn_down_weight"]).T


SUBLAYERS = {"M": _mixer, "E": _experts, "*": _attention, "-": _dense}


def _block(p, l, cfg, h):
    n = "layer%d_" % l
    f = SUBLAYERS[cfg["hybrid_override_pattern"][l]]
    return h + f(p, n, cfg, _rms(h, p[n + "norm_gamma"],
                                 cfg["layer_norm_epsilon"]))


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
                                  cfg["layer_norm_epsilon"]))


def loss(params, cfg, tokens, labels, layers=None):
    """Mean next-token cross-entropy over every position."""
    logp = jax.nn.log_softmax(forward(params, cfg, tokens, layers), axis=-1)
    labels = jnp.asarray(labels, jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
