"""Bytes and operations that serving a decoder of Gated DeltaNet layers beside
plain multi-head attention (``olmo_hybrid``: a delta rule over a Dk x Dv
matrix state a head with ONE decay a head in three layers of four, softmax
attention without positions in the fourth, a dense gated MLP and OLMo's norm
after each sublayer) must move and do, whatever implements it.

Counts of what the mathematics must move, never of what a program happens
to: a state passed over twice, float32 copies of a chunk's blocks, masked
halves of a block's products, a scale row's padding, gathered cache views and
a chunk's padding are not work.  ``work.decode_step_bytes`` finds
``decode_step_bytes`` through the configuration's ``counts``.

``state_step_bytes``: what one decode step moves of one slot's row of one
delta layer: the (H, Dk, Dv) float32 state and the convolution's tail, each
read once and written once.  ``chunk_work``: the convolution and the
recurrence of one delta layer over a chunk of real tokens from a carried
state, by the chunked delta rule at a block of :data:`BLOCK` tokens in its
matrix form (the equations of ``chipbench/reference/olmo_hybrid.py``
regrouped by blocks, as ISSUE 57 writes them): the two roofline readers
divide these by the device time under ``mx.gdn/step`` and ``mx.gdn/chunk``
(its solve included).
"""
from __future__ import annotations

from . import work_ssm

WEIGHT_BYTES = work_ssm.WEIGHT_BYTES
SCALE_BYTES = work_ssm.SCALE_BYTES
STATE_BYTES = 4           # the matrix state is float32
BLOCK = 64                # tokens of a block of the chunked delta rule, the
                          # program's own (ops.gdn.BLOCK; the test pins both)

layers_run = work_ssm.layers_run


def delta_layers(cfg, layers=None):
    """How many of the first ``layers`` layers (those run, by default) are
    Gated DeltaNet: the ones ``layer_types`` calls ``linear_attention``."""
    n = layers_run(cfg) if layers is None else int(layers)
    return sum(kind == "linear_attention" for kind in cfg["layer_types"][:n])


def delta_dims(cfg):
    """``(H, Dk, Dv, K)``: heads, key and value dims a head, the
    convolution's kernel."""
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"])


def conv_dim(cfg):
    h, dk, dv, _ = delta_dims(cfg)
    return 2 * h * dk + h * dv


def head_dim(cfg):
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def attention_mixer_params(cfg):
    """W_q, W_k, W_v, W_o and the gains of the q and k norms over the whole
    projection."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hd * (2 * h + 2 * kvh) + hd * (h + kvh)


def delta_mixer_params(cfg):
    """W_q, W_k (d -> H Dk), W_v, W_g (d -> H Dv), W_o; W_a and W_b (d -> H);
    the convolution; A_log, dt_bias and the output norm's gain."""
    d = cfg["hidden_size"]
    h, dk, dv, k = delta_dims(cfg)
    return d * (2 * h * dk + 3 * h * dv + 2 * h) + conv_dim(cfg) * k \
        + 2 * h + dv


def layer_params(cfg, delta):
    """One layer: its mixer, the gated MLP's three matrices, two norms."""
    d = cfg["hidden_size"]
    mixer = delta_mixer_params(cfg) if delta else attention_mixer_params(cfg)
    return mixer + 3 * d * cfg["intermediate_size"] + 2 * d


def model_params(cfg, layers=None):
    """Parameters of ``layers`` layers (the published count by default), the
    embedding, the untied head and the last norm."""
    n = cfg["num_hidden_layers"] if layers is None else int(layers)
    gdn = delta_layers(cfg, n)
    return gdn * layer_params(cfg, True) \
        + (n - gdn) * layer_params(cfg, False) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def state_row_bytes(cfg):
    """``(state, conv tail)`` bytes one slot holds of one delta layer."""
    h, dk, dv, k = delta_dims(cfg)
    return h * dk * dv * STATE_BYTES, (k - 1) * conv_dim(cfg) * WEIGHT_BYTES


def state_step_bytes(cfg):
    """What one decode step moves of one slot's row of one delta layer: the
    state and the conv tail, each read once and written once."""
    return 2 * sum(state_row_bytes(cfg))


def kv_bytes_per_token(cfg, kv_bytes):
    """An attention layer's cached keys and values a position, with the
    quantised pool's scales (a float a (token, KV head) for each of K and V;
    the pool pads them to 64 floats a token, which is no work)."""
    kvh = cfg["num_key_value_heads"]
    per = 2 * kvh * head_dim(cfg) * kv_bytes
    if kv_bytes < 2:
        per += 2 * kvh * SCALE_BYTES
    return per


def decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must move through HBM: every matrix of the
    layers run and the head once (the embedding is gathered by row), the
    attention layers' keys and values of the live tokens at the pool's
    bytes, and the state row of every slot in every delta layer read and
    written (``slots`` is the traffic file's: a backlog keeps them full but
    for one)."""
    n, slots = layers_run(cfg), int(traffic["slots"])
    gdn = delta_layers(cfg)
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    weights = gdn * layer_params(cfg, True) \
        + (n - gdn) * layer_params(cfg, False) \
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"] \
        + slots * cfg["hidden_size"]
    return weights * WEIGHT_BYTES \
        + (n - gdn) * float(live_tokens) * kv_bytes_per_token(cfg, kv) \
        + gdn * slots * state_step_bytes(cfg)


def chunk_work(cfg, tokens):
    """``(FLOPs, bytes)`` of the convolution and the recurrence of ONE delta
    layer over a chunk of ``tokens`` real tokens from a carried state, by
    blocks of :data:`BLOCK`, in the matrix form.  Inside a block, a head's
    token i (from 0) needs, over the i tokens j < i, ``k_i . k_j`` (2 Dk: a
    row of ``K K^T``; the decay is one multiply a pair and not counted) and
    row i of the forward substitution (``N_i -= A_ij N_j``, 2 Dv), and over
    the i + 1 tokens j <= i, ``q_i . k_j`` (2 Dk) and its weighted sum of
    ``N_j`` (2 Dv).  Every token reads the block's start state twice over Dk
    x Dv (its right-hand side ``k S_0``, its output ``q S_0``) and adds to
    the end state (``k (x) N``).  The decays' exponentials are not counted.
    Bytes: the state and the conv tail read and written once; the q, k, v
    and gate streams and the decay and beta a head read and the output
    written in the stream's type."""
    h, dk, dv, k = delta_dims(cfg)
    t = int(tokens)
    blocks = [BLOCK] * (t // BLOCK) + ([t % BLOCK] if t % BLOCK else [])
    below = sum(b * (b - 1) // 2 for b in blocks)
    upto = below + t
    flops = 2 * k * conv_dim(cfg) * t \
        + h * (2 * (below + upto) * (dk + dv) + 3 * 2 * t * dk * dv)
    moved = state_step_bytes(cfg) \
        + t * (2 * h * dk + 3 * h * dv + 2 * h) * WEIGHT_BYTES
    return flops, moved
