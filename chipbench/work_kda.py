"""Bytes and operations that serving a decoder of Kimi delta attention layers
beside gated attention (``solar_open2``: a delta rule over a matrix state a
head with a decay a channel in three layers of four, softmax attention
without positions in the fourth, every layer a routed expert layer beside a
shared expert) must move and do, whatever implements it, at one chip's share
of the routed experts.

Counts of what the mathematics must move, never of what a program happens
to: a state passed over twice, float32 copies of a chunk's blocks, masked
halves of a block's products, gathered cache views and a chunk's padding are
not work.  ``work.decode_step_bytes`` finds ``decode_step_bytes`` through the
configuration's ``counts``.

``state_step_bytes``: what one decode step moves of one slot's row of one
delta layer: the (H, D, D) float32 state and the convolution's tail, each
read once and written once.  ``chunk_work``: the convolution and the
recurrence of one delta layer over a chunk of real tokens from a carried
state, by the chunked delta rule at a block of :data:`BLOCK` tokens (the
equations of ``chipbench/reference/solar_open2.py`` regrouped by blocks, as
ISSUE 53 writes them): the two roofline readers divide these by the device
time under ``mx.kda/step`` and ``mx.kda/chunk`` (its solve included).
"""
from __future__ import annotations

from . import work_moe, work_ssm

WEIGHT_BYTES = work_moe.WEIGHT_BYTES
STATE_BYTES = 4           # the matrix state is float32
BLOCK = 64                # tokens of a block of the chunked delta rule, the
                          # program's own (ops.kda.BLOCK; the test pins both)

layers_run = work_moe.layers_run
experts_touched = work_moe.experts_touched
# an attention layer's cached keys and values a position, scales included
kv_bytes_per_token = work_ssm.kv_bytes_per_token


def expert_params(cfg):
    """One gated expert's three matrices (a routed one, or the shared)."""
    return work_moe.expert_bytes(cfg) // WEIGHT_BYTES


def delta_layers(cfg, layers=None):
    """How many of the first ``layers`` layers (those run, by default) are
    Kimi delta attention: the ones ``gqa_layers`` does not list."""
    n = layers_run(cfg) if layers is None else int(layers)
    return n - sum(l < n for l in cfg["gqa_layers"])


def delta_dims(cfg):
    """``(H, D, K)``: heads, dims a head (keys and values alike), the
    convolution's kernel."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def gqa_mixer_params(cfg):
    """W_q, W_k, W_v, the elementwise gate's W_g (``use_gqa_gate``), W_o."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hd * (2 * h + 2 * kvh + h * bool(cfg.get("use_gqa_gate")))


def delta_mixer_params(cfg):
    """W_q, W_k, W_v, W_o; the decay's and the gate's low-rank pairs (rank
    D); W_beta; the convolution; A_log, dt_bias and the output norm's gain."""
    d = cfg["hidden_size"]
    h, hd, k = delta_dims(cfg)
    return 4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h \
        + 3 * h * hd * k + h + h * hd + hd


def layer_params(cfg, delta, experts):
    """One layer with ``experts`` routed experts: its mixer, the router with
    its selection bias, the shared expert, the routed ones, two norms."""
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    mixer = delta_mixer_params(cfg) if delta else gqa_mixer_params(cfg)
    return mixer + d * e + e + 2 * d \
        + (int(cfg.get("n_shared_experts") or 0) + experts) \
        * expert_params(cfg)


def model_params(cfg, layers=None, experts=None):
    """Parameters of ``layers`` layers (the published count by default) with
    ``experts`` routed experts a layer (all of them by default), the
    embedding, the untied head and the last norm."""
    n = cfg["num_hidden_layers"] if layers is None else int(layers)
    e = cfg["n_routed_experts"] if experts is None else experts
    kda = delta_layers(cfg, n)
    return kda * layer_params(cfg, True, e) \
        + (n - kda) * layer_params(cfg, False, e) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def state_row_bytes(cfg):
    """``(state, conv tail)`` bytes one slot holds of one delta layer."""
    h, d, k = delta_dims(cfg)
    return h * d * d * STATE_BYTES, (k - 1) * 3 * h * d * WEIGHT_BYTES


def state_step_bytes(cfg):
    """What one decode step moves of one slot's row of one delta layer: the
    state and the conv tail, each read once and written once."""
    return 2 * sum(state_row_bytes(cfg))


def decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must move through HBM: every matrix outside
    the routed experts of the layers run and the head once (the embedding is
    gathered by row), the held experts that ``slots`` rows touch (an
    expectation under uniform routing, ``work_moe.experts_touched``; what a
    run routed is the program's own counter), the attention layers' keys and
    values of the live tokens at the pool's bytes, and the state row of
    every slot in every delta layer read and written (``slots`` is the
    traffic file's: a backlog keeps them full but for one)."""
    n, slots = layers_run(cfg), int(traffic["slots"])
    kda = delta_layers(cfg)
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    touched = experts_touched(cfg, slots)
    weights = kda * layer_params(cfg, True, touched) \
        + (n - kda) * layer_params(cfg, False, touched) \
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"] \
        + slots * cfg["hidden_size"]
    return weights * WEIGHT_BYTES \
        + (n - kda) * float(live_tokens) * kv_bytes_per_token(cfg, kv) \
        + kda * slots * state_step_bytes(cfg)


def chunk_work(cfg, tokens):
    """``(FLOPs, bytes)`` of the convolution and the recurrence of ONE delta
    layer over a chunk of ``tokens`` real tokens from a carried state, by
    blocks of :data:`BLOCK`.  Inside a block, a head's token i (from 0)
    needs, over the i tokens j < i, ``k_i . k_j`` under the decays (A) and
    row i of the forward substitution (N_i -= A_ij N_j), and over the i + 1
    tokens j <= i, ``q_i . k_j`` under the decays and its weighted sum of
    N_j: 2 D each a pair.  Every token reads the block's start state three
    times over D x D (its right-hand side ``k S_0``, its output ``q S_0``)
    or adds to the end state (``k (x) N``).  The decays' exponentials are
    not counted.  Bytes: the state and the conv tail read and written once;
    the q, k, v, decay and gate streams and beta read and the output written
    in the stream's type."""
    h, d, k = delta_dims(cfg)
    t = int(tokens)
    blocks = [BLOCK] * (t // BLOCK) + ([t % BLOCK] if t % BLOCK else [])
    below = sum(b * (b - 1) // 2 for b in blocks)
    upto = below + t
    flops = 2 * k * 3 * h * d * t \
        + h * (2 * below * 2 * d + 2 * upto * 2 * d + 3 * 2 * t * d * d)
    moved = state_step_bytes(cfg) + t * (6 * h * d + h) * WEIGHT_BYTES
    return flops, moved
