"""Bytes one self-drafting tick of K-EXAONE must read from HBM, whatever
implements it, at one chip's share of the routed experts.

A tick runs the stack over two rows a slot (the last token and its draft),
then the multi-token-prediction block over the same two rows, and both heads.
Counted: every matrix of the layers run once (attention, the dense MLP, the
router, the shared expert), the held routed experts that the tick's ``2 x
slots`` rows touch (an expectation under uniform routing, as
``work_moe.experts_touched``; what a run routed is the program's own counter
and ``moe_experts_hbm_util_pct`` reads that), the block's matrices, the head
twice (the draft depends on the token sampled from the stack's logits: two
dependent products with the one matrix), the embedding rows, and the live
keys and values of both full-attention nodes and of the window rings.  Never
counted: gathered views, index traffic, rows of padding.
``work.decode_step_bytes`` finds ``decode_step_bytes`` through the
configuration's ``counts``.
"""
from __future__ import annotations

from . import work_moe

WEIGHT_BYTES = work_moe.WEIGHT_BYTES
SCALE_BYTES = work_moe.SCALE_BYTES
ROWS_A_SLOT = 2         # the last token and its draft


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def held_experts(cfg):
    return int(cfg.get("held_num_experts") or cfg["num_experts"])


def attention_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kvh * hd + 2 * hd   # q, o, k, v, gains


def expert_params(cfg):
    """One gated expert's three matrices (a routed one, or the shared)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_touched(cfg, rows):
    """Expected number of this chip's held experts that at least one of
    ``rows`` rows chose, under uniform routing."""
    k, e = cfg["num_experts_per_tok"], cfg["num_experts"]
    return held_experts(cfg) * (1.0 - (1.0 - float(k) / e) ** rows)


def expert_layer_params(cfg, rows):
    """What one expert layer reads besides its attention: the router and its
    bias, the shared expert, the held experts touched."""
    e = cfg["num_experts"]
    return cfg["hidden_size"] * e + e \
        + int(cfg.get("num_shared_experts") or 0) * expert_params(cfg) \
        + experts_touched(cfg, rows) * expert_params(cfg)


def kv_bytes_per_token(cfg, kv_bytes):
    """Cached keys and values of one position of one attention node, with
    the quantised pool's scales."""
    kvh = cfg["num_key_value_heads"]
    per = 2 * kvh * cfg["head_dim"] * kv_bytes
    if kv_bytes < 2:
        per += 2 * kvh * SCALE_BYTES
    return per


def decode_step_bytes(cfg, traffic, live_tokens):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    slots = int(traffic["slots"])
    rows = ROWS_A_SLOT * slots
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    per_slot = float(live_tokens) / slots
    params = 2 * d * v + 2 * rows * d       # the head twice, embedding rows
    cached = 0.0
    for l in range(layers_run(cfg)):
        params += attention_params(cfg) + 2 * d
        if cfg["mlp_layer_types"][l] == "sparse":
            params += expert_layer_params(cfg, rows)
        else:
            params += 3 * d * cfg["intermediate_size"]
        window = cfg["sliding_window"] \
            if cfg["layer_types"][l] == "sliding_attention" else 0
        cached += min(per_slot, window) if window else per_slot
    for _ in range(int(cfg.get("num_nextn_predict_layers") or 0)):
        # the projection of [embedding ; hidden], one full-attention block
        # of the sparse kind, the three norms around them
        params += 2 * d * d + attention_params(cfg) + 5 * d \
            + expert_layer_params(cfg, rows)
        cached += per_slot
    return params * WEIGHT_BYTES + d * WEIGHT_BYTES \
        + slots * cached * kv_bytes_per_token(cfg, kv)
