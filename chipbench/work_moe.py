"""Bytes a decode tick of a decoder with routed experts and mixed window and
full attention must read, at one chip's share of the experts.

Counts of what must be read, never of what a program happens to read: the
gathered cache views, the dispatch's index traffic and padded rows are not
work.  ``work.decode_step_bytes`` finds ``moe_lm_decode_step_bytes`` through
the configuration's ``counts``.
"""
from __future__ import annotations

WEIGHT_BYTES = 2          # bfloat16
SCALE_BYTES = 4           # one float32 scale a (token, kv head), int8 pool


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def held_experts(cfg):
    return int(cfg.get("held_n_routed_experts") or cfg["n_routed_experts"])


def expert_bytes(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * WEIGHT_BYTES


def experts_touched(cfg, tokens):
    """Expected number of this chip's held experts that at least one of
    ``tokens`` tokens chose, under uniform routing: ``held x (1 - (1 -
    k/E)^tokens)`` (13.9 of 16 at 64 tokens for top 8 of 256).  The
    configuration's ``init`` keeps the routing near it (a small correction
    bias: 13.6 by simulation); ``moe_experts_hbm_util_pct`` does not lean on
    it and takes what a run routed from the program's counter."""
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    return held_experts(cfg) * (1.0 - (1.0 - float(k) / e) ** tokens)


def moe_expert_bytes_per_tick(cfg, traffic):
    """Expert weights one decode tick of ``slots`` tokens must read: the
    touched experts of every MoE layer run, by ``experts_touched``."""
    moe_layers = sum(cfg["moe_layer_freq"][:layers_run(cfg)])
    return moe_layers * experts_touched(cfg, int(traffic["slots"])) \
        * expert_bytes(cfg)


def routed(facts, key):
    """What each decode tick of a traced window routed: ``key``
    (``moe_rows_held``, ``moe_rows_elsewhere`` or ``moe_expert_visits``,
    summed over the MoE layers) from the arguments of the program's
    ``serve.readback`` spans inside the window, one a tick; empty where the
    program notes none.  The counters ``mx_moe_*_total`` hold the same for
    the whole process, the ticks that fill the slots included."""
    from . import spans

    al = spans.aligned(facts, "serve")
    return [a[key] for name, _, _, a in (al["spans"] if al else ())
            if name == "serve.readback" and key in a]


def attention_weight_params(cfg, window):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh = cfg["swa_num_key_value_heads" if window
              else "num_key_value_heads"]
    return d * h * cfg["head_dim"] + d * kvh * cfg["head_dim"] \
        + d * kvh * cfg["v_head_dim"] + h * cfg["v_head_dim"] * d


def kv_bytes_per_token(cfg, window, kv_bytes):
    """Cached keys and values of one position of one layer, with the
    quantised pool's scales."""
    kvh = cfg["swa_num_key_value_heads" if window
              else "num_key_value_heads"]
    per = kvh * (cfg["head_dim"] + cfg["v_head_dim"]) * kv_bytes
    if kv_bytes < 2:
        per += 2 * kvh * SCALE_BYTES
    return per


def moe_lm_decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must read from HBM: the non-expert matrices of
    the layers run and the head once (the embedding is gathered by row); for
    each MoE layer the router and the expected number of held experts that
    ``slots`` tokens touch (``experts_touched``: uniform routing); the keys
    and values of the live tokens on full layers and of ``min(live,
    sliding_window)`` a slot on window layers, at the pool's bytes."""
    n = layers_run(cfg)
    d, slots = cfg["hidden_size"], int(traffic["slots"])
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    per_slot = float(live_tokens) / slots
    total = d * cfg["vocab_size"] * WEIGHT_BYTES
    for l in range(n):
        window = bool(cfg["hybrid_layer_pattern"][l])
        total += attention_weight_params(cfg, window) * WEIGHT_BYTES
        if cfg["moe_layer_freq"][l]:
            total += d * cfg["n_routed_experts"] * WEIGHT_BYTES
        else:
            total += 3 * d * cfg["intermediate_size"] * WEIGHT_BYTES
        seen = min(per_slot, cfg["sliding_window"]) if window else per_slot
        total += slots * seen * kv_bytes_per_token(cfg, window, kv)
    return total + moe_expert_bytes_per_tick(cfg, traffic)
