"""Bytes and operations of a decoder whose every block runs a Mamba-2 mixer
beside grouped-query attention (``model_type`` ``falcon_h1``): what a decode
tick must move, and what the chunk program's scan must do.

Counts of what the mathematics must move, never of what a program happens
to: a state read twice, float32 copies of a chunk's blocks, masked halves of
a block's products and a chunk's padding are not work.
``work.decode_step_bytes`` finds ``hybrid_lm_decode_step_bytes`` through the
configuration's ``counts``.
"""
from __future__ import annotations

import bisect
import re

WEIGHT_BYTES = 2          # bfloat16
SCALE_BYTES = 4           # one float32 scale a (token, kv head), int8 pool
STATE_BYTES = {"float32": 4, "bfloat16": 2}


def layers_run(cfg):
    return int(cfg.get("serve_num_hidden_layers", cfg["num_hidden_layers"]))


def conv_dim(cfg):
    return cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] \
        * cfg["mamba_d_state"]


def attention_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def mixer_params(cfg):
    """W_in, W_out, the convolution with its bias, dt_bias, A_log, D and
    the gated norm's gain."""
    d, d_ssm, h = cfg["hidden_size"], cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    return d * (d_ssm + conv_dim(cfg) + h) + d_ssm * d \
        + conv_dim(cfg) * (cfg["mamba_d_conv"]
                           + bool(cfg.get("mamba_conv_bias", True))) \
        + 3 * h + d_ssm


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def block_params(cfg):
    """One block: attention, mixer, MLP and its two norms."""
    return attention_params(cfg) + mixer_params(cfg) + mlp_params(cfg) \
        + 2 * cfg["hidden_size"]


def model_params(cfg):
    """The blocks run, the embedding, the untied head and the last norm."""
    return layers_run(cfg) * block_params(cfg) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def state_row_bytes(cfg):
    """``(state, conv tail)`` bytes one slot holds of one layer."""
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"] * STATE_BYTES[cfg["ssm_state_dtype"]]
    tail = (cfg["mamba_d_conv"] - 1) * conv_dim(cfg) * WEIGHT_BYTES
    return state, tail


def state_step_bytes(cfg):
    """What one decode step moves of one slot's row of one layer: the
    state and the conv tail, each read once and written once."""
    return 2 * sum(state_row_bytes(cfg))


def kv_bytes_per_token(cfg, kv_bytes):
    """Cached keys and values of one position of one layer, with the
    quantised pool's scales."""
    kvh = cfg["num_key_value_heads"]
    per = 2 * kvh * cfg["head_dim"] * kv_bytes
    if kv_bytes < 2:
        per += 2 * kvh * SCALE_BYTES
    return per


def hybrid_lm_decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must move through HBM: every matrix of the
    blocks run and the head once (the embedding is gathered by row), the
    keys and values of the live tokens at the pool's bytes, and the state
    row of every slot read and written.  ``slots`` is the traffic file's:
    this overstates the state's part by the share of slots that are empty
    or mid-prefill when the tick runs (``slot_occupancy_pct``; a backlog
    keeps them full but for one)."""
    n, slots = layers_run(cfg), int(traffic["slots"])
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    weights = (n * block_params(cfg)
               + cfg["vocab_size"] * cfg["hidden_size"]
               + cfg["hidden_size"]) * WEIGHT_BYTES
    return weights + n * float(live_tokens) * kv_bytes_per_token(cfg, kv) \
        + n * slots * state_step_bytes(cfg)


def chunk_scan_work(cfg, tokens):
    """``(FLOPs, bytes)`` of the convolution and the recurrence of one
    layer over a chunk of ``tokens`` real tokens from a carried state, by
    the chunked algorithm at ``mamba_chunk_size``.  Inside a block of Q
    tokens, token i (from 0) needs ``C_i . B_s`` (N a group) and the
    weighted sum of ``x_s`` (P a head) over the i + 1 tokens s <= i: the
    causal half of the block's products.  Every token adds ``dt x (x) B``
    to the block's end state and reads ``C . S`` of its start state (P x N
    a head each).  Bytes: the state and the conv tail read and written
    once, [x | B | C] and dt read and y written in the stream's type."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    q, t = int(cfg["mamba_chunk_size"]), int(tokens)
    pairs = sum(b * (b + 1) // 2
                for b in [q] * (t // q) + ([t % q] if t % q else []))
    flops = 2 * cfg["mamba_d_conv"] * conv_dim(cfg) * t \
        + 2 * pairs * (g * n + h * p) + 2 * 2 * t * h * p * n
    moved = state_step_bytes(cfg) \
        + t * (conv_dim(cfg) + h + h * p) * WEIGHT_BYTES
    return flops, moved


# -- what a traced window shows ---------------------------------------------

def noted(facts, span, key):
    """``key`` of the arguments of the program's ``span`` spans inside a
    traced window (``ssm_rows`` of ``serve.readback``, one a decode tick;
    ``tokens`` of ``serve.prefill``, one a chunk); empty where the program
    notes none."""
    from . import spans

    al = spans.aligned(facts, "serve")
    return [a[key] for name, _, _, a in (al["spans"] if al else ())
            if name == span and key in a]


def scope_seconds(facts, module, scopes):
    """``(seconds, runs)``: device time of the first chip's ``XLA Ops``
    events inside the window's runs of the program whose module name
    matches ``module``, under any of ``scopes`` (innermost event, as
    ``scopes.by_scope`` counts), and the number of those runs.  None where
    the trace has no device, the program no scope map, or no run."""
    from . import scopes as scopes_mod, trace

    parsed = facts.get("trace")
    if not parsed or not parsed.get("devices"):
        return None
    maps = facts.get("scope_maps") or scopes_mod.program_maps()[0]
    if not maps:
        return None
    lo, hi = trace.window_of(parsed)
    first = parsed["devices"][sorted(parsed["devices"])[0]]
    pattern = re.compile(module)
    runs = [(trace.module_stem(n), s, s + d)
            for n, s, d in first.get(trace.MODULES_LINE, ())
            if pattern.search(trace.module_stem(n)) and s >= lo
            and s + d <= hi]
    if not runs:
        return None
    starts = [s for _, s, _ in runs]
    events = []
    for name, s, d in first[trace.OPS_LINE]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][2]:
            events.append(((runs[i][0], name), s, s + d))
    ns = sum(t for (stem, name), t in
             scopes_mod.self_times(events, lo, hi).items()
             if maps.get(stem, {}).get(name) in scopes)
    return ns / 1e9, len(runs)
