"""Bytes and operations that serving a decoder of ``model_type`` ``nemotron_h``
must move and do, whatever implements it: a stack whose every layer is ONE
sublayer by its letter of ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer,
``E`` routed relu^2 experts of TWO matrices beside a shared one, ``*``
grouped-query attention without positions, ``-`` a dense relu^2 MLP), at one
chip's share of the experts.

Counts of what the mathematics must move, never of what a program happens
to: an expert is two matrices of ``moe_intermediate_size`` whatever padding
its stored layout carries; a mixer stands in the ``M`` layers run and nowhere
else; a state row is read once and written once; float32 copies of a chunk's
blocks, masked halves of a block's products and a chunk's padding are not
work.  ``work.decode_step_bytes`` finds ``decode_step_bytes`` through the
configuration's ``counts``.
"""
from __future__ import annotations

from . import work_ssm

WEIGHT_BYTES = work_ssm.WEIGHT_BYTES
SCALE_BYTES = work_ssm.SCALE_BYTES

layers_run = work_ssm.layers_run
# what a traced window shows: the program's own spans and scopes
noted = work_ssm.noted
scope_seconds = work_ssm.scope_seconds


def letters(cfg, layers=None):
    """The letters of the layers run (the first ``layers``, by default those
    the cell runs)."""
    n = layers_run(cfg) if layers is None else int(layers)
    return cfg["hybrid_override_pattern"][:n]


def held_experts(cfg):
    return int(cfg.get("held_n_routed_experts") or cfg["n_routed_experts"])


# -- parameters ---------------------------------------------------------------

def mixer_keys(cfg):
    """The mixer's sizes under the names ``work_ssm`` reads them by
    (Falcon-H1's): the formulas of a Mamba-2 mixer are one, the two
    ``config.json`` name its sizes apart."""
    heads, head = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return {"hidden_size": cfg["hidden_size"], "mamba_d_ssm": heads * head,
            "mamba_n_heads": heads, "mamba_d_head": head,
            "mamba_d_state": cfg["ssm_state_size"],
            "mamba_n_groups": cfg["n_groups"],
            "mamba_d_conv": cfg["conv_kernel"],
            "mamba_chunk_size": cfg["chunk_size"],
            "mamba_conv_bias": cfg["use_conv_bias"],
            "ssm_state_dtype": cfg["ssm_state_dtype"]}


def conv_dim(cfg):
    return work_ssm.conv_dim(mixer_keys(cfg))


def mixer_params(cfg):
    """W_in, W_out, the convolution with its bias, dt_bias, A_log, D and the
    gated norm's gain."""
    return work_ssm.mixer_params(mixer_keys(cfg))


attention_params = work_ssm.attention_params


def expert_params(cfg):
    """One routed expert: TWO matrices, no gate."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return 2 * cfg["hidden_size"] \
        * cfg["moe_shared_expert_intermediate_size"] \
        * int(cfg.get("n_shared_experts", 0) or 0)


def router_params(cfg):
    """The router's matrix and its selection-only bias."""
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts"]


def layer_params(cfg, letter, experts=None):
    """One layer of ``letter`` with its norm's gain, ``experts`` routed
    experts held (the whole layer's, by default)."""
    d = cfg["hidden_size"]
    if letter == "M":
        return d + mixer_params(cfg)
    if letter == "*":
        return d + attention_params(cfg)
    if letter == "-":
        return d + 2 * d * cfg["intermediate_size"]
    if letter != "E":
        raise ValueError("hybrid_override_pattern letter %r" % letter)
    n = cfg["n_routed_experts"] if experts is None else int(experts)
    return d + router_params(cfg) + shared_params(cfg) \
        + n * expert_params(cfg)


def model_params(cfg, layers=None, experts=None):
    """The layers run, the embedding, the untied head and the last norm;
    ``layers`` and ``experts`` default to the cell's cut."""
    experts = held_experts(cfg) if experts is None else experts
    return sum(layer_params(cfg, c, experts) for c in letters(cfg, layers)) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def active_params_per_token(cfg):
    """Parameters one token's forward pass multiplies in the whole model:
    every mixer and attention, the router, the shared expert and
    ``num_experts_per_tok`` routed experts of every ``E`` layer, the head
    (the embedding is a row)."""
    d = cfg["hidden_size"]
    e_layer = d + router_params(cfg) + shared_params(cfg) \
        + cfg["num_experts_per_tok"] * expert_params(cfg)
    return sum(e_layer if c == "E" else layer_params(cfg, c)
               for c in letters(cfg, cfg["num_hidden_layers"])) \
        + cfg["vocab_size"] * d + d


# -- what a slot holds -------------------------------------------------------

def state_row_bytes(cfg):
    """``(state, conv tail)`` bytes one slot holds of one ``M`` layer."""
    return work_ssm.state_row_bytes(mixer_keys(cfg))


def state_step_bytes(cfg):
    """What one decode step moves of one slot's row of one ``M`` layer: the
    state and the conv tail, each read once and written once."""
    return work_ssm.state_step_bytes(mixer_keys(cfg))


# cached keys and values of one position of one ``*`` layer, with the
# quantised pool's scales
kv_bytes_per_token = work_ssm.kv_bytes_per_token


def resident_bytes(cfg, traffic):
    """``{"weights", "state", "pages"}``: what the cell keeps on the chip
    before any program's scratch."""
    slots = int(traffic["slots"])
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    run = letters(cfg)
    return {
        "weights": model_params(cfg) * WEIGHT_BYTES,
        "state": run.count("M") * slots * sum(state_row_bytes(cfg)),
        "pages": run.count("*") * slots * int(traffic["cache_len"])
        * kv_bytes_per_token(cfg, kv)}


# -- a decode tick -----------------------------------------------------------

def expert_bytes(cfg):
    """One expert's two matrices."""
    return expert_params(cfg) * WEIGHT_BYTES


def experts_touched(cfg, tokens):
    """Expected number of this chip's held experts that at least one of
    ``tokens`` tokens chose, under uniform routing: ``held x (1 - (1 -
    k/E)^tokens)`` (61.0 of 64 at 64 tokens for top 6 of 128).
    ``moe_relu2_experts_hbm_util_pct`` does not lean on it and takes what a
    run routed from the program's counter."""
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    return held_experts(cfg) * (1.0 - (1.0 - float(k) / e) ** tokens)


def decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must move through HBM: the head once (the
    embedding is gathered by row); of every ``M`` layer run its matrices and
    the state row of every slot read and written; of every ``*`` layer its
    matrices and the keys and values of the live tokens at the pool's bytes;
    of every ``E`` layer the router, the shared expert and the expected
    number of held experts that ``slots`` tokens touch (``experts_touched``:
    uniform routing), two matrices each."""
    slots = int(traffic["slots"])
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    d = cfg["hidden_size"]
    total = (cfg["vocab_size"] * d + d) * WEIGHT_BYTES
    for c in letters(cfg):
        if c == "E":
            total += (d + router_params(cfg) + shared_params(cfg)) \
                * WEIGHT_BYTES \
                + experts_touched(cfg, slots) * expert_bytes(cfg)
            continue
        total += layer_params(cfg, c) * WEIGHT_BYTES
        if c == "M":
            total += slots * state_step_bytes(cfg)
        elif c == "*":
            total += float(live_tokens) * kv_bytes_per_token(cfg, kv)
    return total


# -- a chunk -----------------------------------------------------------------

def chunk_scan_work(cfg, tokens):
    """``(FLOPs, bytes)`` of the convolution and the recurrence of ONE ``M``
    layer over a chunk of ``tokens`` real tokens from a carried state, by the
    chunked algorithm at ``chunk_size``: ``work_ssm.chunk_scan_work``'s count
    (the causal half of a block's products, every token's share of the
    block's end state and its read of the start state; the state and the
    conv tail moved once each way, the streams in the stream's type)."""
    return work_ssm.chunk_scan_work(mixer_keys(cfg), tokens)
