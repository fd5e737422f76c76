"""Bytes and operations that serving a latent-attention decoder (``mistral4``:
multi-head latent attention, every layer a routed expert layer beside a
shared expert) must move and do, whatever implements it, at one chip's share
of the routed experts.

``decode_step_bytes``: one decode tick reads every matrix of the layers run
once (the low-rank query's two, the latent's down- and up-projection, the
output's, the router, the shared expert), the held routed experts that the
tick's ``slots`` rows touch (an expectation under uniform routing, as
``work_moe.experts_touched``; what a run routed is the program's own counter
and ``moe_experts_hbm_util_pct`` reads that), the head, the embedding rows,
and the live latent rows: ``kv_lora_rank + qk_rope_head_dim`` values a
position a layer, ONE plane for all heads.  Never counted: gathered views,
keys and values expanded from the rows, rows of padding.
``work.decode_step_bytes`` finds it through the configuration's ``counts``.

``absorbed_step_bytes`` / ``expanded_chunk_flops``: the least the attention
between its projections must read in a decode tick (the live rows once and
the up-projection) and compute in a prefill chunk (every live position
expanded once, then causal QK and PV at the published head widths): what the
two roofline readers divide by latent attention's device time.

``scope_maps`` says what that time is: the instructions under the
``mx.attn_latent`` scopes AND the compiler's own moves between two of them.
The absorbed walk's gathered pages are re-laid out to positions by a
``reshape`` that carries no scope of its own (5.5 ms of a 33.6-ms tick, my
chip run, PR 50), and a reader that left it out would call the layer cheaper
than it is and would not move when a kernel took the reshape away.
"""
from __future__ import annotations

from . import work_moe

WEIGHT_BYTES = work_moe.WEIGHT_BYTES


layers_run = work_moe.layers_run
experts_touched = work_moe.experts_touched
# the scopes latent attention's device time is booked under (obs.scopes)
SCOPES = {"attn_latent"} | {"attn_latent/" + s for s in (
    "rope", "kv_append", "kv_gather", "kv_dequant", "scores", "absorb",
    "expand")}


def scope_maps(facts):
    """The running programs' scope maps (``{module: {instruction: scope}}``,
    what ``scopes.by_scope`` and ``work_ssm.scope_seconds`` join a trace
    with), with every instruction that only moves, carries no scope, and
    lies between two of the layer's own (``obs.programs.instruction_maps()``:
    its ``src``, the scope that made what it carries, and its ``feeds``, its
    nearest scoped consumer, are both in :data:`SCOPES`) booked under its
    ``src``.  None where the program has no scope maps; the maps as they are
    where it has no instruction maps."""
    from . import moves, scopes

    plain = facts.get("scope_maps") or scopes.program_maps()[0]
    if not plain:
        return None
    out = {stem: dict(names) for stem, names in plain.items()}
    what = facts.get("instruction_maps") or moves.program_maps()[0] or {}
    for stem, entry in what.items():
        for name, ins in entry["instructions"].items():
            if ins["moves"] and ins["scope"] == scopes.UNSCOPED \
                    and ins["src"] in SCOPES and ins["feeds"] in SCOPES:
                out.setdefault(stem, {})[name] = ins["src"]
    return out


def device_seconds(facts, module):
    """``(seconds, runs)`` of latent attention (:func:`scope_maps`) in the
    window's runs of the program ``module``, as ``work_ssm.scope_seconds``
    gives them; None where there is nothing to read."""
    from . import work_ssm

    maps = scope_maps(facts)
    return None if maps is None else work_ssm.scope_seconds(
        dict(facts, scope_maps=maps), module, SCOPES)


def device_pct(facts):
    """Latent attention's share (%) of the first chip's busy time in the
    window (:func:`scope_maps`); None where no program has the scope."""
    from . import scopes

    parsed, maps = facts.get("trace"), scope_maps(facts)
    if not maps or not parsed or not parsed.get("devices"):
        return None
    raw = scopes.by_scope(parsed, maps)
    took = sum(ns for scope, ns in raw["scopes"].items() if scope in SCOPES)
    return 100.0 * took / raw["busy_ns"] if took else None


def row_values(cfg):
    """Values cached a position a layer: the latent and the rotary part."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def up_projection_params(cfg):
    """W_kvb: the latent to every head's key dims without positions and its
    values."""
    return cfg["num_attention_heads"] * cfg["kv_lora_rank"] \
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def attention_params(cfg):
    """W_qa, its gain, W_qb, W_kva, the latent's gain, W_kvb, W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d * rq + rq + rq * h * qk + d * row_values(cfg) + r \
        + up_projection_params(cfg) + h * cfg["v_head_dim"] * d


def expert_params(cfg):
    """One gated expert's three matrices (a routed one, or the shared)."""
    return work_moe.expert_bytes(cfg) // WEIGHT_BYTES


def layer_params(cfg, rows):
    """What one layer reads of its weights in a step over ``rows`` rows."""
    d = cfg["hidden_size"]
    return attention_params(cfg) + 2 * d + d * cfg["n_routed_experts"] \
        + int(cfg.get("n_shared_experts") or 0) * expert_params(cfg) \
        + experts_touched(cfg, rows) * expert_params(cfg)


def decode_step_bytes(cfg, traffic, live_tokens):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    slots = int(traffic["slots"])
    layers = layers_run(cfg)
    params = d * v + slots * d + d + layers * layer_params(cfg, slots)
    return params * WEIGHT_BYTES \
        + layers * float(live_tokens) * row_values(cfg) * WEIGHT_BYTES


def absorbed_step_bytes(cfg, live_rows):
    """``live_rows`` (slot, cached position, layer) triples read once, and
    each layer's up-projection (folded into the query, applied after the
    weighted sum)."""
    return (float(live_rows) * row_values(cfg)
            + layers_run(cfg) * up_projection_params(cfg)) * WEIGHT_BYTES


def expanded_chunk_flops(cfg, pos, tokens):
    """A chunk of ``tokens`` rows at positions ``pos ..`` in every layer run:
    each of the ``pos + tokens`` live positions expanded once, and row i's
    products with the ``pos + i + 1`` positions it sees."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pos, tokens = int(pos), int(tokens)
    pairs = tokens * pos + tokens * (tokens + 1) // 2
    expand = 2.0 * (pos + tokens) * up_projection_params(cfg)
    return layers_run(cfg) * (expand
                              + 2.0 * pairs * h * (qk + cfg["v_head_dim"]))
