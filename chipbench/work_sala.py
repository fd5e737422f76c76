"""Bytes and operations of a decoder whose layers are lightning linear
attention (a matrix state a head) or attention that selects its blocks from
an index of compressed keys (``model_type`` ``minicpm_sala``): what a decode
tick must move, and what a lightning step, a lightning chunk, the scoring of
the index and the attention over the chosen blocks must do.

Counts of what the mathematics must move, never of what a program happens
to: a state read twice, the other KV head's lanes of a gathered page, list
entries that point at the scratch page, the dense walk under a chunk's mask
and a chunk's padding are not work.  ``work.decode_step_bytes`` finds
:func:`decode_step_bytes` through the configuration's ``counts``; the
readers under ``layer_metrics/`` take the rest, so that a later change of
implementation is measured against the same work.
"""
from __future__ import annotations

WEIGHT_BYTES = 2          # bfloat16
SCALE_BYTES = 4           # one float32 scale a (token, kv head), int8 pool
STATE_BYTES = 4           # the lightning state is float32
INDEX_BYTES = 2           # a compressed key is kept in the stream's type


def layers_run(cfg):
    """``(sparse, lightning)``: how many of the layers run are of each
    kind (the published ``mixer_types`` from ``serve_first_layer``)."""
    first = int(cfg.get("serve_first_layer", 0))
    run = cfg["mixer_types"][first:first + int(cfg.get(
        "serve_num_hidden_layers", cfg["num_hidden_layers"]))]
    return run.count("minicpm4"), run.count("lightning-attn")


def lightning_params(cfg):
    """W_q, W_k, W_v, W_g, W_o and the three norms' gains."""
    d = cfg["hidden_size"]
    width = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return 5 * d * width + 2 * cfg["lightning_head_dim"] + width


def sparse_params(cfg):
    """W_q, W_g, W_o at the query heads, W_k and W_v at the KV heads."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 3 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def model_params(cfg):
    """The layers run (each with its MLP and two norms), the embedding, the
    untied head and the last norm."""
    sparse, lightning = layers_run(cfg)
    d = cfg["hidden_size"]
    return sparse * sparse_params(cfg) + lightning * lightning_params(cfg) \
        + (sparse + lightning) * (mlp_params(cfg) + 2 * d) \
        + 2 * cfg["vocab_size"] * d + d


def state_row_bytes(cfg):
    """Bytes one slot holds of one lightning layer: H matrices of D x D."""
    return cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 * STATE_BYTES


def state_step_bytes(cfg):
    """What one decode step moves of one slot's row of one lightning layer:
    the state read once and written once."""
    return 2 * state_row_bytes(cfg)


def kv_position_bytes(cfg, kv_bytes):
    """One cached position of ONE KV head of one sparse layer: its key, its
    value and, in a quantised pool, their two scales."""
    return 2 * cfg["head_dim"] * kv_bytes \
        + (2 * SCALE_BYTES if kv_bytes < 2 else 0)


def index_row_bytes(cfg):
    """One compressed key of one KV head."""
    return cfg["head_dim"] * INDEX_BYTES


def attended(cfg, n):
    """``(positions, index rows)`` one decode row of a context of ``n``
    reads a KV head of a sparse layer: every position and no index at or
    under ``dense_len``; past it the positions of ``topk`` blocks and the
    compressed keys of the complete windows."""
    sc = cfg["sparse_config"]
    if n <= sc["dense_len"]:
        return n, 0
    return min(n, sc["topk"] * sc["block_size"]), \
        max(0, (int(n) - sc["kernel_size"]) // sc["kernel_stride"] + 1)


def decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must move through HBM: every matrix of the
    layers run and the head once (the embedding is gathered by row); every
    slot's lightning states read and written; and of each sparse layer, a
    KV head, what :func:`attended` says of a slot at the mean live length
    (``live_tokens`` over ``slots``).  ``slots`` is the traffic file's: a
    backlog keeps them full but for the one that prefills."""
    sparse, lightning = layers_run(cfg)
    slots = int(traffic["slots"])
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    d = cfg["hidden_size"]
    weights = (model_params(cfg) - cfg["vocab_size"] * d) * WEIGHT_BYTES
    positions, rows = attended(cfg, float(live_tokens) / slots)
    selected = cfg["num_key_value_heads"] * (
        positions * kv_position_bytes(cfg, kv) + rows * index_row_bytes(cfg))
    return weights + lightning * slots * state_step_bytes(cfg) \
        + sparse * slots * selected


def lightning_step_work(cfg):
    """``(FLOPs, bytes)`` of one decode row of one lightning layer: the
    state decayed, the outer product added (3 a state element) and read by
    the query (2); the state read once and written once."""
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return 5 * h * d * d, state_step_bytes(cfg)


def lightning_chunk_work(cfg, tokens, block=256):
    """``(FLOPs, bytes)`` of one lightning layer over a chunk of ``tokens``
    real tokens from a carried state, by blocks of ``block``: inside a block
    token i needs ``q_i . k_j`` and the weighted sum of ``v_j`` over the i +
    1 tokens j <= i (2 D each a head: the causal half of the products);
    every token reads ``q S`` of its block's start state and adds ``k^T v``
    to its end state (2 D D a head each).  Bytes: the state read and
    written once, q, k, v and the gate read and the output written in the
    stream's type."""
    h, d, t = cfg["lightning_nh"], cfg["lightning_head_dim"], int(tokens)
    pairs = sum(b * (b + 1) // 2
                for b in [block] * (t // block)
                + ([t % block] if t % block else []))
    flops = 2 * pairs * 2 * d * h + 2 * 2 * t * h * d * d
    moved = state_step_bytes(cfg) + 5 * t * h * d * WEIGHT_BYTES
    return flops, moved


def index_score_work(cfg, n):
    """``(FLOPs, bytes)`` of scoring the index for one decode row of a
    context of ``n`` in one sparse layer: every query head against every
    complete window of its KV head."""
    _, rows = attended(cfg, n)
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * rows, \
        cfg["num_key_value_heads"] * rows * index_row_bytes(cfg)


def chosen_attend_work(cfg, n, kv_bytes=1):
    """``(FLOPs, bytes)`` of one decode row's attention over what it chose
    in one sparse layer: the two products over the positions
    :func:`attended` gives, every query head; those positions' keys, values
    and scales a KV head."""
    positions, _ = attended(cfg, n)
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * positions, \
        cfg["num_key_value_heads"] * positions \
        * kv_position_bytes(cfg, kv_bytes)


def selected_bytes(cfg, chosen, live, kv_bytes=1):
    """What a decode tick's sparse layers must read of the pools, from the
    tick's own counts: ``chosen`` (slot, KV group, layer) blocks attended,
    ``live`` blocks their contexts hold, whose windows' compressed keys are
    scored (``block_size / kernel_stride`` rows a block)."""
    sc = cfg["sparse_config"]
    return chosen * sc["block_size"] * kv_position_bytes(cfg, kv_bytes) \
        + live * (sc["block_size"] // sc["kernel_stride"]) \
        * index_row_bytes(cfg)


# -- what a traced window shows ---------------------------------------------

def scope_and_moves_pct(facts, scope):
    """Share (%) of the first chip's busy time in the window spent in
    events under ``scope`` or in the events of a move for it: an
    instruction that only moves (a copy, a slice, either half of their
    asynchronous pairs) and that ``obs.programs.instruction_maps()`` says
    feeds ``scope`` or carries what ``scope`` made.  Each event counts for
    the time it takes itself, as ``scopes.by_scope`` counts: the compiler
    brings a large operand into fast memory by asynchronous moves issued
    well ahead, and how long such a move is IN FLIGHT says when it was
    issued, not what it cost, so nothing here is a share of a bandwidth.
    None where the trace has no device or no program lists the scope."""
    import bisect

    from . import moves as moves_mod, scopes as scopes_mod, trace

    parsed = facts.get("trace")
    if not parsed or not parsed.get("devices"):
        return None
    maps = facts.get("instruction_maps") or moves_mod.program_maps()[0]
    if not maps:
        return None
    lo, hi = trace.window_of(parsed)
    first = parsed["devices"][sorted(parsed["devices"])[0]]
    modules = first.get(trace.MODULES_LINE, [])
    starts = [s for _, s, _ in modules]
    events = []
    for name, s, d in first[trace.OPS_LINE]:
        i = bisect.bisect_right(starts, s) - 1
        stem = trace.module_stem(modules[i][0]) \
            if i >= 0 and s < modules[i][1] + modules[i][2] else None
        events.append(((stem, name), s, s + d))
    busy = took = 0
    for (stem, name), ns in scopes_mod.self_times(events, lo, hi).items():
        busy += ns
        what = (maps.get(stem) or {}).get("instructions", {}).get(name)
        if what and (what.get("scope") == scope or (
                what.get("moves")
                and scope in (what.get("feeds"), what.get("src")))):
            took += ns
    return 100.0 * took / busy if took else None
