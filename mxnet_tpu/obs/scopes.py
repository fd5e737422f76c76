"""Layer scopes — the one vocabulary that names device time.

Every operation traced into a compiled program sits under
``jax.named_scope("mx.<layer>/<node name>")``.  ``<layer>`` comes from
:data:`LAYER_OF_OP`, keyed on the op kind, unless the model builder
overrides it with the node attribute :data:`LAYER_ATTR` (through the
existing ``mx.AttrScope``).  The optimizer update and the device metric
of the fused train step sit under ``mx.optimizer`` / ``mx.metric``;
inside attention, :func:`scope` adds the sub-scopes of
:data:`SUBSCOPES` where serving time is suspected.

A scope changes HLO *metadata* only (``op_name``): the compiled program
is the same with or without it, and no flag selects it.  XLA keeps the
``op_name`` of a fusion's root on the fusion, so :func:`scope_map` reads
the program's own optimized HLO text back into ``{instruction name:
"<layer>[/<sub>]"}`` — the join the device trace needs, whose events
carry instruction names (``%fusion.123``) and nothing else.
"""
from __future__ import annotations

import re

__all__ = ["LAYER_ATTR", "LAYER_OF_OP", "LAYERS", "MOVE_OPCODES", "OUTPUT",
           "SUBSCOPES", "UNSCOPED", "instruction_map", "layer_of",
           "node_scope", "scope", "scope_of", "scope_map"]

LAYER_ATTR = "__layer__"

LAYER_OF_OP = {
    "dot_product_attention": "attn",
    "FullyConnected": "linear",
    "BatchNorm": "norm",
    "Convolution": "conv",
    "Pooling": "pool",
    "Embedding": "embed",
    "SoftmaxOutput": "head_loss",
    "MoEFFN": "moe",
    "RMSNorm": "norm",
    "SelectiveSSM": "ssm",
    "LightningAttention": "linattn",
    "LatentAttention": "attn_latent",
    "KimiDeltaAttention": "kda",
    "GatedDeltaNet": "gdn",
}
# every value a scope's <layer> may take: the table's, "other" for op
# kinds it does not list, and the two fixed scopes of the train step
# "attn_window" is what a model builder names its sliding-window attention
# nodes (LAYER_ATTR), so that "attn" keeps meaning attention over the whole
# context; "attn_sparse" likewise its nodes with sparse selection
# "mtp" is what a builder names every node of a multi-token-prediction block:
# the block's attention, experts and head are then one layer of their own
LAYERS = tuple(sorted(set(LAYER_OF_OP.values()))) + (
    "attn_window", "attn_sparse", "mtp", "other", "optimizer", "metric")
SUBSCOPES = ("kv_append", "kv_gather", "kv_dequant", "scores", "rope",
             "route", "experts", "combine",
             # the always-on gated MLP beside the routed experts
             "shared",
             # the state-space mixer: its convolution, the recurrence over a
             # chunk or a sequence ("scan") and over one token a slot
             # ("step"), the gate with its grouped norm
             "conv", "scan", "step", "gate_norm",
             # lightning linear attention: its recurrence over a chunk or a
             # sequence ("chunk"; "step" and "gate_norm" as above).  Sparse
             # selection: the index's rows written, and the scoring, pooling
             # and top-k that choose a row's blocks
             "chunk", "index_append", "select",
             # latent attention: the cached rows turned back into per-head
             # keys and values ("expand", the chunk's form), and the key
             # up-projection folded into the query with the value one after
             # the weighted sum ("absorb", the decode row's)
             "expand", "absorb",
             # Kimi delta attention: the triangular solve a block of its
             # chunked form ("conv", "chunk", "step", "gate_norm" as above)
             "solve")
# an instruction no mx.<layer> scope reaches (compiler-made copies,
# casts between the step's phases)
UNSCOPED = "unscoped"


def layer_of(node):
    """The layer of one graph node: its ``__layer__`` attribute, else
    the table's entry for its op kind, else ``other``."""
    attrs = node.attrs
    return (attrs.get(LAYER_ATTR) if attrs else None) \
        or LAYER_OF_OP.get(node.op.name, "other")


def scope(layer, sub=None):
    """``jax.named_scope("mx.<layer>[/<sub>]")``."""
    import jax

    return jax.named_scope("mx.%s/%s" % (layer, sub) if sub
                           else "mx." + layer)


def node_scope(node):
    """The scope both node walks (``executor._run_graph`` and
    ``decode._cached_forward``) trace one graph node under."""
    return scope(layer_of(node), node.name)


# -- reading the scopes back out of optimized HLO ---------------------------

# the last mx.<layer>[/<x>] of an op_name; jax wraps the scopes of a
# backward operation as transpose(jvp(mx.attn/att))/mx.attn/scores/mul,
# which the search strips by taking the innermost (= last) scope
_SCOPE_RE = re.compile(r"mx\.([a-z_]+)(?:/([^/()\"]+))?")


def scope_of(op_name):
    """``"<layer>[/<sub>]"`` of one HLO ``op_name``, or None where no
    ``mx.`` scope is on it."""
    found = _SCOPE_RE.findall(op_name)
    if not found:
        return None
    layer, leaf = found[-1]
    return "%s/%s" % (layer, leaf) if leaf in SUBSCOPES else layer


def scope_map(hlo_text):
    """``(module name, {instruction name: "<layer>[/<sub>]"})`` of one
    optimized HLO module's text.  Every instruction of every computation
    is listed; a fusion counts for the scope of its own
    ``metadata.op_name``; :data:`UNSCOPED` where an instruction carries
    no ``mx.`` scope."""
    from ..analysis.hlo_parse import instructions

    module, rows = instructions(hlo_text)
    return module, {r.name: (scope_of(r.op_name) if r.op_name else None)
                    or UNSCOPED for r in rows}


# -- what an instruction is, not only whose it is ---------------------------

# opcodes that compute nothing: they change where a value lives or how it
# is laid out.  ``slice`` and ``dynamic-slice`` count where they stand as
# instructions of their own (inside a fusion that computes they are that
# fusion's addressing)
MOVE_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "slice", "dynamic-slice", "reshape",
    "transpose", "broadcast", "bitcast"))
# what a fusion or an async wrapper may hold beside them and still compute
# nothing
_PLUMBING = frozenset(("parameter", "constant", "tuple", "get-tuple-element"))
# the later parts of an async wrapper, which move if its start does
_ASYNC_AFTER = ("async-update", "async-done")
# where a value goes when no layer reads it inside the program
OUTPUT = "output"
# instructions a walk to the consumers visits at most
_WALK = 512


def _parameter_name(row):
    """An entry parameter under the name jax gives it in its metadata
    (``state.caches[3][0].scale``, ``env['fc1_weight']``), else its number
    and shape."""
    if row.op_name:
        return row.op_name.replace("\\'", "'")
    return "parameter(%s) %s" % (row.index, row.shape.split("{", 1)[0])


def instruction_map(hlo_text):
    """``(module name, {instruction name: {"scope", "opcode", "shape",
    "bytes", "moves", "src", "feeds", "n_feeds"}})`` of one optimized HLO
    module's text, for every instruction :func:`scope_map` lists and with
    the same ``scope``.

    ``moves``: the instruction computes nothing (:data:`MOVE_OPCODES`; a
    fusion, or an async start / done pair such as ``slice-start``, whose
    computation holds only those).  For an instruction that has no scope
    or that moves:

    - ``src`` is what it carries: the entry parameter it descends from,
      through instructions that have no scope or only move, under the name
      jax gave that parameter; else the scope of its nearest producer that
      has one.  A ``while`` body's parameter is followed to the operand the
      loop was entered with.
    - ``feeds`` (without a scope only) is the scope of its nearest consumer
      that has one, through ``copy-start -> copy-done -> fusion`` chains;
      where its consumers lead to different scopes, the one listed first,
      with ``n_feeds`` the number of different ones; :data:`OUTPUT` where
      only the program's result reads it.

    Either is None where the walk finds nothing."""
    from ..analysis.hlo_parse import instructions

    module, rows = instructions(hlo_text)
    by_name = {r.name: r for r in rows}
    scope = {r.name: (scope_of(r.op_name) if r.op_name else None)
             for r in rows}
    members, users = {}, {}
    for r in rows:
        members.setdefault(r.computation, []).append(r)
        for o in r.operands:
            users.setdefault(o, []).append(r.name)
    # a loop body's parameter stands for the operand of the ``while`` that
    # runs it
    entered = {}
    for r in rows:
        if r.opcode == "while" and r.operands:
            for c in r.called:
                entered[c] = r.operands[0]

    def only_moves(computation, seen=()):
        held = members.get(computation, ())
        return bool(held) and all(
            h.opcode in MOVE_OPCODES or h.opcode in _PLUMBING
            or (h.opcode == "fusion" and h.called
                and h.called[0] not in seen
                and only_moves(h.called[0], seen + (computation,)))
            for h in held) and any(h.opcode not in _PLUMBING for h in held)

    moves = {}

    def is_move(r):
        if r.name not in moves:
            moves[r.name] = False       # a cycle would be no move
            if r.opcode in MOVE_OPCODES:
                hit = True
            elif r.opcode in ("fusion", "async-start") and r.called:
                hit = only_moves(r.called[0])
            elif r.opcode in _ASYNC_AFTER and r.operands \
                    and r.operands[0] in by_name:
                hit = is_move(by_name[r.operands[0]])
            else:
                hit = False
            moves[r.name] = hit
        return moves[r.name]

    def passes(r):
        return scope[r.name] is None or is_move(r)

    def producers(r):
        """Whose values ``r`` reads.  Element k of a loop's state stands
        for operand k of the tuple the loop was entered with."""
        if r.opcode == "get-tuple-element" and r.operands:
            o = by_name.get(r.operands[0])
            if o is not None and o.opcode == "parameter" and not o.entry:
                t = by_name.get(entered.get(o.computation))
                if t is None:
                    return ()
                if t.opcode == "tuple" and r.index is not None \
                        and r.index < len(t.operands):
                    return (t.operands[r.index],)
                return (t.name,)
        if r.opcode == "parameter" and not r.entry:
            return (entered[r.computation],) \
                if r.computation in entered else ()
        return r.operands

    def source_of(r):
        """``(parameter name or None, nearest producer's scope or None)``
        behind ``r``, breadth first over its operands."""
        seen, queue, fallback = {r.name}, [r], None
        while queue:
            nxt = []
            for cur in queue:
                for name in producers(cur):
                    o = by_name.get(name)
                    if o is None or o.name in seen:
                        continue
                    seen.add(o.name)
                    if o.opcode == "parameter" and o.entry:
                        return _parameter_name(o), fallback
                    if o.opcode == "parameter" or o.opcode in _PLUMBING \
                            or passes(o):
                        nxt.append(o)
                    elif fallback is None:
                        fallback = scope[o.name]
            queue = nxt
        return None, fallback

    def fed_by(r):
        """The scopes ``r``'s consumers lead to, in the order listed: each
        consumer's own, or what a consumer without one leads to in turn
        (a bounded walk: a region no scope reaches is not searched whole)."""
        seen, stack, found = {r.name}, [r.name], []
        while stack and len(seen) < _WALK:
            cur = stack.pop()
            nxt = []
            for name in users.get(cur, ()):
                if name in seen:
                    continue
                seen.add(name)
                if scope[name] is not None:
                    found.append(scope[name])
                elif by_name[name].root and by_name[name].entry:
                    found.append(OUTPUT)
                else:
                    nxt.append(name)
            stack.extend(reversed(nxt))
        named = [f for f in found if f != OUTPUT] or found
        return list(dict.fromkeys(named))

    out = {}
    for r in rows:
        own = scope[r.name]
        row = {"scope": own or UNSCOPED, "opcode": r.opcode,
               "shape": r.shape, "bytes": r.bytes, "moves": is_move(r),
               "src": None, "feeds": None, "n_feeds": 0}
        if own is None or row["moves"]:
            param, producer = source_of(r)
            row["src"] = param or producer
        if own is None:
            fed = fed_by(r)
            row["feeds"] = fed[0] if fed else None
            row["n_feeds"] = len(fed)
        out[r.name] = row
    return module, out
