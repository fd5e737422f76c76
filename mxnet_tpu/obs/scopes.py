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

__all__ = ["LAYER_ATTR", "LAYER_OF_OP", "LAYERS", "SUBSCOPES", "UNSCOPED",
           "layer_of", "node_scope", "scope", "scope_of", "scope_map"]

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
}
# every value a scope's <layer> may take: the table's, "other" for op
# kinds it does not list, and the two fixed scopes of the train step
# "attn_window" is what a model builder names its sliding-window attention
# nodes (LAYER_ATTR), so that "attn" keeps meaning attention over the whole
# context
LAYERS = tuple(sorted(set(LAYER_OF_OP.values()))) + (
    "attn_window", "other", "optimizer", "metric")
SUBSCOPES = ("kv_append", "kv_gather", "kv_dequant", "scores", "rope",
             "route", "experts", "combine",
             # the state-space mixer: its convolution, the recurrence over a
             # chunk or a sequence ("scan") and over one token a slot
             # ("step"), the gate with its grouped norm
             "conv", "scan", "step", "gate_norm")
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
    from ..analysis.hlo_parse import instruction_op_names

    module, rows = instruction_op_names(hlo_text)
    return module, {name: (scope_of(op_name) if op_name else None)
                    or UNSCOPED for name, op_name in rows}
