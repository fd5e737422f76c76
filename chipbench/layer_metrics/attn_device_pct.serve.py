"""Share of the first chip's busy time in the window spent under the
``mx.attn`` scopes of the serving programs (``kv_append``, ``kv_gather``,
``kv_dequant``, ``scores`` and the two einsums): ``XLA Ops`` events joined
to the programs' scope maps.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "attn")
