"""Share of the first chip's busy time in the window spent in the always-on
shared expert of the stack's MoE layers (the sub-scope ``mx.moe/shared``;
the prediction block's own is under ``mx.mtp``): ``XLA Ops`` events joined to
the programs' scope maps.  None where no program has such a scope.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    return None if t is None else t["scopes"].get("moe/shared")
