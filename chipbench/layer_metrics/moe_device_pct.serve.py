"""Share of the first chip's busy time in the window spent under the
``mx.moe`` scopes of the serving programs (``route``, ``experts``,
``combine``): ``XLA Ops`` events joined to the programs' scope maps.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "moe")
