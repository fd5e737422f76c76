"""Share of the first chip's busy time in the window that no ``mx.<layer>``
scope names: instructions without a scope in their program's map, and
operations of programs that have no map.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, scopes.UNSCOPED)
