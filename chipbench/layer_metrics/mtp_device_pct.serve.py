"""Share of the first chip's busy time in the window spent under the
``mx.mtp`` scope of the serving programs: every node of the
multi-token-prediction block (its projection, attention, experts and head);
``XLA Ops`` events joined to the programs' scope maps.  None where no program
has such a scope.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    return None if t is None else t["layers"].get("mtp")
