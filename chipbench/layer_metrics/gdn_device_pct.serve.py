"""Share of the first chip's busy time in the window spent under the
``mx.gdn`` scopes of the serving programs (``conv``, ``step``, ``chunk``,
``solve``, ``gate_norm``): Gated DeltaNet between its projections,
which are ``FullyConnected`` nodes and stay under ``mx.linear``.  ``XLA Ops``
events joined to the programs' scope maps.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    # 0 would say "a mixer that took no time": where no program has the
    # scope (the parent of the PR that added it) the metric is left out
    return None if t is None or "gdn" not in t["layers"] \
        else t["layers"]["gdn"]
