"""Share of the first chip's busy time in the window spent under the
``mx.linattn`` scopes of the serving programs (``step``, ``chunk``,
``gate_norm``, the q/k norms and the rotation): lightning linear attention
between its projections, which are ``FullyConnected`` nodes and stay under
``mx.linear``.  ``XLA Ops`` events joined to the programs' scope maps.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    # 0 would say "a mixer that took no time": where no program has the
    # scope (the parent of the PR that added it) the metric is left out
    return None if t is None or "linattn" not in t["layers"] \
        else t["layers"]["linattn"]
