"""Share of the first chip's busy time in the window spent under the
``mx.ssm`` scopes of the serving programs (``conv``, ``scan``, ``step``,
``gate_norm``) of a stack whose mixers stand alone in their layers: the
state-space mixer between its two projections, which are ``FullyConnected``
nodes and stay under ``mx.linear``.  ``XLA Ops`` events joined to the
programs' scope maps.  ``ssm_device_pct.serve`` reads the same scopes where
the mixer stands beside attention in every block and moves the gap; this
one moves the throughput.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    # 0 would say "a mixer that took no time": where no program has the
    # scope the metric is left out
    return None if t is None or "ssm" not in t["layers"] \
        else t["layers"]["ssm"]
