"""Share of the first chip's busy time in the window spent under the
``mx.moe`` scopes of the serving programs (``route``, ``experts``,
``combine``, ``shared``) of a stack whose experts are a layer of their own,
two matrices each: ``XLA Ops`` events joined to the programs' scope maps.
``moe_device_pct.serve`` reads the same scopes and moves the gap; this one
moves the throughput.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    return None if t is None or "moe" not in t["layers"] \
        else t["layers"]["moe"]
