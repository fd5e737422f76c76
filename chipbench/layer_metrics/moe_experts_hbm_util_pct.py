"""The expert products' share of their roofline: the expert weights a decode
tick must read over the decode program's device time under
``mx.moe/experts`` a tick, as a share of the chip's HBM peak.  What must be
read is what the window routed: the held experts that at least one of a
tick's tokens chose, summed over the MoE layers (the decode program's own
count a tick, ``work_moe.routed``, averaged over the window's ticks), times
one expert's bytes (``work_moe.expert_bytes``).  A program that reads every held expert
whatever was chosen reads more than that and shows it here.  The time is
that of the ``XLA Ops`` events inside the decode program's runs whose
instruction the program's scope map files under ``moe/experts`` (innermost
event, as ``scopes.by_scope`` counts), over the number of those runs in the
window.
"""

import bisect
import re

from chipbench import scopes, trace, work_moe

MODULE = re.compile(r"paged_decode")
SCOPE = "moe/experts"


def read(facts):
    parsed = facts.get("trace")
    if not parsed or not parsed.get("devices"):
        return None
    visits = work_moe.routed(facts, "moe_expert_visits")
    if not visits:
        return None
    maps = facts.get("scope_maps") or scopes.program_maps()[0]
    if not maps:
        return None
    lo, hi = trace.window_of(parsed)
    first = parsed["devices"][sorted(parsed["devices"])[0]]
    runs = [(trace.module_stem(n), s, s + d)
            for n, s, d in first.get(trace.MODULES_LINE, ())
            if MODULE.search(trace.module_stem(n)) and s >= lo
            and s + d <= hi]
    if not runs:
        return None
    starts = [s for _, s, _ in runs]
    events = []
    for name, s, d in first[trace.OPS_LINE]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][2]:
            events.append(((runs[i][0], name), s, s + d))
    ns = sum(t for (stem, name), t in
             scopes.self_times(events, lo, hi).items()
             if maps.get(stem, {}).get(name) == SCOPE)
    if not ns:
        return None
    need = sum(visits) / len(visits) \
        * work_moe.expert_bytes(facts["config"])
    return 100.0 * need / (ns / 1e9 / len(runs)) \
        / facts["peaks"]["hbm_bytes_per_s"]
