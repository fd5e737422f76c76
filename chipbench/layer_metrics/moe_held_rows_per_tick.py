"""(token, chosen expert) pairs a decode tick routes to the experts this
chip holds, summed over the MoE layers: the mean over the window's ticks of
what the decode program counted (``work_moe.routed``: beside its tokens,
idle slots left out).  The load the held experts see.
"""

from chipbench import work_moe


def read(facts):
    rows = work_moe.routed(facts, "moe_rows_held")
    return sum(rows) / len(rows) if rows else None
