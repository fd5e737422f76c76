"""Share of the window in which a collective runs on a chip and no other
operation does, averaged over the chips.
"""

from chipbench import trace


def read(facts):
    return trace.exposed_collective_pct(facts["trace"])
