"""1 - the union of the chip's operation intervals over the traced window,
averaged over the chips used.
"""

from chipbench import trace


def read(facts):
    return trace.idle_pct(facts["trace"])
