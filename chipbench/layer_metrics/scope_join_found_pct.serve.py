"""Share of the first chip's busy time in the window on (module, instruction)
pairs that a map lists which was read off the dispatched executable
(``source`` ``"dispatched"``) and which no other live program of that module
name disagrees with (``conflicts`` 0): how much of the serving programs'
device time the per-scope metrics can be trusted on.
"""

from chipbench import moves


def read(facts):
    return moves.pct(facts, "join_found_pct")
