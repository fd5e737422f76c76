"""Share of the ``unscoped`` device time of the train step whose instruction
the program's instruction maps name by what it carries (``src``: the entry
parameter it descends from, else its producer's scope) or by what it feeds
(``feeds``: its nearest scoped consumer).
"""

from chipbench import moves


def read(facts):
    return moves.pct(facts, "unscoped_named_pct")
