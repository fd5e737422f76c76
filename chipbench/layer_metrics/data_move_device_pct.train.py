"""Share of the first chip's busy time in the window spent in instructions of
the train step that compute nothing (``moves`` in the program's instruction
maps): copies and their async halves, slices, reshapes, transposes,
broadcasts, bitcasts and fusions of those alone, under a layer's scope or
under none.
"""

from chipbench import moves


def read(facts):
    return moves.pct(facts, "data_move_pct")
