"""Share of the first chip's busy time in the window that the decode step's
lightning recurrence takes: the events under ``mx.linattn/step`` and the
events of the compiler's moves of its state (slices into fast memory issued
ahead, the copy of the result out: ``work_sala.scope_and_moves_pct``), each
for the time it takes itself.  Not a share of a bandwidth: the state moves
beside other layers' work, and the time a move is in flight says when it was
issued (a share of the HBM peak over the step's own events read 195 %, over
the moves in flight 25.7 %; ``PERF.md`` section 3, PR 43).
"""

from chipbench import work_sala


def read(facts):
    return work_sala.scope_and_moves_pct(facts, "linattn/step")
