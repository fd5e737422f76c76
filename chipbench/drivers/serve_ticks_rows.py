"""``serve_ticks_rows``: the ``serve_ticks`` loop for a model of routed
experts whose weights do not fit one float32 draw, its comparison judged by
the median row.

The window, its fences, the traffic, the timing, the prompt that is chunked
and decoded and the reference's one pass over it are ``serve_ticks``' own
code; the weights are drawn a leaf at a time (``serve_ticks_by_leaf``).  This
module puts one thing in that module's place while a run lasts: where
``serve_ticks.check_against_reference`` calls ``correct.compare_logp`` (the
maximum |log p - log p_ref| over every compared entry), it calls
``serve_ticks_mtp.compare_rows``: ``ok`` by the median over the compared rows
of a row's root-mean-square difference over the vocabulary, the maximum
printed beside it.  A rounding that flips one of a token's chosen experts
moves that row by a whole expert's part, and the largest of a million
differences then says how unlucky the worst row was; a mechanism at fault
moves every row, which the median row shows and the maximum hides (PERF.md
section 6, PR 46 and PR 50: sound maxima 0.03 to 0.40, the float8 control's
0.61).  The limit is the configuration's ``limits.serve_ticks_rows`` entry:
where ``serve_ticks.run`` asks for its own, before anything is built, it is
handed this driver's.  ``python -m chipbench.control`` compares by
``correct.compare_logp`` whatever the driver, so for a cell of this driver it
prints the statistic the cell is NOT held to; the control by this module's
comparison is ``benchmarks/probe_mistral4_faults.py``'s ``fp8_weights``.
"""
from __future__ import annotations

import contextlib
import types

from . import serve_ticks, serve_ticks_by_leaf
from .serve_ticks_mtp import compare_rows

NAME = "serve_ticks_rows"
control_case = serve_ticks_by_leaf.control_case


@contextlib.contextmanager
def _by_rows():
    """``serve_ticks`` sees ``chipbench.correct`` whole, but for the
    comparison and for whose limit is read."""
    before = serve_ticks.correct
    serve_ticks.correct = types.SimpleNamespace(**dict(
        vars(before), compare_logp=compare_rows,
        limit=lambda cfg, _driver, name: before.limit(cfg, NAME, name)))
    try:
        yield
    finally:
        serve_ticks.correct = before


def run(job):
    with _by_rows():
        return serve_ticks_by_leaf.run(job)
