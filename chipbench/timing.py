"""Rate arithmetic on host-clock timestamps — the yardstick for every rate
and tail the benchmark reports.  Pure functions of lists of numbers, so a
test can hold them to hand-computed answers.

A window is the interval between two fences, ``t0`` and ``t1``; ``stamps``
are the ``time.perf_counter()`` readings the loop appended at the end of
each step (or tick) inside it.  Work is whole steps; time is measured time,
never the nominal ``--seconds``.
"""
from __future__ import annotations

import statistics

SEGMENT_S = 2.0
# the shorter windows every side file reports beside the full one
PREFIX_MARKS_S = (10, 20, 30, 40, 51)


def window_rate(units_per_step, steps, t0, t1):
    """Units per second over the measured window: all the work, all the
    time.  No trimming, no best-of."""
    if steps <= 0 or t1 <= t0:
        raise ValueError("empty window: %d steps in %r s" % (steps, t1 - t0))
    return units_per_step * steps / (t1 - t0)


def segment_rates(stamps, t0, units, segment_s=SEGMENT_S):
    """Units per second in each whole ``segment_s`` slice of the window, from
    the per-step stamps: ``units[i]`` of work ended at ``stamps[i]``.  The
    partial slice at the end is left out.  For the side file: a stall shows
    as one low slice."""
    if not stamps:
        return []
    n = int((stamps[-1] - t0) // segment_s)
    done = [0.0] * n
    for s, u in zip(stamps, units):
        i = int((s - t0) // segment_s)
        if i < n:
            done[i] += u
    return [d / segment_s for d in done]


def prefix_rates(stamps, t0, units, marks, skip=3):
    """What a shorter window would have read, from the stamps alone: for each
    mark (seconds after ``t0``), the work that ended after stamp ``skip`` and
    within the mark, over the time between those two stamps.  The first
    ``skip`` stamps are left out because a loop that keeps steps in flight
    stamps its first dispatches before any has finished.  ``{mark: rate}``;
    a mark more than a second beyond the last stamp is left out."""
    out = {}
    if len(stamps) <= skip + 1:
        return out
    base, total, i = stamps[skip], 0.0, skip + 1
    for mark in sorted(marks):
        while i < len(stamps) and stamps[i] - t0 <= mark:
            total += units[i]
            i += 1
        if i == len(stamps) and stamps[-1] - t0 < mark - 1.0:
            break
        if i > skip + 1:
            out[mark] = total / (stamps[i - 1] - base)
    return out


def longest_step(stamps, t0):
    """``(seconds, index)`` of the longest interval between two consecutive
    stamps (the first is measured from ``t0``)."""
    prev, worst, at = t0, 0.0, -1
    for i, s in enumerate(stamps):
        if s - prev > worst:
            worst, at = s - prev, i
        prev = s
    return worst, at


def weighted_percentile(values, weights, q):
    """The smallest value v such that at least ``q`` of the total weight lies
    at or below v.  ``weights[i]`` requests each saw ``values[i]``."""
    pairs = sorted((v, w) for v, w in zip(values, weights) if w > 0)
    total = sum(w for _, w in pairs)
    if total <= 0:
        raise ValueError("no samples")
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= q * total:
            return v
    return pairs[-1][0]


def gaps(stamps, t0, active_before):
    """Inter-token gaps of a tick loop: every slot that was active before
    tick i receives one token in it, so tick i's interval is one gap for
    ``active_before[i]`` requests.  Returns ``(values_s, weights)``."""
    values, prev = [], t0
    for s in stamps:
        values.append(s - prev)
        prev = s
    return values, list(active_before)


def histogram(values, weights, edges):
    """Weight in each ``[edges[i], edges[i+1])`` bin, with an open last bin."""
    out = [0.0] * len(edges)
    for v, w in zip(values, weights):
        i = 0
        while i + 1 < len(edges) and v >= edges[i + 1]:
            i += 1
        out[i] += w
    return out


def spread(values):
    """The contract's spread: interquartile distance over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
