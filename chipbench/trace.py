"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the ``breakdown`` read.  Pure functions of lists of
``(name, start_ns, duration_ns)``; the tests hold them to a recorded trace
and to hand-made ones.

Layout of a TPU trace (PERF.md §6, PR 19): one plane ``/device:TPU:<i>`` per
chip with the lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per operation); the host's
threads are lines of ``/host:CPU``, where a ``TraceAnnotation`` shows under
its own name.  The harness marks its window ``chipbench:window`` and its
loop bodies ``chipbench:<what>``.
"""
from __future__ import annotations

import bisect
import heapq
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "chipbench:window"
MARK = "chipbench:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")


def op_name(name):
    """An ``XLA Ops`` event is named by its whole HLO line, ``%fusion.3 =
    bf16[8,1024]{...} fusion(...)``: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path):
    """``{"devices": {plane: {line: [(name, start, dur)]}}, "host": [...]}``
    from an ``.xplane.pb`` file; times in ns on the trace's own clock."""
    from jax.profiler import ProfileData

    return parse(ProfileData.from_file(path))


def parse(data):
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = sorted(
                        ((op_name(e.name), e.start_ns, e.duration_ns)
                         for e in line.events), key=lambda x: x[1])
            if lines.get(OPS_LINE):
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(MARK))
    host.sort(key=lambda x: x[1])
    return {"devices": devices, "host": host}


# -- interval arithmetic ----------------------------------------------------

def merge(intervals):
    """Sorted, disjoint union of ``[(lo, hi), ...]``."""
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(a, b):
    """The part of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def spans(events):
    return [(s, s + d) for _, s, d in events]


def first_busy(trace):
    """``(starts, ends, before)`` of the first device's merged operation
    intervals, ``before[i]`` the busy ns ahead of interval i: computed once
    per trace and kept in it, so that a reader which asks about every tick
    costs the trace's events once and not once a tick."""
    if "_first_busy" not in trace:
        first = trace["devices"][sorted(trace["devices"])[0]]
        merged = merge(spans(first[OPS_LINE]))
        before, total = [], 0
        for lo, hi in merged:
            before.append(total)
            total += hi - lo
        trace["_first_busy"] = ([lo for lo, _ in merged],
                                [hi for _, hi in merged], before + [total])
    return trace["_first_busy"]


def busy_inside(trace, lo, hi):
    """ns in which the first device ran an operation inside ``[lo, hi)``."""
    starts, ends, before = first_busy(trace)
    if hi <= lo:
        return 0
    i = bisect.bisect_right(ends, lo)          # first interval ending > lo
    j = bisect.bisect_left(starts, hi)         # first interval starting >= hi
    if i >= j:
        return 0
    return (before[j] - before[i]) - max(0, lo - starts[i]) \
        - max(0, ends[j - 1] - hi)


def first_gaps(trace, lo, hi):
    """The first device's idle intervals inside ``[lo, hi)``, sorted."""
    starts, ends, _ = first_busy(trace)
    return subtract([(lo, hi)], list(zip(starts, ends)))


def event_counts(trace):
    """How much there was to reduce: events on the first device's two lines
    and harness marks on the host."""
    first = trace["devices"][sorted(trace["devices"])[0]]
    return {"ops": len(first[OPS_LINE]),
            "modules": len(first.get(MODULES_LINE, ())),
            "host_marks": len(trace["host"]),
            "devices": len(trace["devices"])}


# -- what the metrics read ----------------------------------------------------

def window_of(trace):
    """``(lo, hi)`` in ns: the harness's ``chipbench:window`` span, or, in a
    trace without one, from the first device operation to the last."""
    for name, s, d in trace["host"]:
        if name == WINDOW:
            return s, s + d
    ops = [e for lines in trace["devices"].values() for e in lines[OPS_LINE]]
    return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def busy(trace):
    """``(busy_s, window_s)``: seconds in which an operation ran on a device
    (union of the ``XLA Ops`` intervals inside the window), averaged over the
    devices that ran any, and the window's length."""
    lo, hi = window_of(trace)
    per = [length(clip(merge(spans(lines[OPS_LINE])), lo, hi))
           for lines in trace["devices"].values()]
    return sum(per) / len(per) / 1e9, (hi - lo) / 1e9


def idle_pct(trace):
    b, w = busy(trace)
    return 100.0 * (1.0 - b / w)


def module_stem(name):
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(")[0]


def module_ms(trace, pattern):
    """Durations in ms of the program runs on the first device whose module
    name matches ``pattern`` and which lie wholly inside the window."""
    lo, hi = window_of(trace)
    first = trace["devices"][sorted(trace["devices"])[0]]
    rx = re.compile(pattern)
    return [d / 1e6 for n, s, d in first.get(MODULES_LINE, ())
            if rx.search(module_stem(n)) and s >= lo and s + d <= hi]


def module_names(trace):
    first = trace["devices"][sorted(trace["devices"])[0]]
    return sorted({module_stem(n) for n, _, _ in first.get(MODULES_LINE, ())})


def median(values):
    v = sorted(values)
    n = len(v)
    if not n:
        return None
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def exposed_collective_pct(trace):
    """Share of the window in which a collective runs on a device and no
    other operation does, averaged over the devices.  None in a trace with
    no collective."""
    lo, hi = window_of(trace)
    shares, seen = [], False
    for lines in trace["devices"].values():
        ops = lines[OPS_LINE]
        coll = [e for e in ops if COLLECTIVE.match(e[0])]
        seen |= bool(coll)
        rest = merge(spans([e for e in ops if not COLLECTIVE.match(e[0])]))
        alone = subtract(merge(spans(coll)), rest)
        shares.append(length(clip(alone, lo, hi)) / (hi - lo))
    return 100.0 * sum(shares) / len(shares) if seen else None


def op_stem(name):
    """``fusion.123`` -> ``fusion``: operations of one kind share a row."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(trace, n=10):
    """``[[stem__x<count>_, seconds], ...]``: the device operations of the
    first device that took most time inside the window."""
    lo, hi = window_of(trace)
    first = trace["devices"][sorted(trace["devices"])[0]]
    total, count = {}, {}
    for name, s, d in first[OPS_LINE]:
        if s >= lo and s + d <= hi:
            k = op_stem(name)
            total[k] = total.get(k, 0.0) + d
            count[k] = count.get(k, 0) + 1
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [["%s__x%d_" % (k, count[k]), v / 1e9] for k, v in rows]


def idle_gaps(trace, n=10):
    """``[[what_the_host_was_doing, seconds], ...]``: idle time of the first
    device inside the window, by the innermost harness span that covers each
    gap's middle (``host:other`` where none does), longest first.

    One sweep: gaps and marks are both sorted by start, a gap's middle only
    moves forward, so each mark is pushed once onto a heap ordered by length
    and popped once when a middle has passed its end."""
    lo, hi = window_of(trace)
    marks = [(name, s, s + d) for name, s, d in trace["host"]
             if name != WINDOW]
    total, open_, nxt = {}, [], 0
    for a, b in first_gaps(trace, lo, hi):
        mid = 0.5 * (a + b)
        while nxt < len(marks) and marks[nxt][1] <= mid:
            name, s, e = marks[nxt]
            heapq.heappush(open_, (e - s, nxt, e, name))
            nxt += 1
        # a mark whose end the middle has passed covers no later middle;
        # one hidden under a shorter live mark is dropped when it surfaces
        while open_ and open_[0][2] <= mid:
            heapq.heappop(open_)
        who = "host:" + (open_[0][3][len(MARK):] if open_ else "other")
        total[who] = total.get(who, 0.0) + (b - a)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def host_busy_inside(trace, mark):
    """For every host span named ``mark`` inside the window: ``(span_ms,
    device_busy_ms inside it)`` on the first device."""
    lo, hi = window_of(trace)
    return [(d / 1e6, busy_inside(trace, s, s + d) / 1e6)
            for name, s, d in trace["host"]
            if name == mark and s >= lo and s + d <= hi]


def find_xplane(logdir):
    import glob
    import os

    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    return found[-1]
