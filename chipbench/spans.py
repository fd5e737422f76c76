"""Host phases on the device trace's clock.

The program stamps its spans (``mxnet_tpu.obs.timeline``: ``serve.tick``
and its children, ``fit_step`` and its children) with
``time.perf_counter_ns()``; the device trace runs on the profiler's clock.
The two share pairs of events: the harness's ``chipbench:serve_tick`` span
is opened and closed around ``serve_tick()``, so it holds the program's
``serve.tick``; ``chipbench:fit_step`` is opened from inside the
``batch_end_callback``, so that span of the program holds the mark's
opening.  Each pair bounds the offset between the clocks from above and
from below; over a window's pairs the tightest bounds leave an interval,
whose middle is the offset and whose half-width is what the pairing cannot
see (the residual).  Where the bounds contradict each other, or leave more
than 50 us of play, the readers give None.

With the offset the first device's idle time is split over the innermost
program span open at each moment (``trace.idle_gaps`` gives a whole gap to
the harness mark over its middle; one gap here runs from ``serve.readback``
through ``serve.deliver`` into the next tick's ``serve.decode_dispatch``).
A program without these spans (the parent of the PR that added them) pairs
nothing, and every reader here gives None.
"""
from __future__ import annotations

from . import trace

TOLERANCE_NS = 50_000
RING_NS = 1000            # the ring keeps whole microseconds
RESET = "step_stats_reset"
# how the harness's mark and a span of the program hold one another:
# serving, the mark is opened and closed around serve_tick(), so it holds
# the program's serve.tick; training, the mark is opened from inside the
# batch_end_callback, so that span holds the mark's opening
LOOPS = {
    "serve": {"mark": "chipbench:serve_tick", "top": "serve.tick",
              "bracket": "serve.tick", "mark_holds_span": True},
    "train": {"mark": "chipbench:fit_step", "top": "fit_step",
              "bracket": "batch_end_callback", "mark_holds_span": False},
}


def program_events():
    """The program's timeline ring, oldest first."""
    from mxnet_tpu import obs

    return obs.timeline.events()


def offset_ns(lowers, uppers):
    """``(offset, residual)`` in ns, ``program clock - trace clock``, from
    the bounds the pairs put on it: the middle of ``[max(lowers),
    min(uppers)]`` and half its width.  None with no pair on either side,
    where the bounds contradict each other (the pairing is wrong), or where
    they leave more than 50 us of play."""
    if not lowers or not uppers:
        return None
    lo, hi = max(lowers), min(uppers)
    if hi < lo or hi - lo > 2 * TOLERANCE_NS:
        return None
    return 0.5 * (lo + hi), 0.5 * (hi - lo)


def spans_of(events):
    """``[(name, t0_ns, t1_ns, args)]`` of the ring's complete events,
    sorted by start; the ring keeps whole microseconds."""
    out = [(e["name"], RING_NS * e["ts"], RING_NS * (e["ts"] + e["dur"]),
            e.get("args") or {}) for e in events if e.get("ph") == "X"]
    out.sort(key=lambda x: (x[1], -x[2]))
    return out


def window_pairs(parsed, events, loop):
    """``(marks, brackets, tops)``: the harness's marks inside the window
    ``[(open, close)]`` in trace ns, the program's bracketing spans paired
    with them by position, and the program's top spans of the window, both
    in program ns.  Serving: the ring's last spans are the window's (nothing
    ticks after it).  Training: what the ring holds from its last
    ``step_stats_reset`` instant on, which the harness's call to
    ``profiler.reset_step_stats()`` leaves as it opens the window (the
    callback that made the call is still open then, so it counts)."""
    how = LOOPS[loop]
    lo, hi = trace.window_of(parsed)
    marks = [(s, s + d) for name, s, d in parsed["host"]
             if name == how["mark"] and s >= lo and s + d <= hi]
    every = spans_of(events)
    tops = [sp for sp in every if sp[0] == how["top"]]
    brackets = [sp for sp in every if sp[0] == how["bracket"]]
    if how["mark_holds_span"]:
        tops = brackets = tops[-len(marks):] if marks else []
    else:
        resets = [RING_NS * e["ts"] for e in events
                  if e.get("ph") == "i" and e["name"] == RESET]
        if not resets:
            return marks, [], []
        tops = [sp for sp in tops if sp[1] >= resets[-1]]
        brackets = [sp for sp in brackets if sp[2] >= resets[-1]]
    return marks, brackets, tops


def aligned(facts, loop):
    """``{"offset", "residual", "tops": [(name, t0, t1, args)], "spans":
    [...]}`` with every time in trace ns: the window's top spans and all
    the program's spans that lie inside one of them.  None where the clocks
    cannot be paired.  Kept in ``facts`` once computed."""
    key = "_aligned_" + loop
    if key in facts:
        return facts[key]
    facts[key] = out = None
    parsed = facts.get("trace")
    events = facts.get("program_events")
    if events is None:
        events = program_events()
    if not parsed or not parsed.get("host"):
        return None
    marks, brackets, tops = window_pairs(parsed, events, loop)
    pairs = list(zip(marks, brackets))
    if LOOPS[loop]["mark_holds_span"]:
        # mark open <= span open, span close <= mark close
        uppers = [sp[1] + RING_NS - m[0] for m, sp in pairs]
        lowers = [sp[2] - m[1] for m, sp in pairs]
    else:
        # span open <= mark open <= span close
        lowers = [sp[1] - m[0] for m, sp in pairs]
        uppers = [sp[2] + RING_NS - m[0] for m, sp in pairs]
    found = offset_ns(lowers, uppers)
    if found is None:
        return None
    off, residual = found
    shift = lambda sp: (sp[0], sp[1] - off, sp[2] - off, sp[3])
    wlo, whi = trace.window_of(parsed)
    # a top span that outlives the window (the iteration that closes it,
    # and what the loop runs after) is not the window's
    tops = [t for t in map(shift, tops) if wlo <= t[1] and t[2] <= whi]
    if not tops:
        return None
    lo, hi = tops[0][1], tops[-1][2]
    inner = [sp for sp in map(shift, spans_of(events))
             if sp[0] != LOOPS[loop]["top"] and lo <= sp[1] and sp[2] <= hi]
    facts[key] = out = {"offset": off, "residual": residual, "tops": tops,
                        "spans": inner, "pairs": len(pairs)}
    _publish(facts, loop, out)
    return out


def children(al, names):
    """``{top index: [(name, t0, t1, args)]}``: the aligned spans named in
    ``names``, by the top span that contains them."""
    out = {i: [] for i in range(len(al["tops"]))}
    i = 0
    for sp in al["spans"]:
        if sp[0] not in names:
            continue
        while i < len(al["tops"]) and al["tops"][i][2] < sp[2]:
            i += 1
        if i == len(al["tops"]):
            break
        if al["tops"][i][1] <= sp[1]:
            out[i].append(sp)
    return out


def device_busy_inside(parsed, intervals):
    """ns of the first device's operation time inside the disjoint, sorted
    ``intervals`` ``[(lo, hi)]``."""
    return sum(trace.busy_inside(parsed, a, b) for a, b in intervals)


def innermost(spans):
    """Disjoint ``[(name, lo, hi)]``, sorted: for spans sorted by start that
    nest (one thread's), the stretches in which each is the innermost one
    open."""
    out, stack, t = [], [], None

    def advance(to):
        nonlocal t
        if stack and to > t:
            out.append((stack[-1][0], t, to))
        t = to if t is None else max(t, to)

    for name, s, e, _ in spans:
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


def idle_by_span(facts, loop):
    """``{span name or None: ns}``: idle time of the first device inside
    the window, split over the innermost program span open at each moment
    (the loop's top span where none of its children is; None outside every
    top span)."""
    key = "_idle_" + loop
    if key in facts:
        return facts[key]
    al = aligned(facts, loop)
    if al is None or not facts["trace"].get("devices"):
        return None
    parsed = facts["trace"]
    lo, hi = trace.window_of(parsed)
    gaps = trace.first_gaps(parsed, lo, hi)
    every = sorted(al["tops"] + al["spans"], key=lambda x: (x[1], -x[2]))
    out, named, j = {}, 0, 0
    for name, a, b in innermost(every):       # both lists sorted, disjoint
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        k, ns = j, 0
        while k < len(gaps) and gaps[k][0] < b:
            ns += min(gaps[k][1], b) - max(gaps[k][0], a)
            k += 1
        if ns:
            out[name] = out.get(name, 0) + ns
            named += ns
    out[None] = trace.length(gaps) - named
    facts[key] = out
    return out


def idle_named_pct(facts, loop):
    """Share of the window's device idle time that lies under a child of
    the loop's top span."""
    by = idle_by_span(facts, loop)
    if not by:
        return None
    total = sum(by.values())
    own = by.get(None, 0) + by.get(LOOPS[loop]["top"], 0)
    return 100.0 * (total - own) / total if total else None


def _publish(facts, loop, al):
    """Print the clocks' offset and where the device's idle time lay, and
    write both with each child span's mean length to
    ``chipbench/out/spans-<cell>-<pid>.json``."""
    import json
    import os

    from . import harness

    n = len(al["tops"])
    mean_us, count = {}, {}
    for name, s, e, _ in al["spans"]:
        mean_us[name] = mean_us.get(name, 0.0) + (e - s) / 1e3 / n
        count[name] = count.get(name, 0) + 1
    idle = idle_by_span(facts, loop) or {}
    out = {"loop": loop, "offset_ns": al["offset"],
           "residual_ns": al["residual"], "pairs": al["pairs"], "tops": n,
           "top_mean_us": sum(t[2] - t[1] for t in al["tops"]) / 1e3 / n,
           "span_us_per_top": mean_us, "span_count": count,
           "idle_us_per_top": {str(k): v / 1e3 / n
                               for k, v in idle.items()}}
    print("clock offset (%s): %.0f ns +- %.0f ns over %d pairs; device idle "
          "by program span, us per %s: %s"
          % (loop, al["offset"], al["residual"], al["pairs"],
             LOOPS[loop]["top"], json.dumps(
                 {k: round(v, 1) for k, v in out["idle_us_per_top"].items()})),
          flush=True)
    name = facts.get("cell", {}).get("name")
    if name is not None:
        os.makedirs(harness.OUT_DIR, exist_ok=True)
        with open(os.path.join(harness.OUT_DIR, "spans-%s-%d.json"
                               % (name, os.getpid())), "w") as f:
            json.dump(out, f, indent=1)
