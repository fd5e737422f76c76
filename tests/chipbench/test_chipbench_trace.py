"""The trace reduction on hand-made traces (exact answers) and on a small
trace recorded on the v5e (``chipbench/testdata``, made by
``record_small.py`` there)."""
import os

import pytest
from jax.profiler import ProfileData

from chipbench import manifest, trace

TESTDATA = os.path.join(manifest.ROOT, "chipbench", "testdata")


def _ops(events, base=1000):
    """text-proto lines for ``[(metadata_id, start_ns, dur_ns)]``."""
    return " ".join(
        "events { metadata_id: %d offset_ps: %d duration_ps: %d }"
        % (m, (s - base) * 1000, d * 1000) for m, s, d in events)


def _space(device_ops, modules=(), host=(), second_device_ops=None):
    names = {1: "%fusion.1 = bf16[8,128]{1,0} fusion(%p)", 2: "%fusion.7 = x",
             3: "%all-reduce.3 = f32[4] all-reduce(%g)",
             4: "%copy.2 = y", 5: "jit_step(123)", 6: "jit__chunk_impl(9)",
             7: "chipbench:window", 8: "chipbench:fit_step",
             9: "chipbench:serve_tick", 10: "not ours"}
    meta = " ".join(
        'event_metadata { key: %d value { id: %d name: "%s" } }'
        % (k, k, v) for k, v in names.items())

    def plane(name, ops, mods):
        return ('planes { name: "%s" lines { name: "XLA Ops" timestamp_ns: '
                '1000 %s } lines { name: "XLA Modules" timestamp_ns: 1000 %s '
                '} %s }' % (name, _ops(ops), _ops(mods), meta))

    txt = plane("/device:TPU:0", device_ops, modules)
    if second_device_ops is not None:
        txt += plane("/device:TPU:1", second_device_ops, ())
    txt += ('planes { name: "/host:CPU" lines { name: "python3" timestamp_ns:'
            ' 1000 %s } %s }' % (_ops(host), meta))
    return trace.parse(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt)))


# window 2000..12000 ns; ops: fusion 2000-5000, fusion 4000-6000 (overlap),
# all-reduce 7000-9000 alone, copy 8500-9500 (overlaps the collective's tail)
OPS = [(1, 2000, 3000), (2, 4000, 2000), (3, 7000, 2000), (4, 8500, 1000)]
HOST = [(7, 2000, 10000), (8, 2000, 5000), (8, 7000, 5000), (10, 100, 5)]
MODS = [(5, 2000, 4000), (5, 7000, 2500), (6, 9600, 400), (5, 11000, 5000)]


def test_interval_arithmetic():
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.length(trace.merge([(1, 3), (2, 4)])) == 3
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.clip([(0, 5), (8, 12)], 4, 9) == [(4, 5), (8, 9)]


def test_op_and_module_names():
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(%a, %b)") == \
        "fusion.12"
    assert trace.op_stem("fusion.12") == "fusion"
    assert trace.op_stem("all-reduce-start.3") == "all-reduce-start"
    assert trace.module_stem("jit_step(1234)") == "jit_step"


def test_busy_union_and_idle_share():
    t = _space(OPS, MODS, HOST)
    assert trace.window_of(t) == (2000, 12000)
    busy_s, window_s = trace.busy(t)
    # union: 2000-6000 and 7000-9500 = 6500 ns of a 10000 ns window
    assert busy_s == pytest.approx(6500e-9) and \
        window_s == pytest.approx(10000e-9)
    assert trace.idle_pct(t) == pytest.approx(35.0)


def test_busy_is_averaged_over_the_devices_that_ran():
    t = _space(OPS, MODS, HOST, second_device_ops=[(1, 2000, 1500)])
    busy_s, _ = trace.busy(t)
    assert busy_s == pytest.approx((6500e-9 + 1500e-9) / 2)


def test_window_without_a_mark_is_first_to_last_operation():
    t = _space(OPS)
    assert trace.window_of(t) == (2000, 9500)


def test_per_module_time_counts_runs_wholly_inside_the_window():
    t = _space(OPS, MODS, HOST)
    # the run that starts at 11000 ends after the window: left out
    assert trace.module_ms(t, r"^jit_step$") == [pytest.approx(4000e-6),
                                                 pytest.approx(2500e-6)]
    assert trace.module_ms(t, r"chunk_impl") == [pytest.approx(400e-6)]
    assert trace.median(trace.module_ms(t, r"^jit_step$")) == \
        pytest.approx(3250e-6)
    assert trace.median([]) is None
    assert trace.module_names(t) == ["jit__chunk_impl", "jit_step"]


def test_exposed_collective_time():
    t = _space(OPS, MODS, HOST)
    # all-reduce 7000-9000, another op runs 8500-9500: 1500 ns exposed
    assert trace.exposed_collective_pct(t) == pytest.approx(15.0)
    assert trace.exposed_collective_pct(_space(OPS[:2], MODS, HOST)) is None


def test_top_ops_group_by_stem():
    rows = trace.top_ops(_space(OPS, MODS, HOST))
    assert rows[0] == ["fusion__x2_", pytest.approx(5000e-9)]
    assert rows[1] == ["all-reduce__x1_", pytest.approx(2000e-9)]
    assert len(trace.top_ops(_space(OPS, MODS, HOST), n=1)) == 1


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    rows = dict(trace.idle_gaps(_space(OPS, MODS, HOST)))
    # gaps: 6000-7000 (middle 6500: inside the first fit_step) and
    # 9500-12000 (middle 10750: inside the second)
    assert rows == {"host:fit_step": pytest.approx(3500e-9)}
    rows = dict(trace.idle_gaps(_space(OPS, MODS, HOST[:1])))
    assert rows == {"host:other": pytest.approx(3500e-9)}


def test_host_span_less_device_busy_inside_it():
    host = [(7, 2000, 10000), (9, 2000, 5000), (9, 7000, 5000)]
    rows = trace.host_busy_inside(_space(OPS, MODS, host),
                                  "chipbench:serve_tick")
    # first tick 2000-7000: busy 2000-6000; second 7000-12000: 7000-9500
    assert rows == [(pytest.approx(5000e-6), pytest.approx(4000e-6)),
                    (pytest.approx(5000e-6), pytest.approx(2500e-6))]


@pytest.mark.parametrize("chips", [1, 4])
def test_recorded_trace(chips):
    path = os.path.join(TESTDATA, "small_%dchip.xplane.pb" % chips)
    if not os.path.exists(path):
        pytest.skip("no %d-chip trace was recorded" % chips)
    t = trace.load(path)
    assert len(t["devices"]) == chips
    assert "jit_step" in trace.module_names(t)
    # five marked steps; a run that straddles the window's edge (the host's
    # and the chip's clocks agree to microseconds, not better) is left out
    assert len(trace.module_ms(t, r"^jit_step$")) in (4, 5)
    busy_s, window_s = trace.busy(t)
    assert 0 < busy_s < window_s
    assert 0 < trace.idle_pct(t) < 100
    # the breakdown is of the first chip alone
    first = {"devices": dict(sorted(t["devices"].items())[:1]),
             "host": t["host"]}
    first_busy_s = trace.busy(first)[0]
    rows = trace.top_ops(t)
    assert rows and all(" = " not in name for name, _ in rows)
    assert sum(s for _, s in rows) <= first_busy_s * 1.0001
    gaps = dict(trace.idle_gaps(t))
    assert "host:fit_step" in gaps
    assert sum(gaps.values()) == pytest.approx(window_s - first_busy_s,
                                               rel=1e-6)
    exposed = trace.exposed_collective_pct(t)
    assert (exposed is None) == (chips == 1)
    if exposed is not None:
        assert 0 <= exposed < 100


# -- the sweeps against the loops they replaced ---------------------------------

def _idle_gaps_by_loops(t, n=10):
    """``trace.idle_gaps`` as it was: every gap against every mark."""
    lo, hi = trace.window_of(t)
    first = t["devices"][sorted(t["devices"])[0]]
    gaps = trace.subtract([(lo, hi)],
                          trace.merge(trace.spans(first[trace.OPS_LINE])))
    marks = [(name, s, s + d) for name, s, d in t["host"]
             if name != trace.WINDOW]
    total = {}
    for a, b in gaps:
        mid, best = 0.5 * (a + b), None
        for name, s, e in marks:
            if s <= mid < e and (best is None or e - s < best[1]):
                best = (name, e - s)
        who = "host:" + (best[0][len(trace.MARK):] if best else "other")
        total[who] = total.get(who, 0.0) + (b - a)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def _busy_inside_by_loops(t, intervals):
    """``spans.device_busy_inside`` as it was: the first device's merged
    operations clipped against each interval in turn."""
    first = t["devices"][sorted(t["devices"])[0]]
    ops = trace.merge(trace.spans(first[trace.OPS_LINE]))
    return [trace.length(trace.clip(ops, a, b)) for a, b in intervals]


def _recorded():
    out = []
    for chips in (1, 4):
        path = os.path.join(TESTDATA, "small_%dchip.xplane.pb" % chips)
        if os.path.exists(path):
            out.append(trace.load(path))
    return out


def _nested_marks():
    """Marks that nest, overlap and tie in length, and gaps whose middles
    fall on every kind of boundary between them."""
    ops = [(1, 2000 + 100 * i, 40 + (i % 7)) for i in range(95)]
    host = [(7, 2000, 10000), (8, 2000, 5000), (9, 2500, 1000),
            (9, 2600, 300), (8, 2600, 300), (9, 6000, 2000),
            (8, 6500, 1500), (9, 7900, 100), (8, 9000, 3000)]
    return _space(ops, MODS, host)


def test_the_sweeps_give_the_loops_answers_exactly():
    traces = _recorded() + [_space(OPS, MODS, HOST),
                            _space(OPS, MODS, HOST[:1]), _nested_marks()]
    assert len(traces) >= 4
    for t in traces:
        assert trace.idle_gaps(t) == _idle_gaps_by_loops(t)
        assert trace.idle_gaps(t, n=1) == _idle_gaps_by_loops(t, n=1)
        lo, hi = (int(x) for x in trace.window_of(t))
        step = max(1, (hi - lo) // 37)
        cuts = [(a, a + w) for a in range(lo - step, hi + step, step)
                for w in (1, step // 3, step, 5 * step)]
        assert [trace.busy_inside(t, a, b) for a, b in cuts] == \
            _busy_inside_by_loops(t, cuts)
        from chipbench import spans
        assert spans.device_busy_inside(t, cuts) == \
            sum(_busy_inside_by_loops(t, cuts))
        starts, ends, before = trace.first_busy(t)
        assert before[-1] == sum(e - s for s, e in zip(starts, ends))
        assert trace.first_gaps(t, lo, hi) == trace.subtract(
            [(lo, hi)], list(zip(starts, ends)))


# what the parent's loops (commit a017abf) read off the recorded traces
RECORDED = {
    1: ([["host:fit_step", 0.007730682]],
        [(3.695629, 0.029284), (1.164899, 0.014794), (1.05721, 0.014392),
         (0.99419, 0.014507), (0.85757, 0.0)]),
    4: ([["host:fit_step", 0.006816071]],
        [(1.80026, 0.054863), (1.44086, 0.054823), (1.26307, 0.054939),
         (1.32745, 0.054964), (1.17504, 0.0)]),
}


@pytest.mark.parametrize("chips", [1, 4])
def test_the_recorded_traces_breakdown_is_what_it_was(chips):
    """Digit for digit what the loops gave before the sweeps replaced
    them."""
    t = trace.load(os.path.join(TESTDATA, "small_%dchip.xplane.pb" % chips))
    assert trace.idle_gaps(t) == RECORDED[chips][0]
    assert trace.host_busy_inside(t, "chipbench:fit_step") == \
        RECORDED[chips][1]


def _synthetic(n):
    """``n`` operations with a gap after each, under ``n`` marks that nest
    two deep: what a 30-s window of a 1.4-ms tick would hold."""
    ops = [("fusion.%d" % (i % 50), 1000 * i, 600) for i in range(n)]
    host = [("chipbench:window", 0, 1000 * n)]
    for i in range(n // 2):
        host.append(("chipbench:serve_tick", 2000 * i, 2000))
        host.append(("chipbench:inner", 2000 * i + 500, 1000))
    host.sort(key=lambda x: x[1])
    return {"devices": {"/device:TPU:0": {
        trace.OPS_LINE: ops, trace.MODULES_LINE: []}}, "host": host}


def test_twenty_thousand_gaps_and_marks_reduce_in_seconds():
    """Gaps x marks took 15 s here at this size (and a 7 x faster tick made
    the traced serving run 7 x this large, PERF.md section 7 (a)); one sweep
    takes 0.1 s.  The limit is generous: it catches a loop, not a slow
    machine."""
    import time

    n = 20000
    t = _synthetic(n)
    began = time.perf_counter()
    rows = dict(trace.idle_gaps(t))
    ticks = trace.host_busy_inside(t, "chipbench:serve_tick")
    took = time.perf_counter() - began
    assert took < 10.0, took
    # a gap's middle lies at 800 of every 1000 ns: the even ones inside an
    # inner mark (500..1500 of 2000), the odd ones under the tick alone
    assert rows == {"host:inner": pytest.approx(n / 2 * 400e-9),
                    "host:serve_tick": pytest.approx(n / 2 * 400e-9)}
    assert len(ticks) == n // 2 and ticks[7] == (
        pytest.approx(2000e-6), pytest.approx(1200e-6))
    small = _synthetic(400)
    assert trace.idle_gaps(small) == _idle_gaps_by_loops(small)
