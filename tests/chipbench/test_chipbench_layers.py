"""Device time by layer (``chipbench/scopes.py``), the program's spans on the
trace's clock (``chipbench/spans.py``), and the twelve readers built on them:
on the trace recorded on the v5e with a hand-made scope map, and on hand-made
traces and rings whose answers are exact."""
import os

import pytest

from chipbench import manifest, scopes, spans, trace

from test_chipbench_trace import TESTDATA, _space

MAN = manifest.load_manifest()
NEW = ["attn_device_pct.train", "attn_device_pct.serve",
       "norm_device_pct.train", "head_loss_device_pct.train",
       "optimizer_device_pct.train", "unscoped_device_pct.train",
       "unscoped_device_pct.serve", "fit_dispatch_host_ms",
       "serve_dispatch_host_ms", "serve_deliver_host_ms",
       "idle_named_pct.serve", "idle_named_pct.train"]


def test_manifest_still_validates_with_the_new_metrics():
    assert manifest.validate(MAN) == []
    names = [m["name"] for m in MAN["per_layer"]]
    assert names[-len(NEW):] == NEW       # appended, in the issue's order
    for m in MAN["per_layer"][-len(NEW):]:
        assert m["unit"] == ("ms" if m["name"].endswith("_ms") else "%")
        assert m["source"] == ("program_span" if "host_ms" in m["name"]
                               or "idle_named" in m["name"]
                               else "device_trace")


# -- (d) the join, on the recorded trace --------------------------------------

def test_layers_of_the_recorded_trace_sum_to_its_busy_time():
    parsed = trace.load(os.path.join(TESTDATA, "small_1chip.xplane.pb"))
    first = parsed["devices"][sorted(parsed["devices"])[0]]
    stems = trace.module_names(parsed)
    assert stems == ["jit_step"]
    names = sorted({n for n, _, _ in first[trace.OPS_LINE]})
    # a hand-made map: fusions are the matmul chain, the rest of the names
    # but one are listed as unscoped, and one is not in the map at all
    missing = [n for n in names if not n.startswith("fusion")][0]
    smap = {n: ("linear" if n.startswith("fusion") else scopes.UNSCOPED)
            for n in names if n != missing}
    raw = scopes.by_scope(parsed, {"jit_step": smap})
    busy_s, _ = trace.busy(parsed)
    assert raw["busy_ns"] == pytest.approx(busy_s * 1e9, rel=1e-9)
    assert sum(raw["scopes"].values()) == raw["busy_ns"]
    assert set(raw["scopes"]) == {"linear", scopes.UNSCOPED}
    assert 0 < raw["found_ns"] < raw["busy_ns"]
    assert sum(raw["unscoped_kinds"].values()) == \
        raw["scopes"][scopes.UNSCOPED]
    # the same through the table the readers share
    facts = {"trace": parsed, "scope_maps": {"jit_step": smap}}
    t = scopes.table(facts)
    assert sum(t["layers"].values()) == pytest.approx(100.0)
    assert 0 < t["layers"]["linear"] < 100
    assert scopes.table(facts) is t           # once per run
    # a module without a map: all of its time is unscoped, none found
    raw = scopes.by_scope(parsed, {"jit_other": smap})
    assert raw["found_ns"] == 0
    assert raw["scopes"] == {scopes.UNSCOPED: raw["busy_ns"]}


def test_self_times_count_nested_and_overlapping_events_once():
    ev = [("while", 0, 100), ("a", 10, 40), ("b", 30, 60), ("c", 120, 130)]
    assert scopes.self_times(ev, 0, 200) == {
        "while": 10 + 40, "a": 20, "b": 30, "c": 10}
    assert scopes.self_times(ev, 35, 125) == {
        "b": 25, "while": 40, "c": 5}


# -- (e) the clocks ---------------------------------------------------------------

def test_offset_is_the_middle_of_what_the_pairs_allow():
    off = 5_000_000_000
    # program - trace is at most these (a span opened after its mark) ...
    uppers = [off + d for d in (3000, 2000, 9000, 40000, 2500)]
    # ... and at least these (the span closed before its mark did)
    lowers = [off - d for d in (700, 5000, 1000)]
    assert spans.offset_ns(lowers, uppers) == (off + 650, 1350)
    assert spans.offset_ns([], uppers) is None              # one-sided
    # pairs that disagree (paired one tick off): the bounds cross
    assert spans.offset_ns([off + 300_000_000], uppers) is None
    # bounds that agree but leave more than 50 us of play
    assert spans.offset_ns([off - 150_000], uppers) is None


def test_innermost_splits_time_over_nested_spans():
    sp = [("tick", 0, 100, {}), ("a", 10, 40, {}), ("prog", 20, 30, {}),
          ("b", 40, 90, {}), ("tick", 110, 120, {})]
    assert spans.innermost(sp) == [
        ("tick", 0, 10), ("a", 10, 20), ("prog", 20, 30), ("a", 30, 40),
        ("b", 40, 90), ("tick", 90, 100), ("tick", 110, 120)]


# -- (f) the readers, on hand-made facts ----------------------------------------------

OFFSET = 5_000_000_000            # program clock - trace clock, ns
DELAYS = (3000, 2000, 4000)       # serve: mark opens, then serve.tick
MARKS = (20_000, 50_000, 80_000)  # the harness's spans, 30 us each


def _x(name, t0, t1, args=None):
    """A ring event for the trace interval ``[t0, t1)`` ns."""
    ev = {"name": name, "ph": "X", "ts": (t0 + OFFSET) // 1000,
          "dur": (t1 - t0) // 1000, "pid": 1, "tid": 1}
    if args:
        ev["args"] = args
    return ev


def _train_facts():
    """Three steps of 30 us.  Each mark is opened 1 us before the end of a
    ``batch_end_callback`` span; the first of them, inside the callback
    that also reset the step statistics."""
    ops = [(1, 22_000, 20_000), (2, 52_000, 23_000), (3, 82_000, 10_000),
           (4, 92_000, 8_000)]
    mods = [(5, 22_000, 20_000), (5, 52_000, 23_000), (5, 82_000, 18_000)]
    host = [(7, 20_000, 100_000)] + [(8, m, 30_000) for m in MARKS]
    ring = [_x("fit_step", 5000, 21_000, {"step": 0}),       # warm-up
            _x("batch_end_callback", 14_000, 21_000),
            {"name": "step_stats_reset", "ph": "i", "s": "t",
             "ts": (15_000 + OFFSET) // 1000, "pid": 1, "tid": 1}]
    for i, m in enumerate(MARKS):
        t = m + 2000
        ring += [_x("input_wait", t, t + 1000),
                 _x("train_step", t + 2000, t + 8000),
                 _x("host_wait", t + 10_000, t + 20_000),
                 _x("batch_end_callback", t + 26_000 + (i == 2) * 1000,
                    t + 29_000),
                 _x("fit_step", t, t + 29_000, {"step": i + 1})]
    smap = {"fusion.1": "attn/scores", "fusion.7": "norm",
            "all-reduce.3": "optimizer", "copy.2": "head_loss"}
    return {"trace": _space(ops, mods, host), "program_events": ring,
            "scope_maps": {"jit_step": smap}}


def _serve_facts():
    ops = [(1, m + 5000, 15_000) for m in MARKS] + [(4, 112_000, 2_000)]
    mods = [(6, m + 5000, 15_000) for m in MARKS] + [(5, 112_000, 2_000)]
    host = [(7, 20_000, 100_000)] + [(9, m, 30_000) for m in MARKS]
    ring = [_x("serve.tick", 1000, 9000, {"tick": 1})]      # the fill
    for i, (m, d) in enumerate(zip(MARKS, DELAYS)):
        t = m + d
        ring.append(_x("serve.admit", t, t + 1000))
        if i == 0:
            ring.append(_x("serve.prefill", t + 1000, t + 2000,
                           {"rid": 9, "slot": 0, "pos": 0, "tokens": 8}))
        ring += [_x("serve.decode_dispatch", t + 2000, t + 5000),
                 _x("paged_decode_step", t + 2000, t + 4000),
                 _x("serve.readback", t + 5000, t + 20_000),
                 _x("serve.deliver", t + 20_000, t + 24_000),
                 _x("serve.tick", t, t + 26_000, {"tick": i + 2})]
    smap = {"fusion.1": "attn/kv_gather"}
    return {"trace": _space(ops, mods, host), "program_events": ring,
            "scope_maps": {"jit__chunk_impl": smap}}


# What the pairs allow.  Training: a callback opens 3, 3 and 2 us before
# its mark (and 6 for the first, long one) and closes 1 us after, which the
# ring's whole microseconds widen to 2: the offset lies in [-2, +2] us
# around the truth, and the estimate is the truth exactly.  Serving:
# serve.tick opens 2 us after its mark at the least (3 with the ring's
# rounding) and closes with it at the latest: [0, 3] us, estimate 1.5 us
# off, so the program's spans land 1.5 us early.
ALIGN = {"train": (OFFSET, 2000), "serve": (OFFSET + 1500, 1500)}

# busy: train 20+23+10+8 = 61 us; serve 3*15 + 2 = 47 us, of which the
# 2 us of jit_step's copy.2 belong to a module without a map
EXPECT = {
    "attn_device_pct.train": 100 * 20 / 61,
    "norm_device_pct.train": 100 * 23 / 61,
    "optimizer_device_pct.train": 100 * 10 / 61,
    "head_loss_device_pct.train": 100 * 8 / 61,
    "unscoped_device_pct.train": 0.0,
    "attn_device_pct.serve": 100 * 45 / 47,
    "unscoped_device_pct.serve": 100 * 2 / 47,
    # 29 us a step less 1 us of input_wait and 10 of host_wait
    "fit_dispatch_host_ms": 0.018,
    # admit 1 + dispatch 3 a tick, prefill 1 once, less the device's time
    # inside each dispatch span: the operation starts 5 us after the mark
    # and the span, 1.5 us early, ends 1.5, 0.5 and 2.5 us into it
    "serve_dispatch_host_ms": (3 * 4 + 1 - (1.5 + 0.5 + 2.5)) / 3 / 1000,
    "serve_deliver_host_ms": 0.004,
    # the device idles 2 us before the first step (under no top span), 10
    # and 7 us between steps (3 + 3 us of it under the callbacks, 1 + 1 us
    # between the top spans) and 20 us after the last operation (2 us of
    # host_wait, 2 of callback): 10 of 39 us lie under a child
    "idle_named_pct.train": 100 * 10 / 39,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_hand_made_facts(metric, capsys):
    serve = metric.endswith(".serve") or metric.startswith("serve_")
    loop = "serve" if serve else "train"
    facts = _serve_facts() if serve else _train_facts()
    got = manifest.load_reader(metric)(facts)
    if metric in EXPECT:
        assert got == pytest.approx(EXPECT[metric], abs=1e-9)
    if "idle_named" in metric:
        by = spans.idle_by_span(facts, loop)
        busy_s, window_s = trace.busy(facts["trace"])
        assert sum(by.values()) == pytest.approx((window_s - busy_s) * 1e9)
        own = by[None] + by.get(spans.LOOPS[loop]["top"], 0)
        assert got == pytest.approx(100.0 * (1 - own / sum(by.values())))
    if metric == "idle_named_pct.serve":
        # the readback spans, 1.5 us early, end 1.5, 0.5 and 2.5 us after
        # the device went idle; the deliver spans (4 us) are idle throughout
        assert by["serve.readback"] == 4500
        assert by["serve.deliver"] == 12_000
        assert by["serve.admit"] == 3000 and by["serve.prefill"] == 1000
    if "_device_pct" not in metric:
        al = spans.aligned(facts, loop)
        assert (al["offset"], al["residual"]) == ALIGN[loop]
        assert len(al["tops"]) == 3 and al["pairs"] == 3
        assert "clock offset (%s)" % loop in capsys.readouterr().out


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_none_for_a_program_without_scopes_or_spans(
        metric, monkeypatch):
    """The parent of the PR that added them: no scope maps, no
    ``serve.tick`` / ``fit_step`` in the ring.  No reader raises."""
    monkeypatch.setattr(scopes, "program_maps", lambda: (None, None))
    facts = _serve_facts()
    facts["scope_maps"] = None
    facts["program_events"] = [e for e in facts["program_events"]
                               if e["name"] == "paged_decode_step"]
    assert manifest.load_reader(metric)(facts) is None
    # and with no trace of a device at all
    assert manifest.load_reader(metric)(
        {"trace": {"devices": {}, "host": []}, "program_events": [],
         "scope_maps": None}) is None
