"""Device time by what an instruction is (``chipbench/moves.py``) and the six
readers built on it: on hand-made times and maps whose answers are exact, on
the trace recorded on the v5e, on a program that has the instruction maps and
on one that has not (the parent of the PR that added them)."""
import json
import os

import pytest

from chipbench import manifest, moves, scopes, trace

from test_chipbench_trace import TESTDATA, _space

MAN = manifest.load_manifest()
SERVE = ["opt_serve_backlog", "mimo_serve_longshort", "falconh1_serve_chat"]
TRAIN = ["rn50_train_bs256", "opt_train_t2048", "rn50_train_dp4",
         "opt_train_t256", "opt_train_t1024"]
# metric -> (the share of moves.table it reads, better)
NEW = {"data_move_device_pct.serve": ("data_move_pct", "lower"),
       "data_move_device_pct.train": ("data_move_pct", "lower"),
       "unscoped_named_pct.serve": ("unscoped_named_pct", "higher"),
       "unscoped_named_pct.train": ("unscoped_named_pct", "higher"),
       "scope_join_found_pct.serve": ("join_found_pct", "higher"),
       "scope_join_found_pct.train": ("join_found_pct", "higher")}


def _row(scope, opcode, moves_, src=None, feeds=None, shape="f32[8]{0}",
         size=32):
    return {"scope": scope, "opcode": opcode, "shape": shape, "bytes": size,
            "moves": moves_, "src": src, "feeds": feeds,
            "n_feeds": 1 if feeds else 0}


def _maps(source="dispatched", conflicts=0):
    """A decode program of five instructions and a chunk program of one."""
    return {
        "jit__paged_decode_impl": {
            "source": source, "conflicts": conflicts, "instructions": {
                "fusion.1": _row("linear", "fusion", False),
                "reshape.2": _row("attn/kv_gather", "reshape", True,
                                  src="state.caches[0][0].scale"),
                "copy.3": _row("unscoped", "copy", True,
                               src="state.caches[0][0].scale",
                               feeds="attn/scores",
                               shape="f32[9,16,4]{2,1,0:T(8,128)S(1)}",
                               size=2304),
                "copy.4": _row("unscoped", "copy", True,
                               src="state.caches[1][1].scale",
                               feeds="attn/scores",
                               shape="f32[9,16,4]{2,1,0:T(8,128)S(1)}",
                               size=2304),
                "fusion.5": _row("unscoped", "fusion", False)}},
        "jit__chunk_impl": {
            "source": "dispatched", "conflicts": 0, "instructions": {
                "fusion.1": _row("ssm/scan", "fusion", False)}}}


# ns by (module stem, instruction): 1000 in all; fusion.9 is in no map and
# jit__keep_tok has none
TIMES = {("jit__paged_decode_impl", "fusion.1"): 400,
         ("jit__paged_decode_impl", "reshape.2"): 100,
         ("jit__paged_decode_impl", "copy.3"): 150,
         ("jit__paged_decode_impl", "copy.4"): 50,
         ("jit__paged_decode_impl", "fusion.5"): 40,
         ("jit__paged_decode_impl", "fusion.9"): 10,
         ("jit__chunk_impl", "fusion.1"): 240,
         ("jit__keep_tok", "add.1"): 10}


def test_reduce_on_hand_made_times():
    t = moves.reduce(TIMES, _maps(), 4)
    assert t["busy_s"] == pytest.approx(1e-6) and t["each"] == 4
    # moves: the scoped reshape and the two scopeless copies
    assert t["data_move_pct"] == pytest.approx(30.0)
    # unscoped: the copies, fusion.5, and what no map lists
    assert t["unscoped_pct"] == pytest.approx(26.0)
    assert t["unscoped_named_pct"] == pytest.approx(100.0 * 200 / 260)
    # all but fusion.9 and the module without a map
    assert t["join_found_pct"] == pytest.approx(98.0)
    assert t["no_map_ms"] == {"jit__keep_tok": pytest.approx(1e-5)}
    assert t["maps"]["jit__chunk_impl"] == {
        "source": "dispatched", "conflicts": 0, "instructions": 1}
    # rows by time; the copies of two layers' scale planes for one scope
    # share a row
    rows = t["rows"]
    assert [r["ms_window"] for r in rows] == sorted(
        (r["ms_window"] for r in rows), reverse=True)
    assert sum(r["ms_window"] for r in rows) == pytest.approx(1e-3)
    copies = next(r for r in rows if r["opcode"] == "copy")
    assert copies == {
        "module": "jit__paged_decode_impl", "opcode": "copy",
        "scope": "unscoped", "src": "state.caches[*][*].scale",
        "feeds": "attn/scores", "shape": "f32[9,16,4]{2,1,0:T(8,128)S(1)}",
        "bytes": 2304, "moves": True, "instructions": 2,
        "ms_window": pytest.approx(2e-4), "ms_each": pytest.approx(5e-5)}
    lost = next(r for r in rows if r["module"] == "jit__keep_tok")
    assert (lost["opcode"], lost["scope"], lost["shape"]) == (
        "add", "unscoped", None)


@pytest.mark.parametrize("source,conflicts,found", [
    ("dispatched", 0, 98.0), ("relowered", 0, 24.0), ("dispatched", 3, 24.0)])
def test_a_relowered_or_disputed_map_joins_nothing(source, conflicts, found):
    """Time counts as joined only under a map that was read off the
    dispatched executable and that no other live program disputes."""
    t = moves.reduce(TIMES, _maps(source, conflicts), None)
    assert t["join_found_pct"] == pytest.approx(found)
    # what an instruction is does not depend on it
    assert t["data_move_pct"] == pytest.approx(30.0)
    assert t["rows"][0]["ms_each"] is None


def _recorded():
    parsed = trace.load(os.path.join(TESTDATA, "small_1chip.xplane.pb"))
    first = parsed["devices"][sorted(parsed["devices"])[0]]
    names = sorted({n for n, _, _ in first[trace.OPS_LINE]})
    rows = {}
    for n in names:
        kind = trace.op_stem(n)
        if "fusion" in kind:
            rows[n] = _row("linear", "fusion", False)
        elif kind == "custom-call":
            continue                    # in no map
        else:
            rows[n] = _row("unscoped", kind, True, src="env['w']",
                           feeds="linear" if "done" in kind else None)
    return parsed, {"jit_step": {"source": "dispatched", "conflicts": 0,
                                 "instructions": rows}}


def test_table_of_the_recorded_trace(tmp_path, monkeypatch, capsys):
    from chipbench import harness

    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    parsed, maps = _recorded()
    facts = {"trace": parsed, "instruction_maps": maps, "steps": 5,
             "cell": {"name": "recorded"}}
    t = moves.table(facts)
    assert moves.table(facts) is t            # once per run
    busy_s, _ = trace.busy(parsed)
    assert t["busy_s"] == pytest.approx(busy_s, rel=1e-9)
    assert sum(r["ms_window"] for r in t["rows"]) == pytest.approx(
        busy_s * 1e3, rel=1e-9)
    # the same join as scopes.by_scope makes: the unscoped share agrees
    smap = {"jit_step": {k: v["scope"]
                         for k, v in maps["jit_step"]["instructions"].items()}}
    raw = scopes.by_scope(parsed, smap)
    assert t["unscoped_pct"] == pytest.approx(
        100.0 * raw["scopes"][scopes.UNSCOPED] / raw["busy_ns"])
    assert t["join_found_pct"] == pytest.approx(
        100.0 * raw["found_ns"] / raw["busy_ns"])
    assert 0 < t["data_move_pct"] < t["unscoped_pct"] < 100
    assert 99 < t["unscoped_named_pct"] < 100  # all but the custom-call
    assert t["each"] == 5 and t["no_map_ms"] == {}
    path = os.path.join(str(tmp_path), "moves-recorded-%d.json" % os.getpid())
    with open(path) as f:
        assert json.load(f)["rows"] == t["rows"]
    assert "device time by instruction" in capsys.readouterr().out


def _facts(per="ticks"):
    ops = [(1, 1000, 400), (4, 1400, 100)]    # fusion.1, copy.2
    parsed = _space(ops, modules=[(5, 990, 600)], host=[(7, 900, 1000)])
    maps = {"jit_step": {"source": "dispatched", "conflicts": 0,
                         "instructions": {
                             "fusion.1": _row("linear", "fusion", False),
                             "copy.2": _row("unscoped", "copy", True,
                                            feeds="linear")}}}
    return {"trace": parsed, "instruction_maps": maps, per: 2}


EXPECT = {"data_move_pct": 20.0, "unscoped_named_pct": 100.0,
          "join_found_pct": 100.0}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_on_hand_made_facts(metric):
    facts = _facts("ticks" if metric.endswith(".serve") else "steps")
    got = manifest.load_reader(metric)(facts)
    assert got == pytest.approx(EXPECT[NEW[metric][0]])
    assert facts["_moves_table"]["each"] == 2


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_gives_none_for_a_program_without_instruction_maps(
        metric, monkeypatch):
    """The parent of the PR that added them: ``obs.programs`` has no
    ``instruction_maps``.  No reader raises, every line is left out."""
    from mxnet_tpu import obs

    monkeypatch.delattr(type(obs.programs), "instruction_maps")
    assert moves.program_maps() == (None, None)
    facts = _facts()
    facts["instruction_maps"] = None
    assert manifest.load_reader(metric)(facts) is None
    # and with no trace of a device at all
    assert manifest.load_reader(metric)(
        {"trace": {"devices": {}, "host": []},
         "instruction_maps": _facts()["instruction_maps"]}) is None


def test_the_running_programs_maps_are_read_through_one_listener(
        monkeypatch):
    """``moves.program_maps`` counts backend compiles through
    ``scopes.program_maps``' listener and registers none of its own."""
    import jax

    from mxnet_tpu import obs

    registered = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    monkeypatch.setitem(scopes._compiles, "listening", False)
    maps, compiles = moves.program_maps()
    assert maps == obs.programs.instruction_maps() and compiles == 0
    scopes.program_maps()
    moves.program_maps()
    # (the program's own reader may register its listener meanwhile)
    assert [f for f in registered if f.__module__.startswith("chipbench")] \
        == [scopes._on_compile]


def test_the_six_entries_are_in_the_manifest():
    assert manifest.validate(MAN) == []
    tail = MAN["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == [
        "data_move_device_pct.serve", "data_move_device_pct.train",
        "unscoped_named_pct.serve", "unscoped_named_pct.train",
        "scope_join_found_pct.serve", "scope_join_found_pct.train"]
    for m in tail:
        serve = m["name"].endswith(".serve")
        assert m == {"name": m["name"], "unit": "%",
                     "better": NEW[m["name"]][1], "source": "device_trace",
                     "layer": "device",
                     "moves": "serve_out_tokens_per_s" if serve
                     else "train_samples_per_s",
                     "workloads": SERVE if serve else TRAIN}
        assert os.path.exists(os.path.join(
            manifest.ROOT, manifest.reader_path(m["name"])))
    for cell in SERVE + TRAIN:
        names = [m["name"] for m in manifest.load_cell(cell)["per_layer"]]
        want = ".serve" if cell in SERVE else ".train"
        assert [n for n in names if n.split(".")[0] + want in NEW
                and n in NEW] == [n for n in NEW if n.endswith(want)]
