"""The eight readers of the program's account of its start and compiles
(``chipbench/startup.py``) on a hand-made registry snapshot, and their
entries in the manifest."""
import os

import pytest

from chipbench import manifest, startup

MAN = manifest.load_manifest()
NEW = {"setup_import_s": ("s", "lower"), "setup_build_s": ("s", "lower"),
       "setup_outside_s": ("s", "lower"),
       "compile_trace_lower_s": ("s", "lower"),
       "compile_cache_read_s": ("s", "lower"),
       "compile_miss_s": ("s", "lower"),
       "compiles_per_program": ("1", "lower"),
       "compile_named_pct": ("%", "higher")}


def fam(kind, names, rows):
    return {"type": kind, "help": "", "label_names": list(names),
            "series": [{"labels": dict(zip(names, labels)), "value": v}
                       for labels, v in rows]}


SNAPSHOT = {
    "mx_setup_seconds": fam("gauge", ("phase",), [
        (("before_import",), 0.25), (("import.self",), 1.5),
        (("import.jax",), 4.0), (("import.pallas",), 1.25),
        (("build.symbol",), 0.5), (("build.predictor",), 2.0),
        (("build.server",), 0.125), (("build.serve_open",), 0.375),
        (("until_loop",), 20.0), (("compile",), 1.0),
        (("outside",), 10.5), (("after_loop",), 1.25)]),
    "mx_compile_seconds": fam("counter", ("program", "stage"), [
        (("prefill", "trace"), 2.0), (("prefill", "lower"), 1.0),
        (("prefill", "cache_read"), 0.5),
        (("paged_decode_step", "trace"), 3.0),
        (("paged_decode_step", "lower"), 1.5),
        (("paged_decode_step", "compile"), 8.0),
        (("(eager)", "trace"), 0.25), (("(eager)", "lower"), 0.25),
        (("(eager)", "cache_read"), 0.125), (("(eager)", "compile"), 0.375),
        (("(outside)", "trace"), 7.0), (("(outside)", "lower"), 5.0),
        (("(outside)", "compile"), 100.0),
        (("(outside)", "cache_read"), 9.0)]),
    "mx_compiles_total": fam("counter", ("program", "cache"), [
        (("prefill", "hit"), 2.0), (("paged_decode_step", "hit"), 1.0),
        (("paged_decode_step", "miss"), 2.0), (("(eager)", "hit"), 30.0),
        (("(outside)", "miss"), 4.0)]),
    "mx_serve_steps": fam("counter", (), [((), 12.0)]),
}
# by hand: 1.5 + 4 + 1.25; 0.5 + 2 + 0.125 + 0.375; the named and eager
# traces and lowerings 2 + 1 + 3 + 1.5 + 0.25 + 0.25; reads 0.5 + 0.125;
# misses 8 + 0.375; (2 + 1 + 2) compiles over 2 programs; named 16 of the
# program's 17 seconds
WANT = {"setup_import_s": 6.75, "setup_build_s": 3.0,
        "setup_outside_s": 10.5, "compile_trace_lower_s": 8.0,
        "compile_cache_read_s": 0.625, "compile_miss_s": 8.375,
        "compiles_per_program": 2.5,
        "compile_named_pct": 100.0 * 16.0 / 17.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_a_hand_made_snapshot(name):
    read = manifest.load_reader(name)
    assert read({"registry": SNAPSHOT}) == pytest.approx(WANT[name],
                                                         abs=1e-12)
    # a program without the families (the parent) reads 0.0, never None
    for empty in ({}, {"mx_serve_steps": SNAPSHOT["mx_serve_steps"]}):
        got = read({"registry": empty})
        assert got == 0.0 and isinstance(got, float)


def test_readers_take_one_snapshot_of_the_live_registry():
    from mxnet_tpu import obs

    facts = {}
    assert startup.setup_seconds(facts, "import") > 0       # this process's
    assert facts["registry"]["mx_setup_seconds"]["type"] == "gauge"
    obs.registry.gauge("mx_setup_seconds", labels=("phase",)).labels(
        phase="import.test_later").inc(5.0)
    try:
        # the eight read one reading: what is booked later is not in it
        assert startup.setup_seconds(facts, "import.test_later") == 0.0
        assert startup.setup_seconds({}, "import.test_later") == 5.0
    finally:
        obs.registry.get("mx_setup_seconds").reset_series("import.test_later")


def test_the_eight_entries_are_in_the_manifest():
    assert manifest.validate(MAN) == []
    tail = MAN["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        unit, better = NEW[m["name"]]
        # compile_s's form: no list of cells, so every cell reports them
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_counter",
                     "layer": "compile / caches", "moves": "setup_s"}
        assert os.path.exists(os.path.join(
            manifest.ROOT, manifest.reader_path(m["name"])))
    for cell in MAN["workloads"]:
        names = [m["name"] for m in manifest.load_cell(cell["name"])[
            "per_layer"]]
        assert names[-len(NEW):] == list(NEW), cell["name"]
