"""The process's account of its own start and of every compile
(``mxnet_tpu/obs/startup.py``): phases of the start in
``mx_setup_seconds{phase}``, compile stages by program in
``mx_compile_seconds{program, stage}`` / ``mx_compiles_total{program,
cache}``, both on the timeline too.  What is set once a process is tested
in a child process, the rest here."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, obs, profiler
from mxnet_tpu.obs import startup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT = "/jax/compilation_cache/cache_hits"


@pytest.fixture
def telemetry(request):
    orig = os.environ.get("MXNET_TELEMETRY")

    def set_(on):
        os.environ["MXNET_TELEMETRY"] = "1" if on else "0"
        config.refresh("MXNET_TELEMETRY")

    def fin():
        if orig is None:
            os.environ.pop("MXNET_TELEMETRY", None)
        else:
            os.environ["MXNET_TELEMETRY"] = orig
        config.refresh("MXNET_TELEMETRY")

    request.addfinalizer(fin)
    return set_


def family(name, key):
    """``{label value(s): value}`` of one family's series."""
    fam = obs.registry.snapshot().get(name, {"series": []})
    out = {}
    for row in fam["series"]:
        k = tuple(row["labels"][n] for n in key) if isinstance(key, tuple) \
            else row["labels"][key]
        out[k] = row["value"]
    return out


def setup_spans(ev=None):
    return [e for e in (ev or obs.timeline.events()) if e["cat"] == "setup"]


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def in_a_fresh_thread(fn):
    """Run ``fn`` where no interval has closed yet: what a thread's earlier
    compiles left for a parent must not reach a hand-made one."""
    out = []
    t = threading.Thread(target=lambda: out.append(fn()))
    t.start()
    t.join()
    return out[0]


# ---------------------------------------------------------------------------
# once a process: in a child
# ---------------------------------------------------------------------------
_CHILD = r"""
import json, sys
import mxnet_tpu as mx
from mxnet_tpu import obs
if sys.argv[1] == "fit":
    import numpy as np
    rng = np.random.RandomState(0)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4), name="softmax")
    it = mx.io.NDArrayIter(rng.uniform(-1, 1, (8, 8)).astype(np.float32),
                           rng.randint(0, 4, (8,)).astype(np.float32),
                           batch_size=4)
    mx.mod.Module(net, context=mx.cpu()).fit(it, num_epoch=2,
                                             optimizer="sgd")
snap = obs.registry.snapshot()
rows = lambda n: [[r["labels"], r["value"]] for r in snap[n]["series"]] \
    if n in snap else []
print(json.dumps({
    "setup": rows("mx_setup_seconds"), "compile": rows("mx_compile_seconds"),
    "spans": [e for e in obs.timeline.events()
              if e["cat"] in ("setup", "compile")
              or e["name"] == "fit_step"]}))
"""


def child(case, **env):
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, case], cwd=ROOT, check=True,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env)).stdout
    got = json.loads(out.strip().splitlines()[-1])
    got["setup"] = {labels["phase"]: v for labels, v in got["setup"]}
    return got


def test_a_child_accounts_for_its_import():
    got = child("import")
    s = got["setup"]
    assert s["before_import"] > 0 and s["import.jax"] > 0 \
        and s["import.self"] > 0
    # nothing is counted twice: import.self books what import.jax (and a
    # compile stage, were there one) did not, so together they are the
    # outer span's length
    spans = {e["name"]: e for e in got["spans"]}
    assert _inside(spans["import.jax"], spans["import.self"])
    compiled = sum(v for _, v in got["compile"])
    assert s["import.self"] + s["import.jax"] + compiled == pytest.approx(
        spans["import.self"]["dur"] * 1e-6, abs=2e-3)
    # no loop yet: the account is still open
    assert not {"until_loop", "outside", "compile"} & set(s)


def test_a_childs_parts_add_up_to_until_loop():
    got = child("fit")
    s = got["setup"]
    for name in ("build.bind", "build.init_params", "build.init_optimizer",
                 "import.self", "import.jax"):
        assert s[name] > 0, name
    parts = sum(v for k, v in s.items()
                if k.startswith(("import", "build"))) \
        + s["compile"] + s["outside"]
    assert parts == pytest.approx(s["until_loop"] + s.get("after_loop", 0.0),
                                  abs=1e-6)
    assert s["outside"] >= s["before_import"] >= 0
    # set once, as the first fit_step opened: not at the second epoch's
    first = min((e for e in got["spans"] if e["name"] == "fit_step"),
                key=lambda e: e["ts"])
    jax_import = next(e for e in got["spans"] if e["name"] == "import.jax")
    assert s["until_loop"] == pytest.approx(
        s["before_import"] + (first["ts"] - jax_import["ts"]) * 1e-6,
        abs=0.05)
    # what compiled before the loop was the program's own eager work
    assert s["compile"] > 0
    assert {labels["program"] for labels, _ in got["compile"]} \
        >= {"(eager)", "train_step"}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def test_nested_phases_do_not_count_twice(telemetry):
    telemetry(True)
    before = family("mx_setup_seconds", "phase")
    obs.timeline.clear()
    with obs.phase("build.test_outer"):
        time.sleep(0.02)
        with obs.phase("build.test_inner"):
            time.sleep(0.05)
        time.sleep(0.01)
    after = family("mx_setup_seconds", "phase")
    outer = after["build.test_outer"] - before.get("build.test_outer", 0.0)
    inner = after["build.test_inner"] - before.get("build.test_inner", 0.0)
    spans = {e["name"]: e for e in setup_spans()}
    assert _inside(spans["build.test_inner"], spans["build.test_outer"])
    assert inner == pytest.approx(spans["build.test_inner"]["dur"] * 1e-6,
                                  abs=1e-5)
    # a parent plus its children is the parent's span
    assert outer + inner == pytest.approx(
        spans["build.test_outer"]["dur"] * 1e-6, abs=1e-5)
    assert outer > 0.025 and inner > 0.045


def test_a_compile_inside_a_phase_is_taken_out_of_it(telemetry):
    telemetry(True)

    def run():
        seconds = family("mx_compile_seconds", ("program", "stage"))
        before = family("mx_setup_seconds", "phase").get("build.test_c", 0.0)
        with obs.phase("build.test_c"):
            time.sleep(0.05)
            startup._on_duration(LOWER, 0.04, fun_name="f")
        span = [e for e in setup_spans() if e["name"] == "build.test_c"][-1]
        booked = family("mx_compile_seconds", ("program", "stage"))
        return (family("mx_setup_seconds", "phase")["build.test_c"] - before,
                booked[("(eager)", "lower")]
                - seconds.get(("(eager)", "lower"), 0.0),
                span["dur"] * 1e-6)

    phase_s, lower_s, span_s = in_a_fresh_thread(run)
    assert lower_s == pytest.approx(0.04, abs=1e-6)
    assert span_s > 0.05 and phase_s == pytest.approx(span_s - 0.04,
                                                      abs=1e-5)


def _fit(epochs=1):
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4), name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.uniform(-1, 1, (12, 8)).astype(np.float32),
                           rng.randint(0, 4, (12,)).astype(np.float32),
                           batch_size=4)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer="sgd")
    return mod


def test_a_fit_leaves_its_phases_and_its_steps_compiles(telemetry):
    telemetry(True)
    _fit()              # whatever ran before, a loop has opened by now
    until = family("mx_setup_seconds", "phase")["until_loop"]
    setup0 = family("mx_setup_seconds", "phase")
    compiles0 = family("mx_compiles_total", ("program", "cache"))
    obs.timeline.clear()
    _fit()
    ev = obs.timeline.events()
    setup1 = family("mx_setup_seconds", "phase")
    for name in ("build.bind", "build.init_params", "build.init_optimizer"):
        assert setup1[name] > setup0[name], name
        assert any(e["name"] == name for e in setup_spans(ev)), name
    # set once a process
    assert family("mx_setup_seconds", "phase")["until_loop"] == until > 0
    # the step's trace, lowering and compile are under its program span,
    # on the timeline inside the fit_step that paid for them
    assert family("mx_compiles_total", ("program", "cache"))[
        ("train_step", "miss")] > compiles0[("train_step", "miss")]
    stages = family("mx_compile_seconds", ("program", "stage"))
    assert all(stages[("train_step", s)] > 0
               for s in ("trace", "lower", "compile"))
    steps = [e for e in ev if e["name"] == "fit_step"]
    mine = [e for e in ev if e["cat"] == "compile"
            and e["args"]["program"] == "train_step"]
    assert {e["name"] for e in mine} == {"compile.trace", "compile.lower",
                                         "compile.compile"}
    assert all(any(_inside(e, s) for s in steps) for e in mine)
    assert {e["name"] for e in mine if _inside(e, steps[0])
            and e["args"]["fun"] in ("step", "jit(step)")} == {
        "compile.trace", "compile.lower", "compile.compile"}


def _paged_server(slots=2):
    from mxnet_tpu.analysis.programs import _LM, _lm_params, _lm_symbol
    from mxnet_tpu.decode import DecodePredictor, DecodeServer

    d = _LM
    sym = _lm_symbol()
    pred = DecodePredictor(sym, _lm_params(sym, slots, d["seq_len"]),
                           cache_len=d["seq_len"], temperature=0.0,
                           kv_dtype="int8", paged=True, page_tokens=4,
                           prefill_chunk=4)
    return pred, DecodeServer(pred, max_prefill=12, slots=slots,
                              max_new_tokens=3, spec_k=0)


def test_a_session_names_its_compiles_and_a_new_shape_mid_session(telemetry):
    telemetry(True)
    setup0 = family("mx_setup_seconds", "phase")
    obs.timeline.clear()
    pred, server = _paged_server()
    rng = np.random.RandomState(3)
    server.submit(rng.randint(0, 32, size=(6,)))
    while server.has_work:
        server.serve_tick()
    setup1 = family("mx_setup_seconds", "phase")
    for name in ("build.predictor", "build.server", "build.serve_open"):
        assert setup1[name] > setup0.get(name, 0.0), name
    assert setup1["until_loop"] > 0
    stages = family("mx_compile_seconds", ("program", "stage"))
    for program in ("prefill", "paged_decode_step"):
        assert all(stages[(program, s)] > 0
                   for s in ("trace", "lower", "compile")), program
    # the shape probe traces the graph and dispatches nothing: a phase
    # that names its trace
    assert setup1["build.shape_probe"] > setup0.get("build.shape_probe", 0.0)
    assert stages[("shape_probe", "trace")] > 0
    assert ("shape_probe", "compile") not in stages
    # a dispatch at a new shape inside the running session: the chunk's
    # width changes, so the next prompt's chunks compile a program
    compiles = family("mx_compiles_total", ("program", "cache"))
    n_ev = len(obs.timeline.events())
    server._chunk_w = 8
    server.submit(rng.randint(0, 32, size=(7,)))
    while server.has_work:
        server.serve_tick()
    assert family("mx_compiles_total", ("program", "cache"))[
        ("prefill", "miss")] == compiles[("prefill", "miss")] + 1
    ev = obs.timeline.events()[n_ev:]
    ticks = [e for e in ev if e["name"] == "serve.tick"]
    mine = [e for e in ev if e["cat"] == "compile"
            and e["args"]["program"] == "prefill"]
    assert {e["name"] for e in mine} == {"compile.trace", "compile.lower",
                                         "compile.compile"}
    tick = next(t for t in ticks if _inside(mine[0], t))
    assert all(_inside(e, tick) for e in mine)
    chunk = next(e for e in ev if e["name"] == "prefill"
                 and _inside(e, tick))
    assert all(_inside(e, chunk) for e in mine)


def test_reset_step_stats_leaves_the_account(telemetry):
    telemetry(True)
    _fit()
    before = [family("mx_setup_seconds", "phase"),
              family("mx_compile_seconds", ("program", "stage")),
              family("mx_compiles_total", ("program", "cache"))]
    assert all(before)
    profiler.reset_step_stats()
    assert [family("mx_setup_seconds", "phase"),
            family("mx_compile_seconds", ("program", "stage")),
            family("mx_compiles_total", ("program", "cache"))] == before


# ---------------------------------------------------------------------------
# the listener
# ---------------------------------------------------------------------------
def test_nested_stages_and_a_cache_read_add_up(telemetry):
    telemetry(True)

    def run():
        seconds = family("mx_compile_seconds", ("program", "stage"))
        counts = family("mx_compiles_total", ("program", "cache"))
        obs.timeline.clear()
        with obs.program_span("test_prog"):
            t0 = time.perf_counter()
            time.sleep(0.03)
            # an inner trace (a jit called while tracing) ends first
            startup._on_duration(TRACE, 0.01, fun_name="inner")
            traced = time.perf_counter() - t0
            startup._on_duration(TRACE, traced, fun_name="outer")
            t1 = time.perf_counter()
            time.sleep(0.02)
            # the cache answered: its retrieval, then the backend step
            # that holds it
            startup._on_event(HIT)
            startup._on_duration(RETRIEVAL, 0.015)
            read = time.perf_counter() - t1
            startup._on_duration(BACKEND, read, fun_name="outer")
        now = family("mx_compile_seconds", ("program", "stage"))
        got = {s: now[("test_prog", s)] - seconds.get(("test_prog", s), 0.0)
               for s in ("trace", "cache_read")}
        hits = family("mx_compiles_total", ("program", "cache"))[
            ("test_prog", "hit")] - counts.get(("test_prog", "hit"), 0.0)
        return got, hits, (traced, read), \
            ("test_prog", "compile") in now, obs.timeline.events()

    got, hits, (traced, read), compiled, ev = in_a_fresh_thread(run)
    assert hits == 1 and not compiled
    # the inner trace is not counted again, and the read's remainder goes
    # with the read: the stages add up to the time they took
    assert got["trace"] == pytest.approx(traced, abs=1e-6)
    assert got["cache_read"] == pytest.approx(read, abs=1e-6)
    spans = [e for e in ev if e["cat"] == "compile"]
    assert [e["name"] for e in spans] == ["compile.trace", "compile.trace",
                                          "compile.cache_read"]
    assert all(e["args"]["program"] == "test_prog" for e in spans)
    assert spans[-1]["args"]["retrieval_s"] == 0.015
    prog = next(e for e in ev if e["name"] == "test_prog")
    assert all(_inside(e, prog) for e in spans[1:])


def test_who_an_unnamed_compile_belongs_to(telemetry):
    telemetry(True)

    def who():
        startup._on_duration(LOWER, 0.001, fun_name="f")
        return obs.timeline.events()[-1]["args"]["program"]

    def run():
        out = [who()]
        with obs.phase("build.test_who"):
            out.append(who())
        with obs.top_span("serve.tick", cat="serve"):
            out.append(who())
            with obs.program_span("test_who"):
                out.append(who())
                # a span inside a span hands the name back as it closes,
                # and so does a phase that names a program
                with obs.program_span("test_who.inner"):
                    out.append(who())
                out.append(who())
                with obs.phase("build.test_probe", program="test_probe"):
                    out.append(who())
                out.append(who())
            out.append(who())
        out.append(who())
        return out

    assert in_a_fresh_thread(run) == [
        "(outside)", "(eager)", "(eager)", "test_who", "test_who.inner",
        "test_who", "test_probe", "test_who", "(eager)", "(outside)"]


def test_pallas_is_imported_through_one_door(telemetry):
    """``startup.pallas()`` hands out the two modules; the process's first
    call is phase ``import.pallas`` and no kernel module imports them
    another way."""
    import re

    telemetry(True)
    pl, pltpu = startup.pallas()
    assert pl is sys.modules["jax.experimental.pallas"]
    assert pltpu is sys.modules["jax.experimental.pallas.tpu"]
    before = family("mx_setup_seconds", "phase").get("import.pallas")
    assert startup.pallas() == (pl, pltpu)
    assert family("mx_setup_seconds", "phase").get("import.pallas") == before
    ops = os.path.join(ROOT, "mxnet_tpu", "ops")
    direct = [name for name in sorted(os.listdir(ops))
              if name.endswith(".py") and re.search(
                  r"^\s*(from|import) jax\.experimental(\.pallas| import "
                  r"pallas)\b(?!\.ops)",
                  open(os.path.join(ops, name)).read(), re.M)]
    assert direct == []


def test_one_listener_of_each_kind():
    import jax
    from jax._src import monitoring

    from mxnet_tpu.obs import program_maps

    mine = lambda fs: [f for f in fs
                       if f.__module__.startswith("mxnet_tpu")]
    startup.install()
    startup.install()
    assert mine(monitoring.get_event_duration_listeners()) \
        == [startup._on_duration]
    assert mine(monitoring.get_event_listeners()) == [startup._on_event]
    assert not hasattr(program_maps, "_on_compile")
    # the map readers' count is the same listener's
    before = program_maps._backend_compiles()
    jax.jit(lambda x: x * 3 + 1)(np.arange(5.0))
    assert program_maps._backend_compiles() == before + 1


def test_telemetry_off_records_none_of_it(telemetry):
    import jax

    from mxnet_tpu.obs import program_maps

    telemetry(False)
    before = (obs.registry.snapshot(), len(obs.timeline),
              program_maps._backend_compiles())
    with obs.phase("build.test_off"):
        with obs.top_span("serve.tick", cat="serve"):
            with obs.program_span("test_off"):
                jax.jit(lambda x: x * 5 + 2)(np.arange(7.0))
    assert obs.phased("build.test_off")(lambda: 3)() == 3
    assert (obs.registry.snapshot(), len(obs.timeline)) == before[:2]
    # the map readers still learn that something compiled
    assert program_maps._backend_compiles() == before[2] + 1
