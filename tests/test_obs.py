"""The unified telemetry subsystem (mxnet_tpu.obs) + its zero-overhead
contract.

Registry/timeline mechanics: concurrent increments sum exactly,
histogram percentiles match numpy, exporters round-trip, the span ring
buffer holds its bound under sustained traffic, and the exported
timeline is valid Chrome-trace JSON.

The tripwire that keeps telemetry FREE: the compiled HLO of an
instrumented fused train step / donated decode step is byte-identical
to the uninstrumented one (instrumentation is host-side timing only —
nothing may ever leak into a traced program), and the analysis
host-sync pass stays green on the instrumented programs (zero new host
syncs).
"""
import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, obs, profiler
from mxnet_tpu.obs.metrics import MetricsRegistry
from mxnet_tpu.obs.trace import TraceTimeline


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_concurrent_counter_increments_sum_exactly():
    reg = MetricsRegistry()
    plain = reg.counter("t_ops", "ops")
    labeled = reg.counter("t_ops_by", "ops by worker", labels=("who",))
    hist = reg.histogram("t_lat", "latencies")
    nthreads, per = 8, 2000

    def worker(i):
        child = labeled.labels(who="w%d" % (i % 3))
        for j in range(per):
            plain.inc()
            child.inc()
            hist.observe(j * 1e-4)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert plain.get() == nthreads * per
    snap = reg.snapshot()
    assert sum(r["value"] for r in snap["t_ops_by"]["series"]) \
        == nthreads * per
    assert snap["t_lat"]["series"][0]["value"]["count"] == nthreads * per


def test_histogram_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("t_h", "h")
    rng = np.random.RandomState(7)
    vals = rng.lognormal(-3, 1.5, size=997)
    for v in vals:
        h.observe(v)
    for q in (0.5, 0.9, 0.95, 0.99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q * 100)), rel=1e-12)
    assert reg.histogram("t_empty", "e").percentile(0.5) is None


def test_exporters_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("t_c", "a counter").inc(5)
    reg.gauge("t_g", "a gauge").set(2.5)
    h = reg.histogram("t_h", "a histogram", labels=("k",))
    h.labels(k="x").observe(0.03)
    h.labels(k="x").observe(0.3)
    path = str(tmp_path / "metrics.jsonl")
    reg.export_jsonl(path)
    reg.counter("t_c").inc(1)
    reg.export_jsonl(path)
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2 and lines[1]["ts"] >= lines[0]["ts"]
    assert lines[0]["metrics"]["t_c"]["series"][0]["value"] == 5
    assert lines[1]["metrics"] == reg.snapshot()
    prom = reg.prometheus_text()
    assert "# TYPE t_c counter" in prom and "t_c 6" in prom
    assert "t_g 2.5" in prom
    assert 't_h_count{k="x"} 2' in prom
    assert 't_h_bucket{k="x",le="0.05"} 1' in prom
    assert 't_h_bucket{k="x",le="+Inf"} 2' in prom


def test_metrics_http_server():
    reg = MetricsRegistry()
    reg.counter("t_http", "served").inc(3)
    tl = TraceTimeline(capacity=16)
    tl.instant("ping")
    srv = obs.MetricsServer(registry=reg, timeline=tl, port=0).start()
    try:
        base = "http://127.0.0.1:%d" % srv.port
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "t_http 3" in text
        trace = json.loads(
            urllib.request.urlopen(base + "/trace").read().decode())
        assert trace["traceEvents"][0]["name"] == "ping"
        assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# trace timeline
# ---------------------------------------------------------------------------
def test_ring_buffer_bound_under_sustained_spans():
    tl = TraceTimeline(capacity=128)
    for i in range(2000):
        tl.add_span("s%d" % i, i * 1e-3, 1e-4)
    assert len(tl) == 128
    assert tl.dropped == 2000 - 128
    names = [e["name"] for e in tl.events()]
    assert names[0] == "s%d" % (2000 - 128)   # oldest evicted first
    assert names[-1] == "s1999"
    tl.clear()
    assert len(tl) == 0 and tl.dropped == 0


def _profiled_host_names(logdir):
    """Names of the host events in the newest ``.xplane.pb`` under a
    ``jax.profiler`` trace directory."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, "the profiler session left no .xplane.pb"
    names = set()
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_chrome_trace_schema_and_profiler_mirror(tmp_path):
    import jax

    tl = TraceTimeline(capacity=1024)
    with tl.span("outer", cat="loop", args={"epoch": 0}):
        with tl.span("inner"):
            pass
        tl.instant("commit", cat="elastic", args={"step": 3})
    t = threading.Thread(target=lambda: tl.add_span("other-thread", 0.0,
                                                    1e-3))
    t.start()
    t.join()
    out = str(tmp_path / "trace.json")
    tl.export(out)
    payload = json.load(open(out))
    events = payload["traceEvents"]
    assert {"outer", "inner", "commit", "other-thread"} \
        == {e["name"] for e in events}
    tids = {e["tid"] for e in events if e["name"] in ("outer",
                                                      "other-thread")}
    assert len(tids) == 2          # thread-aware
    for e in events:
        assert isinstance(e["name"], str) and isinstance(e["ts"], int)
        assert e["ph"] in ("X", "i") and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
        else:
            assert e.get("s") in ("t", "p", "g")
    # nesting: inner lies within outer on the same thread
    by = {e["name"]: e for e in events}
    assert by["outer"]["ts"] <= by["inner"]["ts"]
    assert by["inner"]["ts"] + by["inner"]["dur"] \
        <= by["outer"]["ts"] + by["outer"]["dur"]
    # one clock: a live span is stamped with perf_counter, not the epoch
    import time
    assert abs(by["outer"]["ts"] - time.perf_counter_ns() // 1000) < 60e6

    # the mirror: a profiler session around obs.span / obs.program_span
    # holds them as mx:<name> in its own .xplane.pb (the joined view);
    # a plain TraceTimeline.span stays in its ring only
    logdir = str(tmp_path / "xla")
    jax.profiler.start_trace(logdir)
    try:
        with obs.span("serve.tick", cat="serve", args={"tick": 7}):
            with obs.program_span("mirror-test-program"):
                pass
        with tl.span("ring-only"):
            pass
    finally:
        jax.profiler.stop_trace()
    names = _profiled_host_names(logdir)
    assert any(n.startswith("mx:serve.tick") for n in names), sorted(
        n for n in names if "mx" in n)
    assert "mx:mirror-test-program" in names
    assert not any("ring-only" in n for n in names)


# ---------------------------------------------------------------------------
# profiler facade satellites
# ---------------------------------------------------------------------------
def test_request_stats_p95_and_percentile_guard():
    profiler.reset_step_stats()
    for i in range(20):
        profiler.record_request(0.001 * i, 0.01 * (i + 1), 10 + i, 0.1)
    stats = profiler.step_stats()["requests"]
    assert stats["count"] == 20
    for key in ("queue_wait_p50_s", "queue_wait_p95_s", "ttft_p50_s",
                "ttft_p95_s", "decode_tokens_per_sec_p50",
                "decode_tokens_per_sec_p95"):
        assert stats[key] is not None and stats[key] >= 0
    assert stats["decode_tokens_per_sec_p95"] >= \
        stats["decode_tokens_per_sec_p50"]
    # the empty-input guard (the historical version raised IndexError)
    assert profiler._percentile([], 0.5) is None
    profiler.reset_step_stats()
    assert "requests" not in profiler.step_stats()


def test_profiler_start_clears_stale_events(tmp_path):
    fname = str(tmp_path / "p.json")
    obs.timeline.add_span("stale-span", 0.0, 1e-3)
    mx.profiler.profiler_set_config(filename=fname)
    mx.profiler.profiler_set_state("run")
    with mx.profiler.Scope("fresh-span"):
        pass
    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    names = {e["name"] for e in json.load(open(fname))["traceEvents"]}
    assert "fresh-span" in names
    assert "stale-span" not in names


# ---------------------------------------------------------------------------
# the zero-overhead tripwire
# ---------------------------------------------------------------------------
@pytest.fixture
def telemetry(request):
    """Set MXNET_TELEMETRY and refresh the config cache; restores (and
    re-refreshes) on teardown regardless of outcome."""
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


def _train_artifact():
    from mxnet_tpu.analysis.programs import _drive_fused, _mlp_module
    from mxnet_tpu.base import NameManager

    with NameManager():  # deterministic auto-names across builds
        mod, batch = _mlp_module()
    step = _drive_fused(mod, batch, steps=1)
    return step.artifact(name="train_step")


def _decode_artifact():
    import jax

    from mxnet_tpu.analysis.programs import _lm_params, _lm_symbol
    from mxnet_tpu.base import NameManager
    from mxnet_tpu.decode import DecodePredictor

    with NameManager():  # deterministic auto-names across builds
        sym = _lm_symbol()
    pred = DecodePredictor(sym, _lm_params(sym, 2, 16), cache_len=16,
                           temperature=0.0, kv_dtype="", paged=False)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 32, size=(2, 16)).astype(np.float32)
    prompts[:, 8:] = 0.0
    key = jax.random.PRNGKey(0)
    state, _ = pred.prefill(prompts, 8, key)
    state, _ = pred.step(state, key)
    return pred.decode_artifact(state)


def _program_spans():
    """How many ``cat="program"`` spans the timeline holds, by name."""
    import collections

    return collections.Counter(e["name"] for e in obs.timeline.events()
                               if e["cat"] == "program")


def _without_source_locations(hlo):
    """Compiled HLO embeds python source locations (four header tables
    plus a ``stack_frame_id`` per op).  The telemetry wrapper dispatches
    the step from a different line of ``run()``, so those differ by
    construction; everything else must match byte for byte."""
    hlo = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(?:\d+ .*\n)*", "", hlo, flags=re.M)
    return re.sub(r" stack_frame_id=\d+", "", hlo)


def test_instrumentation_is_free_hlo_byte_identical(telemetry):
    """The acceptance tripwire: telemetry on vs off, the fused train
    step and the donated decode step lower AND compile to byte-identical
    programs, and the host-sync pass finds zero host round-trips in the
    instrumented ones — telemetry can never silently add a transfer or
    retrace."""
    from mxnet_tpu import analysis

    telemetry(True)
    train_on = _train_artifact()
    decode_on = _decode_artifact()
    telemetry(False)
    train_off = _train_artifact()
    decode_off = _decode_artifact()

    assert train_on.stablehlo_text == train_off.stablehlo_text
    assert _without_source_locations(train_on.compiled_text) == \
        _without_source_locations(train_off.compiled_text)
    assert decode_on.stablehlo_text == decode_off.stablehlo_text
    assert _without_source_locations(decode_on.compiled_text) == \
        _without_source_locations(decode_off.compiled_text)

    # zero new host syncs: the host-sync pass is green on the
    # INSTRUMENTED programs (no callback prims, no infeed/outfeed)
    report = analysis.run_passes([train_on, decode_on],
                                 passes=[analysis.HostSyncPass()],
                                 budgets={})
    assert report.ok(), report.format_text()
    assert all(f.severity == "info" for f in report.findings), \
        report.format_text()
    # both programs really were instrumented: each dispatch left its
    # span on the timeline while telemetry was on
    assert {"train_step", "decode_step"} <= set(_program_spans())


def test_telemetry_off_records_nothing(telemetry):
    telemetry(False)
    before = len(obs.timeline)
    with obs.span("should-not-record"):
        obs.instant("nor-this")
    with obs.program_span("nor-that"):
        pass
    assert len(obs.timeline) == before
    telemetry(True)
    with obs.span("records"):
        pass
    assert len(obs.timeline) == before + 1


# ---------------------------------------------------------------------------
# layer scopes: every program's optimized HLO reads back as a scope map
# ---------------------------------------------------------------------------
def _lm_train_step():
    from mxnet_tpu.analysis.programs import _LM, _drive_fused, _lm_symbol
    from mxnet_tpu.io import DataBatch

    d = _LM
    mod = mx.mod.Module(_lm_symbol(), context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", (d["batch"], d["seq_len"]),
                                         layout="NT")],
             label_shapes=[mx.io.DataDesc("softmax_label",
                                          (d["batch"], d["seq_len"]),
                                          layout="NT")])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    rng = np.random.RandomState(0)
    toks = rng.randint(0, d["vocab"], (d["batch"], d["seq_len"]))
    batch = DataBatch([mx.nd.array(toks.astype(np.float32))],
                      [mx.nd.array(np.roll(toks, -1, 1).astype(np.float32))])
    return _drive_fused(mod, batch, steps=2), "train_step"


def _resnet_train_step():
    from mxnet_tpu.analysis.programs import _drive_fused
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.models import resnet

    sym = resnet.get_symbol(num_classes=4, num_layers=8,
                            image_shape=(3, 16, 16))
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3, 16, 16))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)
    batch = DataBatch(
        [mx.nd.array(rng.uniform(-1, 1, (2, 3, 16, 16)).astype(np.float32))],
        [mx.nd.array(rng.randint(0, 4, (2,)).astype(np.float32))])
    return _drive_fused(mod, batch, steps=2), "train_step"


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


def _served(name):
    pred, server = _paged_server()
    rng = np.random.RandomState(1)
    for n in (5, 7, 3):
        server.submit(rng.randint(0, 32, size=(n,)))
    assert len(server.run()) == 3
    return pred, name


_SCOPED_PROGRAMS = {
    # program -> (builder, layers its map must hold)
    "lm_train_step": (_lm_train_step, {
        "attn", "attn/scores", "linear", "norm", "embed", "head_loss",
        "optimizer", "other"}),
    "resnet_train_step": (_resnet_train_step, {
        "conv", "norm", "pool", "linear", "head_loss", "optimizer"}),
    "paged_decode_step": (lambda: _served("paged_decode_step"), {
        "attn", "attn/kv_append", "attn/kv_gather", "attn/kv_dequant",
        "attn/scores", "linear", "norm", "embed", "head_loss"}),
    "prefill_chunk": (lambda: _served("prefill_chunk"), {
        "attn/kv_append", "attn/kv_gather", "attn/kv_dequant",
        "attn/scores", "linear", "norm", "head_loss"}),
}


@pytest.mark.parametrize("program", sorted(_SCOPED_PROGRAMS))
def test_scope_map_names_every_instruction(program, telemetry):
    """``obs.programs.scope_map``: every instruction of the program's
    optimized HLO gets a layer of the one vocabulary, each layer the
    model has is there, and reading it compiles nothing (the text comes
    off the executable the program already dispatched)."""
    import jax

    from mxnet_tpu.obs import scopes

    telemetry(True)
    build, want = _SCOPED_PROGRAMS[program]
    owner, name = build()      # keep the owner alive: the thunk is weak
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    smap = obs.programs.scope_map(name)
    assert not compiles, "scope_map compiled %d program(s)" % len(compiles)
    assert smap and obs.programs.scope_map(name) is smap     # cached
    vocabulary = set(scopes.LAYERS) | {scopes.UNSCOPED} \
        | {"attn/" + s for s in scopes.SUBSCOPES} | {"head_loss/sample"}
    found = set(smap.values())
    assert found <= vocabulary, found - vocabulary
    assert want <= found, want - found
    # keyed by the HLO module's own name for the device trace's join
    stem = {"lm_train_step": "jit_step", "resnet_train_step": "jit_step",
            "paged_decode_step": "jit__paged_decode_impl",
            "prefill_chunk": "jit__chunk_impl"}[program]
    assert smap.items() <= obs.programs.scope_maps()[stem].items()
    # the scopes the framework traces are all read back: no mx.<layer>
    # op_name falls to "unscoped"
    fn_args = owner._static_args[name] if hasattr(owner, "_static_args") \
        else None
    text = owner._program_hlo(name) if fn_args else owner.compiled_hlo()
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", line)
        if m and re.search(r'op_name="[^"]*mx\.', line):
            assert smap[m.group(1)] != scopes.UNSCOPED, line[:200]
    del owner


def test_scope_of_strips_the_backward_wrapper():
    from mxnet_tpu.obs.scopes import scope_of

    assert scope_of("jit(step)/mx.linear/layer0_q/dot_general") == "linear"
    assert scope_of("jit(step)/transpose(jvp(mx.attn/att0))/mx.attn/scores"
                    "/mul") == "attn/scores"
    assert scope_of("jit(step)/transpose(jvp(mx.norm/bn1))/reduce_sum") \
        == "norm"
    assert scope_of("jit(step)/mx.optimizer/sub") == "optimizer"
    # a node that happens to be named like a sub-scope: still its layer's
    assert scope_of("jit(f)/mx.other/scores/add") == "other/scores"
    assert scope_of("jit(step)/jvp()/reduce_sum") is None


# ---------------------------------------------------------------------------
# instruction maps: what an instruction is, what it carries, what it feeds
# ---------------------------------------------------------------------------
_HAND_HLO = """HloModule jit_hand, is_scheduled=true, entry_computation_layout={(f32[8,16]{1,0:T(8,128)}, f32[4,4]{1,0})->f32[8,16]{1,0}}

%fused_computation.1 (param_0.1: f32[8,16], param_1.1: f32[8,16]) -> f32[8,16] {
  %param_0.1 = f32[8,16]{1,0:T(8,128)S(1)} parameter(0)
  %param_1.1 = f32[8,16]{1,0:T(8,128)} parameter(1)
  ROOT %add.1 = f32[8,16]{1,0:T(8,128)} add(%param_0.1, %param_1.1), metadata={op_name="jit(hand)/mx.linear/fc1/add"}
}

%copy_fusion.clone (param_0.2: f32[8,16]) -> f32[16,8] {
  %param_0.2 = f32[8,16]{1,0:T(8,128)} parameter(0)
  %bitcast.3 = f32[8,16]{1,0:T(8,128)} bitcast(%param_0.2)
  ROOT %transpose.4 = f32[16,8]{1,0:T(8,128)} transpose(%bitcast.3), dimensions={1,0}
}

%async_computation (param_0.3: f32[8,16]) -> f32[2,16] {
  %param_0.3 = f32[8,16]{1,0:T(8,128)} parameter(0)
  ROOT %slice.7 = f32[2,16]{1,0:T(2,128)S(1)} slice(%param_0.3), slice={[0:2], [0:16]}
}

%body (p.1: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p.1 = (s32[]{:T(128)}, f32[8,16]{1,0:T(8,128)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%p.1), index=0
  %x.1 = f32[8,16]{1,0:T(8,128)} get-tuple-element(%p.1), index=1
  %copy.9 = f32[8,16]{0,1:T(8,128)} copy(%x.1)
  %fusion.9 = f32[8,16]{1,0:T(8,128)} fusion(%copy.9, %x.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(hand)/while/body/mx.attn/att0/mx.attn/kv_gather/add"}
  ROOT %tuple.9 = (s32[]{:T(128)}, f32[8,16]{1,0:T(8,128)}) tuple(%i.1, %fusion.9)
}

%cond (p.2: (s32[], f32[8,16])) -> pred[] {
  %p.2 = (s32[]{:T(128)}, f32[8,16]{1,0:T(8,128)}) parameter(0)
  %i.2 = s32[]{:T(128)} get-tuple-element(%p.2), index=0
  %c.2 = s32[]{:T(128)} constant(3)
  ROOT %lt.2 = pred[]{:T(512)} compare(%i.2, %c.2), direction=LT
}

ENTRY %main.1 (env__fc1_weight__.1: f32[8,16], Arg_1.2: f32[4,4]) -> f32[8,16] {
  %env__fc1_weight__.1 = f32[8,16]{1,0:T(8,128)} parameter(0), sharding={replicated}, metadata={op_name="env[\\'fc1_weight\\']"}
  %Arg_1.2 = f32[4,4]{1,0:T(4,128)} parameter(1)
  %copy-start = (f32[8,16]{1,0:T(8,128)S(1)}, f32[8,16]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%env__fc1_weight__.1), cross_program_prefetch_index=0
  %copy-done = f32[8,16]{1,0:T(8,128)S(1)} copy-done(%copy-start)
  %fusion.1 = f32[8,16]{1,0:T(8,128)} fusion(%copy-done, %env__fc1_weight__.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(hand)/mx.linear/fc1/add" stack_frame_id=7}, backend_config={"op_name":"not this one"}
  %reshape.2 = f32[16,8]{1,0:T(8,128)} reshape(%fusion.1), metadata={op_name="jit(hand)/mx.linear/fc1/reshape"}
  %fusion.5 = f32[16,8]{1,0:T(8,128)} fusion(%fusion.1), kind=kLoop, calls=%copy_fusion.clone
  %slice-start = ((f32[8,16]{1,0:T(8,128)}), f32[2,16]{1,0:T(2,128)S(1)}, s32[]{:S(2)}) async-start(%fusion.1), calls=%async_computation
  %slice-done = f32[2,16]{1,0:T(2,128)S(1)} async-done(%slice-start)
  %copy.3 = f32[4,4]{0,1:T(4,128)} copy(%Arg_1.2)
  %dot.6 = f32[8,16]{1,0:T(8,128)} custom-call(%fusion.5, %slice-done, %copy.3), custom_call_target="x", metadata={op_name="jit(hand)/mx.attn/att0/mx.attn/scores/dot_general"}
  %zero.1 = s32[]{:T(128)} constant(0)
  %tuple.1 = (s32[]{:T(128)}, f32[8,16]{1,0:T(8,128)}) tuple(%zero.1, %copy-done)
  %while.1 = (s32[]{:T(128)}, f32[8,16]{1,0:T(8,128)}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(hand)/mx.attn/att0/while"}
  %gte.1 = f32[8,16]{1,0:T(8,128)} get-tuple-element(%while.1), index=1
  %copy.8 = f32[8,16]{1,0} copy(%gte.1)
  ROOT %tuple.2 = (f32[8,16]{1,0}) tuple(%copy.8)
}
"""


def test_instructions_reads_opcode_shape_operands_and_computations():
    from mxnet_tpu.analysis.hlo_parse import instructions

    module, rows = instructions(_HAND_HLO)
    assert module == "jit_hand"
    by = {r.name: r for r in rows}
    assert len(by) == len(rows)                 # names are unique
    start = by["copy-start"]
    assert start.opcode == "copy-start" and start.entry \
        and start.computation == "main.1"
    assert start.operands == ("env__fc1_weight__.1",)
    # the shape as printed, layout and memory space too; an async start
    # counts what its done delivers, not its whole tuple
    assert start.shape.startswith("(f32[8,16]{1,0:T(8,128)S(1)}, ")
    assert start.bytes == 8 * 16 * 4 == by["copy-done"].bytes
    assert by["copy-done"].shape == "f32[8,16]{1,0:T(8,128)S(1)}"
    assert by["slice-start"].opcode == "async-start" \
        and by["slice-start"].called == ("async_computation",) \
        and by["slice-start"].bytes == 2 * 16 * 4
    p = by["env__fc1_weight__.1"]
    assert p.opcode == "parameter" and p.index == 0 \
        and p.op_name == "env[\\'fc1_weight\\']" and p.operands == ()
    assert by["param_0.1"].index == 0 and not by["param_0.1"].entry
    assert by["x.1"].index == 1 and by["fusion.1"].index is None
    f = by["fusion.1"]
    assert f.opcode == "fusion" and f.called == ("fused_computation.1",) \
        and f.operands == ("copy-done", "env__fc1_weight__.1")
    # the metadata's own op_name, not one quoted in a backend_config
    assert f.op_name == "jit(hand)/mx.linear/fc1/add"
    assert by["while.1"].called == ("cond", "body") \
        and by["while.1"].operands == ("tuple.1",)
    assert by["tuple.2"].root and by["add.1"].root and not f.root
    assert by["zero.1"].operands == () and by["copy.9"].computation == "body"


_HAND_WANT = {
    # a scopeless copy of an entry parameter, feeding a scoped fusion
    # through copy-start -> copy-done (and the loop, listed later)
    "copy-start": dict(scope="unscoped", moves=True,
                       src="env['fc1_weight']", feeds="linear", n_feeds=2),
    "copy-done": dict(scope="unscoped", moves=True, src="env['fc1_weight']",
                      feeds="linear", n_feeds=2, opcode="copy-done",
                      shape="f32[8,16]{1,0:T(8,128)S(1)}", bytes=512),
    # a scoped reshape is a move all the same, and carries its producer's
    "reshape.2": dict(scope="linear", moves=True, src="linear", feeds=None),
    # a fusion of moves only; its producer has a scope, its consumer too
    "fusion.5": dict(scope="unscoped", moves=True, src="linear",
                     feeds="attn/scores", n_feeds=1),
    "fusion.1": dict(scope="linear", moves=False, src=None, feeds=None),
    # slice-start / slice-done: an async pair round a slice
    "slice-start": dict(scope="unscoped", moves=True, src="linear",
                        feeds="attn/scores"),
    "slice-done": dict(scope="unscoped", moves=True, src="linear",
                       feeds="attn/scores"),
    # a parameter jax gave no name: its number and shape
    "copy.3": dict(scope="unscoped", moves=True,
                   src="parameter(1) f32[4,4]", feeds="attn/scores"),
    # inside the while body: the loop's state is followed to the operand
    # the loop was entered with, and on to the entry parameter
    "copy.9": dict(scope="unscoped", moves=True, src="env['fc1_weight']",
                   feeds="attn/kv_gather", n_feeds=1),
    # what only the program's result reads
    "copy.8": dict(scope="unscoped", moves=True, src="attn", feeds="output"),
    "dot.6": dict(scope="attn/scores", moves=False),
    "lt.2": dict(scope="unscoped", moves=False, src=None, feeds=None,
                 n_feeds=0),
}


@pytest.mark.parametrize("name", sorted(_HAND_WANT))
def test_instruction_map_says_what_an_instruction_is(name):
    from mxnet_tpu.obs import scopes

    module, imap = scopes.instruction_map(_HAND_HLO)
    assert module == "jit_hand"
    assert set(imap[name]) == {"scope", "opcode", "shape", "bytes", "moves",
                               "src", "feeds", "n_feeds"}
    got = {k: imap[name][k] for k in _HAND_WANT[name]}
    assert got == _HAND_WANT[name]
    # the scope map is the same text's, instruction for instruction
    assert {k: v["scope"] for k, v in imap.items()} \
        == scopes.scope_map(_HAND_HLO)[1]


def _count_compiles():
    import jax

    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: seen.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return seen


_MAPPED_PROGRAMS = {
    # program -> (builder, the module its map is keyed by)
    "lm_train_step": (_lm_train_step, "jit_step"),
    "paged_decode_step": (lambda: _served("paged_decode_step"),
                          "jit__paged_decode_impl"),
    "prefill_chunk": (lambda: _served("prefill_chunk"), "jit__chunk_impl"),
}


@pytest.mark.parametrize("program", sorted(_MAPPED_PROGRAMS))
def test_instruction_maps_are_the_dispatched_programs(program, telemetry):
    """``obs.programs.instruction_maps``: read off the executable the
    program dispatches, with nothing compiled, and marked so."""
    from mxnet_tpu.obs import scopes

    telemetry(True)
    obs.programs.reset(clear_static=True)
    build, stem = _MAPPED_PROGRAMS[program]
    owner, name = build()
    compiles = _count_compiles()
    maps = obs.programs.instruction_maps()
    assert not compiles, "reading the maps compiled %d" % len(compiles)
    entry = maps[stem]
    assert entry["source"] == "dispatched" and entry["conflicts"] == 0
    rows = entry["instructions"]
    assert {k: v["scope"] for k, v in rows.items()} \
        == obs.programs.scope_maps()[stem] == obs.programs.scope_map(name)
    # every entry parameter is known by the name jax gave it
    params = [v for v in rows.values() if v["opcode"] == "parameter"]
    assert params
    # a scopeless move at the top of the program names what it carries or
    # what it feeds
    loose = [v for v in rows.values() if v["scope"] == scopes.UNSCOPED
             and v["moves"] and (v["src"] or v["feeds"])]
    assert loose
    vocabulary = set(scopes.LAYERS) | {scopes.OUTPUT, None} \
        | {"attn/" + s for s in scopes.SUBSCOPES} | {"head_loss/sample"}
    assert {v["feeds"] for v in rows.values()} <= vocabulary
    del owner


def test_a_map_read_by_compiling_says_relowered(telemetry):
    """A reader whose text comes from a compile made while it is read
    (another compile's ``fusion.N`` need not be the running program's) is
    marked ``"relowered"``, not ``"dispatched"``."""
    import jax
    import jax.numpy as jnp

    telemetry(True)
    obs.programs.reset(clear_static=True)
    x = jnp.ones((4, 4))
    loaded = jax.jit(lambda a: a @ a + 1.0).lower(x).compile().as_text()

    def fresh():
        return jax.jit(lambda a: a @ a - 2.0).lower(x).compile().as_text()

    obs.programs.register_hlo("held", lambda: loaded)
    obs.programs.register_hlo("fresh", fresh)
    assert obs.programs.scope_map("held") is not None
    by_name = {r.name: r for r in obs.programs._hlo.values()}
    obs.programs.instruction_maps()
    assert by_name["held"].source == "dispatched"
    assert by_name["fresh"].source == "relowered"
    obs.programs.reset(clear_static=True)


def test_two_predictors_keep_their_own_maps_and_count_the_conflict(
        telemetry, caplog):
    """Two live programs of one module name: ``scope_maps`` joins with the
    newest registered whose owner lives, whole, and counts the instruction
    names they disagree on; a collected owner's map no longer counts."""
    import gc
    import logging

    from mxnet_tpu.analysis.programs import _LM, _lm_params, _lm_symbol
    from mxnet_tpu.decode import DecodePredictor, DecodeServer

    telemetry(True)
    obs.programs.reset(clear_static=True)
    rng = np.random.RandomState(3)

    def serve(slots):
        sym = _lm_symbol()
        pred = DecodePredictor(sym, _lm_params(sym, slots, _LM["seq_len"]),
                               cache_len=_LM["seq_len"], temperature=0.0,
                               kv_dtype="int8", paged=True, page_tokens=4,
                               prefill_chunk=4)
        server = DecodeServer(pred, max_prefill=12, slots=slots,
                              max_new_tokens=3, spec_k=0)
        for n in (5, 7, 3):
            server.submit(rng.randint(0, 32, size=(n,)))
        assert len(server.run()) == 3
        return pred

    stem = "jit__paged_decode_impl"
    first = serve(2)
    alone = dict(obs.programs.scope_maps()[stem])
    assert obs.programs.instruction_maps()[stem]["conflicts"] == 0
    second = serve(3)
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.obs.program_maps"):
        entry = obs.programs.instruction_maps()[stem]
        obs.programs.instruction_maps()
    # the newer predictor's program, whole: not the two merged
    assert entry["conflicts"] > 0
    newest = obs.programs.scope_maps()[stem]
    assert newest == {k: v["scope"] for k, v in entry["instructions"].items()}
    assert newest == obs.programs.scope_map("paged_decode_step")
    shapes = {v["shape"] for v in entry["instructions"].values()}
    assert any(s.startswith("s32[3]") for s in shapes) \
        and not any(s.startswith("s32[2]{") for s in shapes)
    # said once, not at every reading
    said = [r for r in caplog.records if stem in r.getMessage()]
    assert len(said) == 1
    # the newer owner gone: the older one's map is the module's again
    del second
    gc.collect()
    assert obs.programs.scope_maps()[stem] == alone
    entry = obs.programs.instruction_maps()[stem]
    assert entry["conflicts"] == 0 and any(
        v["shape"].startswith("s32[2]{")
        for v in entry["instructions"].values())
    del first
    obs.programs.reset(clear_static=True)


@pytest.mark.parametrize("program,stem", [
    ("slot_commit", "jit__commit_impl"), ("page_fork", "jit__fork_impl"),
    ("keep_tok", "jit__keep_tok")])
def test_programs_beside_the_steps_have_maps_after_a_serve(program, stem,
                                                           telemetry):
    """What the serving loop dispatches beside its steps (the commit of a
    slot, a copy-on-write fork, the copy of a step's tokens) registers
    its program too, read with nothing compiled; a program that never
    ran is not compiled for its map."""
    telemetry(True)
    obs.programs.reset(clear_static=True)
    pred, server = _paged_server()
    rng = np.random.RandomState(5)
    prefix = rng.randint(0, 32, size=(6,))
    for n in (3, 2, 4, 3):
        server.submit(np.concatenate([prefix, rng.randint(0, 32, (n,))]))
    assert len(server.run()) == 4
    assert pred.trace_counts["fork"] == 1 and pred.trace_counts["commit"] == 1
    compiles = _count_compiles()
    maps = obs.programs.instruction_maps()
    assert not compiles
    assert maps[stem]["source"] == "dispatched" and maps[stem]["instructions"]
    assert obs.programs.scope_map(program) == obs.programs.scope_maps()[stem]
    # never dispatched here: no map, and nothing compiled to make one
    assert "jit__extract_impl" not in maps and "jit__install_impl" not in maps
    assert obs.programs.scope_map("page_extract") is None
    assert not compiles
    obs.programs.reset(clear_static=True)


def _small_fit(num_epoch=2):
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4), name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.uniform(-1, 1, (12, 8)).astype(np.float32),
                           rng.randint(0, 4, (12,)).astype(np.float32),
                           batch_size=4)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd")
    return mod


def test_every_dispatch_leaves_a_program_span_and_a_reader(telemetry):
    """After a fit and a serve every dispatched step left a
    ``cat="program"`` span under its name, as many as were dispatched,
    and ``obs.programs`` holds HLO readers and nothing else: no timing,
    no static cost, no table."""
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.obs.program_maps import _Registered

    telemetry(True)
    obs.programs.reset(clear_static=True)
    obs.timeline.clear()
    mod = _small_fit()
    pred, server = _paged_server()
    rng = np.random.RandomState(4)
    for n in (5, 7, 3):
        server.submit(rng.randint(0, 32, size=(n,)))
    assert len(server.run()) == 3
    spans = _program_spans()
    # three batches an epoch, two epochs; a chunk a ``serve.prefill``
    assert spans["train_step"] == mod._fused_step.num_steps == 6
    assert spans["paged_decode_step"] == server.steps > 0
    assert spans["prefill"] == sum(e["name"] == "serve.prefill"
                                   for e in obs.timeline.events()) > 0
    assert set(vars(obs.programs)) == {"_lock", "_hlo", "_registered",
                                       "_conflicts_logged"}
    assert all(isinstance(r, _Registered)
               for r in obs.programs._hlo.values())
    assert {name for name, _ in obs.programs._hlo} == {
        "train_step", "paged_decode_step", "prefill_chunk"} \
        | set(DecodePredictor._BESIDE)
    assert not hasattr(obs.programs, "table")
    del mod, pred, server
    obs.programs.reset(clear_static=True)


@pytest.mark.parametrize("kind", ["step", "predictor"])
def test_a_collected_owner_is_not_pinned_by_the_readers(kind, telemetry):
    """A fused step (with its module's master weights) or a predictor
    (its parameters and snapped programs) that nothing else holds is
    collected although the process-wide readers know its programs: a
    reader's thunk names its owner weakly."""
    import gc
    import weakref

    telemetry(True)
    obs.programs.reset(clear_static=True)
    if kind == "step":
        holder = _small_fit(num_epoch=1)
        owner, name = holder._fused_step, "train_step"
    else:
        owner, holder = _paged_server()
        holder.submit(np.arange(5))
        assert len(holder.run()) == 1
        name = "paged_decode_step"
    assert (name, id(owner)) in obs.programs._hlo
    ref = weakref.ref(owner)
    del owner, holder
    gc.collect()
    assert ref() is None
    # its record goes with it at the next reading
    assert obs.programs.scope_map(name) is None
    assert not obs.programs._hlo and not obs.programs.scope_maps()
    obs.programs.reset(clear_static=True)


# ---------------------------------------------------------------------------
# host phase spans
# ---------------------------------------------------------------------------
def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def test_serve_tick_spans_nest_and_share_the_request_id(telemetry):
    telemetry(True)
    pred, server = _paged_server()
    rng = np.random.RandomState(2)
    rid = server.submit(rng.randint(0, 32, size=(6,)))
    obs.timeline.clear()
    server.serve_reset()
    server.serve_open()
    while server.has_work:
        server.serve_tick()
    ev = obs.timeline.events()
    ticks = [e for e in ev if e["name"] == "serve.tick"]
    assert [e["args"]["tick"] for e in ticks] == \
        list(range(1, len(ticks) + 1))
    children = ("serve.admit", "serve.prefill", "serve.commit",
                "serve.decode_dispatch", "serve.readback", "serve.deliver")
    for name in children:
        kids = [e for e in ev if e["name"] == name]
        assert kids, name
        for kid in kids:
            assert sum(_inside(kid, t) for t in ticks) == 1, kid
    # the dispatch spans hold the program spans
    for prog, parent in (("prefill", "serve.prefill"),
                         ("paged_decode_step", "serve.decode_dispatch")):
        parents = [e for e in ev if e["name"] == parent]
        for e in (e for e in ev if e["name"] == prog):
            assert any(_inside(e, p) for p in parents), prog
    # one identifier per request, on every event of its life
    for name in ("admit", "serve.prefill", "serve.commit",
                 "retire", "request"):
        got = [e["args"]["rid"] for e in ev if e["name"] == name]
        assert got and set(got) == {rid}, (name, got)
    # request = [submit, first token]: it ends at the first token's stamp,
    # which the host takes as the readback of the tick AFTER the commit's
    # returns (the commit tick queues its step behind the chunk and reads
    # the tick before it; the token comes with that step's, one tick on),
    # before the retire instant.  The stamp is wall-clock and the spans are
    # perf_counter: the two agree to some tens of microseconds
    req = next(e for e in ev if e["name"] == "request")
    commit = next(e for e in ev if e["name"] == "serve.commit")
    retire = next(e for e in ev if e["name"] == "retire")
    at = next(i for i, t in enumerate(ticks) if _inside(commit, t))
    assert not any(e["name"] == "serve.readback" and _inside(e, ticks[at])
                   for e in ev)     # nothing was unread in the commit's tick
    readback = next(e for e in ev if e["name"] == "serve.readback"
                    and _inside(e, ticks[at + 1]))
    assert [t["args"]["read"] for t in ticks[at:at + 2]] == ["first",
                                                             "behind"]
    end, slack = req["ts"] + req["dur"], 200
    assert commit["ts"] + commit["dur"] <= end <= retire["ts"] + slack
    assert readback["ts"] - slack <= end \
        <= readback["ts"] + readback["dur"] + slack


def test_fit_step_spans_nest(telemetry):
    telemetry(True)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4), name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.uniform(-1, 1, (12, 8)).astype(np.float32),
                           rng.randint(0, 4, (12,)).astype(np.float32),
                           batch_size=4)
    seen = []
    mod = mx.mod.Module(net, context=mx.cpu())
    obs.timeline.clear()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            batch_end_callback=lambda p: seen.append(p.nbatch))
    ev = obs.timeline.events()
    steps = [e for e in ev if e["name"] == "fit_step"]
    # three batches, and the iteration that found the iterator empty
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    assert seen == [0, 1, 2]
    epoch = next(e for e in ev if e["name"] == "fit_epoch")
    assert all(_inside(s, epoch) for s in steps)
    for name, count in (("input_wait", 3), ("train_step", 3),
                        ("metric_update", 3), ("batch_end_callback", 3)):
        kids = [e for e in ev if e["name"] == name]
        assert len(kids) == count, (name, len(kids))
        for kid, step in zip(kids, steps):
            assert _inside(kid, step), (name, kid, step)
    for kid in (e for e in ev if e["name"] == "host_wait"):
        assert any(_inside(kid, s) for s in steps) or kid["ts"] >= \
            steps[-1]["ts"]


def test_loops_record_no_span_with_telemetry_off(telemetry):
    telemetry(False)
    pred, server = _paged_server()
    server.submit(np.arange(5))
    before = len(obs.timeline)
    assert len(server.run()) == 1
    assert len(obs.timeline) == before
