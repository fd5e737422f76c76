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


def test_chrome_trace_schema_and_jax_merge(tmp_path):
    import gzip

    tl = TraceTimeline(capacity=1024)
    with tl.span("outer", cat="loop", args={"epoch": 0}):
        with tl.span("inner"):
            pass
        tl.instant("commit", cat="elastic", args={"step": 3})
    t = threading.Thread(target=lambda: tl.add_span("other-thread", 0.0,
                                                    1e-3))
    t.start()
    t.join()
    # a fake jax.profiler capture to merge
    jax_dir = tmp_path / "xla" / "plugins" / "host"
    jax_dir.mkdir(parents=True)
    with gzip.open(str(jax_dir / "h.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": [
            {"name": "xla-op", "ph": "X", "ts": 1, "dur": 2,
             "pid": 1, "tid": 1}]}, f)
    out = str(tmp_path / "trace.json")
    tl.export(out, jax_trace_dir=str(tmp_path / "xla"))
    payload = json.load(open(out))
    events = payload["traceEvents"]
    assert {"outer", "inner", "commit", "other-thread", "xla-op"} \
        <= {e["name"] for e in events}
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
    # merged jax.profiler events may be metadata records without a name
    names = {e.get("name") for e in json.load(open(fname))["traceEvents"]}
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
    # both programs really were instrumented: their dispatch wall landed
    # in the roofline accounting while telemetry was on
    rows = {r["program"] for r in obs.programs.table()}
    assert {"train_step", "decode_step"} <= rows


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
