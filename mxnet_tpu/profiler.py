"""Profiler facade over the unified telemetry subsystem (mxnet_tpu.obs).

Reference: `src/engine/profiler.{h,cc}` + `python/mxnet/profiler.py` — per-op
engine timestamps dumped as Chrome trace-event JSON.  TPU-native: the span
store is now ``obs.timeline`` (an always-on bounded ring buffer), the loop
counters live in ``obs.registry`` (typed metrics with JSON-lines and
Prometheus exporters), and this module keeps the reference's API as a thin
compatibility facade: ``dump_profile()`` still writes a chrome-trace JSON
of whatever spans were recorded.  The view that joins them with the XLA
device timeline is the ``jax.profiler`` capture ``start()`` makes beside
it (``<filename stem>_xla/``): every ``obs.span`` is mirrored there as a
``TraceAnnotation("mx:<name>")``, on the device trace's own clock.

Thread-safety contract (this module's historical holes, now closed):
``profiler_set_state`` and ``dump_profile`` mutate/read shared state under
the module lock; ``start()`` clears stale events from any prior run; the
span store is bounded the same way the request store always was.
"""
from __future__ import annotations

import os
import threading
import time

from . import obs as _obs
from .obs.metrics import percentile as _nearest_rank

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "Scope", "start", "stop", "record_host_wait", "record_input_wait",
           "record_step", "bump_metric_d2h", "bump_metric_sync",
           "record_request", "record_ckpt_stall", "record_ckpt_write",
           "bump_recovery", "step_stats", "reset_step_stats"]

_state = {"mode": "symbolic", "filename": "profile.json", "running": False,
          "jax_trace": False}
_lock = threading.Lock()

# ---------------------------------------------------------------------------
# Training-loop step accounting (always on — counters only; op-granularity
# span events are recorded only while the profiler runs).  The async fit
# loop reports where the host thread's time went: blocked on device results
# (host_wait), blocked on the input pipeline (input_wait), or free to run
# ahead.  metric_d2h counts device->host array materializations on behalf
# of metrics — the transfers MXNET_METRIC_SYNC_PERIOD exists to eliminate.
# Storage is the obs registry, so the same numbers are scrapeable over
# /metrics and exportable as JSON lines without a second bookkeeping path.
# ---------------------------------------------------------------------------
_R = _obs.registry
_c_steps = _R.counter("mx_steps", "training steps dispatched")
_c_host_wait = _R.counter("mx_host_wait_seconds",
                          "host time blocked on device results")
_c_input_wait = _R.counter("mx_input_wait_seconds",
                           "host time blocked on the input pipeline")
_c_metric_d2h = _R.counter("mx_metric_d2h",
                           "device->host transfers on behalf of metrics")
_c_metric_syncs = _R.counter("mx_metric_syncs",
                             "device metric-accumulator drains")
_c_ckpt_stall = _R.counter("mx_ckpt_stall_seconds",
                           "loop-thread time spent on checkpoint work")
_c_ckpt_writes = _R.counter("mx_ckpt_writes",
                            "committed fence checkpoints")
_g_last_ckpt_ms = _R.gauge("mx_last_ckpt_ms",
                           "duration of the last committed checkpoint write")
_c_recoveries = _R.counter("mx_recoveries",
                           "elastic recovery events (resume/shrink/regrow)")
# per-request serving SLOs (decode.DecodeServer retirements); histograms
# keep a bounded sample reservoir — the cap the old _requests list had
_c_requests = _R.counter("mx_requests", "served requests retired")
_c_req_tokens = _R.counter("mx_request_tokens",
                           "tokens delivered to retired requests")
_h_queue_wait = _R.histogram("mx_request_queue_wait_seconds",
                             "submit -> admission wait per request")
_h_ttft = _R.histogram("mx_request_ttft_seconds",
                       "submit -> first token per request")
_h_decode_rate = _R.histogram(
    "mx_request_decode_tokens_per_sec",
    "post-first-token decode rate per request",
    buckets=(1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 5000, 10000))

_t0 = time.time()

# the families this facade OWNS (and may therefore zero): other
# subsystems' registry series (serve-loop mirrors, liveness gauges, user
# metrics) are not this module's to reset
_OWNED_METRICS = (_c_steps, _c_host_wait, _c_input_wait, _c_metric_d2h,
                  _c_metric_syncs, _c_ckpt_stall, _c_ckpt_writes,
                  _g_last_ckpt_ms, _c_recoveries, _c_requests,
                  _c_req_tokens, _h_queue_wait, _h_ttft, _h_decode_rate)


def _percentile(values, q):
    """Nearest-rank percentile of a sorted list — ``None`` on empty input
    (callers must guard; the historical version raised IndexError)."""
    return _nearest_rank(values, q)


def _loop_span(name, seconds, t0=None, args=None):
    """Always-on loop span (host_wait/input_wait/ckpt_*/request) into the
    bounded timeline; gated only by MXNET_TELEMETRY.  ``t0`` is the
    ``time.perf_counter()`` reading the interval began at; without it
    the span is taken to end now."""
    if _obs.enabled():
        if t0 is None:
            t0 = time.perf_counter() - seconds
        _obs.timeline.add_span(name, t0, seconds, cat="loop", args=args)


def record_host_wait(seconds, t0=None):
    """Time the loop spent blocked on a device result (fence/metric sync)."""
    _c_host_wait.inc(seconds)
    _loop_span("host_wait", seconds, t0)


def record_input_wait(seconds, t0=None):
    """Time the loop spent waiting for the input pipeline's next batch."""
    _c_input_wait.inc(seconds)
    _loop_span("input_wait", seconds, t0)


def record_step(n=1):
    """One (or n) training steps dispatched."""
    _c_steps.inc(n)


def bump_metric_d2h(n=1):
    """n device->host transfers performed on behalf of a metric."""
    _c_metric_d2h.inc(n)


def bump_metric_sync(n=1):
    """n device-accumulator drains (each moves the whole accumulator)."""
    _c_metric_syncs.inc(n)


def record_ckpt_stall(seconds):
    """Time the training loop's host thread spent on checkpointing work
    (elastic fence snapshot + write submission; the ENTIRE save when
    MXNET_CKPT_ASYNC=0).  Feeds ``checkpoint_stall_fraction`` in
    ``step_stats`` — the number async fenced checkpointing exists to
    drive toward zero."""
    _c_ckpt_stall.inc(seconds)
    _loop_span("ckpt_stall", seconds)


def record_ckpt_write(ms):
    """One committed fence checkpoint written (by the writer thread or
    inline): duration in milliseconds."""
    _c_ckpt_writes.inc()
    _g_last_ckpt_ms.set(float(ms))
    _loop_span("ckpt_write", ms / 1e3)


def bump_recovery(n=1):
    """n elastic recovery events (resume-from-checkpoint at startup, or a
    mid-fit mesh shrink/regrow reconfiguration)."""
    _c_recoveries.inc(n)


def record_request(queue_wait_s, ttft_s, tokens, decode_s, rid=None):
    """One served request retired (decode.DecodeServer): time queued
    before admission, time to first token (from submit), tokens
    delivered, and the wall time its post-first-token decode took.  The
    ``request`` span covers [submit, first token] and carries ``rid``,
    the identifier its ``admit`` / ``retire`` events and its
    ``serve.prefill`` / ``serve.commit`` spans share."""
    tokens = int(tokens)
    _c_requests.inc()
    _c_req_tokens.inc(tokens)
    _h_queue_wait.observe(float(queue_wait_s))
    _h_ttft.observe(float(ttft_s))
    if tokens > 1:
        _h_decode_rate.observe((tokens - 1) / max(float(decode_s), 1e-9))
    ttft = max(float(ttft_s), 0.0)
    _loop_span("request", ttft,
               time.perf_counter() - max(float(decode_s), 0.0) - ttft,
               args=None if rid is None else {"rid": rid})


def reset_step_stats():
    """Zero the loop counters and request histograms — a bench's
    measurement window starts here.  Only
    the facade-owned series reset; other subsystems' registry metrics
    (serve-loop mirrors, liveness gauges, user counters) are untouched."""
    global _t0
    with _lock:
        for m in _OWNED_METRICS:
            m.reset()
        _t0 = time.time()
    # the window's opening, on the timeline's clock: a reader of the ring
    # finds the spans recorded since
    _obs.instant("step_stats_reset", cat="loop")


def step_stats():
    """Snapshot of loop accounting plus the derived bench-contract ratios:
    ``input_stall_fraction`` (share of wall time blocked on input) and
    ``host_syncs_per_step`` (metric-driven d2h transfers per step)."""
    with _lock:
        t0 = _t0
    out = {
        "steps": int(_c_steps.get()),
        "host_wait_s": _c_host_wait.get(),
        "input_wait_s": _c_input_wait.get(),
        "metric_d2h": int(_c_metric_d2h.get()),
        "metric_syncs": int(_c_metric_syncs.get()),
        "ckpt_stall_s": _c_ckpt_stall.get(),
        "ckpt_writes": int(_c_ckpt_writes.get()),
        "last_ckpt_ms": _g_last_ckpt_ms.get(),
        "recoveries": int(_c_recoveries.get()),
    }
    wall = max(time.time() - t0, 1e-9)
    out["wall_s"] = wall
    nreq = int(_c_requests.get())
    if nreq:
        out["requests"] = {
            "count": nreq,
            "tokens": int(_c_req_tokens.get()),
            "queue_wait_p50_s": _h_queue_wait.percentile(0.50),
            "queue_wait_p95_s": _h_queue_wait.percentile(0.95),
            "ttft_p50_s": _h_ttft.percentile(0.50),
            "ttft_p95_s": _h_ttft.percentile(0.95),
        }
        if _h_decode_rate.count:
            out["requests"]["decode_tokens_per_sec_p50"] = \
                _h_decode_rate.percentile(0.50)
            out["requests"]["decode_tokens_per_sec_p95"] = \
                _h_decode_rate.percentile(0.95)
    out["input_stall_fraction"] = min(out["input_wait_s"] / wall, 1.0)
    out["host_wait_fraction"] = min(out["host_wait_s"] / wall, 1.0)
    out["checkpoint_stall_fraction"] = min(out["ckpt_stall_s"] / wall, 1.0)
    steps = max(out["steps"], 1)
    out["host_syncs_per_step"] = out["metric_d2h"] / steps
    return out


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Set up the profiler (reference: python/mxnet/profiler.py:10)."""
    with _lock:
        _state["mode"] = mode
        _state["filename"] = filename


def profiler_set_state(state="stop"):
    """'run' or 'stop' (reference: profiler.py:30).  Serialized under the
    module lock — concurrent callers can no longer interleave the
    running-flag flip with the jax trace start/stop."""
    import jax

    with _lock:
        if state == "run" and not _state["running"]:
            # a fresh profile window: stale span events from a prior run
            # must not leak into this run's dump
            _obs.timeline.clear()
            _state["running"] = True
            trace_dir = os.path.splitext(_state["filename"])[0] + "_xla"
            try:
                jax.profiler.start_trace(trace_dir)
                _state["jax_trace"] = True
            except Exception:  # profiling backend unavailable (CPU tests)
                _state["jax_trace"] = False
        elif state == "stop" and _state["running"]:
            _state["running"] = False
            if _state["jax_trace"]:
                _state["jax_trace"] = False
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass


def start():
    profiler_set_state("run")


def stop():
    profiler_set_state("stop")


def is_running():
    return _state["running"]


class Scope:
    """Record one named span into the trace (engine OprExecStat analog).

    Op-granularity spans (imperative dispatch, eager per-node walks) are
    recorded only while the profiler runs — they are high-frequency and
    would otherwise churn the always-on ring; the loop-accounting spans
    above are always on."""

    def __init__(self, name, category="operator"):
        self.name = name
        self.category = category

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if _state["running"] and _obs.enabled():
            _obs.timeline.add_span_ns(self.name, self._t0,
                                      time.perf_counter_ns(),
                                      cat=self.category)
        return False


def dump_profile():
    """Write chrome-trace JSON (reference: profiler.py:46 dump_profile):
    the current timeline ring contents.  The same spans, joined with the
    device's operations on one clock, are in the ``jax.profiler`` capture
    under ``<filename stem>_xla/`` (named ``mx:<span>``)."""
    with _lock:
        _obs.timeline.export(_state["filename"])


# reference env_var.md:71-79 — start profiling at library load
from . import config as _config

if _config.get("MXNET_PROFILER_AUTOSTART"):
    start()
