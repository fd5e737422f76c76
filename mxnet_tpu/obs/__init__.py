"""mxnet_tpu.obs — the unified telemetry subsystem.

Three layers (docs/observability.md), shared process-wide singletons:

* :data:`registry` — the typed/labeled metrics registry
  (:mod:`~mxnet_tpu.obs.metrics`): counters, gauges, histograms behind
  one lock, with JSON-lines and Prometheus exporters;
* :data:`timeline` — the always-on trace timeline
  (:mod:`~mxnet_tpu.obs.trace`): a bounded ring buffer of thread-aware
  spans and instant events, exported as Chrome-trace JSON (Perfetto);
* :data:`programs` — the programs that ran
  (:mod:`~mxnet_tpu.obs.program_maps`): a lazy reader of each one's
  optimized HLO and its scope map (:mod:`~mxnet_tpu.obs.scopes`: which
  layer every instruction belongs to).

The process's own start and every compile are accounted for by
:mod:`~mxnet_tpu.obs.startup` (``obs.phase`` / ``obs.top_span``, the one
``jax.monitoring`` listener).

``profiler`` (the historical module) is a thin compatibility facade over
these; new code records here directly.  Instrumentation is HOST-side
only: nothing in this package runs inside a traced program (the layer
scopes are HLO metadata, and always there), so compiled HLO is
byte-identical with telemetry on or off (``MXNET_TELEMETRY``),
and the zero-overhead tripwire in ``tests/test_obs.py`` plus the
analysis ``host-sync`` pass keep it that way.
"""
from __future__ import annotations

import time

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      PeriodicExporter, percentile)
from .prom import MetricsServer
from .program_maps import ProgramMaps
from .trace import TraceTimeline, _annotation

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "MetricsServer",
    "PeriodicExporter", "ProgramMaps", "TraceTimeline",
    "enabled", "mirror", "percentile", "phase", "phased",
    "program_span", "programs", "registry",
    "serve_metrics", "span", "timeline", "top_span",
]

from .. import config as _config

# ---------------------------------------------------------------------------
# process-wide singletons
# ---------------------------------------------------------------------------
registry = MetricsRegistry()
timeline = TraceTimeline(capacity=max(int(_config.get("MXNET_TRACE_BUFFER")),
                                      1))
programs = ProgramMaps()


def enabled():
    """Whether telemetry recording is armed (``MXNET_TELEMETRY``).
    Counters predating the subsystem (``profiler.step_stats``'s loop
    accounting) stay on regardless; this gates the timeline spans /
    instant events and the per-program dispatch spans."""
    return bool(_config.get("MXNET_TELEMETRY"))


# ---------------------------------------------------------------------------
# no-op-when-disabled recording helpers (the instrumentation surface the
# rest of the framework calls — one isinstance-free fast path each)
# ---------------------------------------------------------------------------
class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()

from .startup import _tls, phase, phased, top_span  # noqa: E402


class _ProgramSpan:
    """Times one compiled-program dispatch: a span on the timeline
    (cat="program").  While it is open the thread's compile stages are
    booked under its name (:mod:`~mxnet_tpu.obs.startup`)."""

    __slots__ = ("_name", "_t0", "_ann", "_outer")

    def __init__(self, name):
        self._name = name
        self._ann = _annotation(name, None)

    def __enter__(self):
        self._ann.__enter__()
        self._outer = _tls.program
        _tls.program = self._name
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _tls.program = self._outer
        timeline.add_span_ns(self._name, self._t0, t1, cat="program")
        self._ann.__exit__(*exc)
        return False


def program_span(name):
    """Context manager timing one dispatch of program ``name`` (no-op
    when telemetry is off)."""
    return _ProgramSpan(name) if enabled() else _NULL


def span(name, cat="host", args=None):
    """Context manager recording one timeline span, mirrored into the
    profiler's trace as ``mx:<name>`` (no-op when off)."""
    return timeline.span(name, cat=cat, args=args, mirror=True) \
        if enabled() else _NULL


def mirror(name):
    """The ``mx:<name>`` annotation alone, for an interval whose timeline
    span is recorded after the fact (``profiler.record_input_wait``): the
    profiler's own trace then holds it too (no-op when off)."""
    return _annotation(name, None) if enabled() else _NULL


def instant(name, cat="event", args=None):
    """Record one timeline instant event (no-op when off)."""
    if enabled():
        timeline.instant(name, cat=cat, args=args)


# ---------------------------------------------------------------------------
# process-wide HTTP exporters — the registry/timeline are process-global,
# so one server per (host, port) is the correct cardinality; a second
# DecodeServer configured for the same port must REUSE the first server,
# not crash on EADDRINUSE
# ---------------------------------------------------------------------------
import threading as _threading

_servers = {}
_servers_lock = _threading.Lock()


def serve_metrics(port, host="127.0.0.1"):
    """Get-or-create the process-wide :class:`MetricsServer` bound to
    ``(host, port)``, serving the global registry and timeline."""
    key = (host, int(port))
    with _servers_lock:
        srv = _servers.get(key)
        if srv is None or srv._httpd is None:
            srv = MetricsServer(port=int(port), host=host).start()
            _servers[key] = srv
        return srv


# ---------------------------------------------------------------------------
# env-armed periodic JSON-lines export
# ---------------------------------------------------------------------------
_exporter = None


def _maybe_start_exporter():
    global _exporter
    path = _config.get("MXNET_METRICS_EXPORT")
    period = float(_config.get("MXNET_METRICS_EXPORT_PERIOD"))
    if _exporter is None and path and period > 0:
        _exporter = PeriodicExporter(registry, path, period).start()
    return _exporter


_maybe_start_exporter()
