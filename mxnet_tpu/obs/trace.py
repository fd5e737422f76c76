"""Always-on trace timeline — bounded ring buffer, Chrome-trace export.

The telemetry subsystem's second layer (docs/observability.md): a span /
instant-event API whose storage is a fixed-capacity ring buffer
(``MXNET_TRACE_BUFFER`` events, oldest evicted first), so leaving it
armed in production costs one deque append per event and bounded memory
— the always-on property the old profiler's unbounded ``events`` list
could not offer.

Events are thread-aware (every record carries the writing thread's id,
so the fit loop, the checkpoint writer, prefetch workers and a serving
loop interleave legibly) and nest naturally: complete ("X") events with
overlapping [ts, ts+dur) on one thread render as a flame stack in any
Chrome-trace viewer.  :meth:`TraceTimeline.export` writes the standard
``{"traceEvents": [...]}`` JSON — open it at ``chrome://tracing`` or
https://ui.perfetto.dev.

One clock: every stamp is ``time.perf_counter_ns()`` (monotonic; ``ts``
is that reading in whole microseconds).  The framework's live spans
(``obs.span`` / ``obs.program_span``) also enter a
``jax.profiler.TraceAnnotation("mx:<name>")``, so a profiler session —
``profiler.start()`` or anyone's ``jax.profiler.trace`` — holds the same
spans in its own ``.xplane.pb`` beside the device's operations, on the
device trace's clock by construction.  With no session active the
annotation costs a flag test.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

__all__ = ["TraceTimeline", "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = 65536


class TraceTimeline:
    """Bounded, thread-safe event ring buffer in Chrome-trace form."""

    def __init__(self, capacity=DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._buf = deque(maxlen=int(capacity))
        self._total = 0  # events ever added (dropped = total - len)

    @property
    def capacity(self):
        return self._buf.maxlen

    @property
    def dropped(self):
        """Events evicted by the ring bound since the last clear."""
        with self._lock:
            return max(0, self._total - len(self._buf))

    def __len__(self):
        with self._lock:
            return len(self._buf)

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------
    def _push(self, ev):
        with self._lock:
            self._buf.append(ev)
            self._total += 1

    def add_span(self, name, t0, dur, cat="host", tid=None, args=None):
        """One complete ("X") event: ``t0`` a ``time.perf_counter()``
        reading in seconds, ``dur`` seconds.  For spans recorded after
        the fact (``profiler.record_host_wait`` knows the duration only
        after the wait); live spans stamp nanoseconds themselves."""
        self.add_span_ns(name, int(t0 * 1e9), int((t0 + dur) * 1e9),
                         cat=cat, tid=tid, args=args)

    def add_span_ns(self, name, t0_ns, t1_ns, cat="host", tid=None,
                    args=None):
        """One complete event between two ``time.perf_counter_ns()``
        readings.  Both ends are floored to microseconds separately, so
        a span that contains another in nanoseconds still does."""
        ts = t0_ns // 1000
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": ts, "dur": max(t1_ns // 1000 - ts, 0),
              "pid": os.getpid(),
              "tid": tid if tid is not None else threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def instant(self, name, cat="event", args=None, scope="t"):
        """One instant ("i") event — elastic shrink/regrow, checkpoint
        commits, COW forks, admissions/retirements, prefill-chunk
        windows.  ``scope`` "t"=thread, "p"=process, "g"=global."""
        ev = {"name": name, "cat": cat, "ph": "i", "s": scope,
              "ts": time.perf_counter_ns() // 1000, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def span(self, name, cat="host", args=None, mirror=False):
        """Context manager recording one complete event around the body
        (nests: inner spans on the same thread stack in the viewer).
        ``mirror`` also enters ``TraceAnnotation("mx:<name>", **args)``
        for the profiler's own trace."""
        return _LiveSpan(self, name, cat, args, mirror)

    # ------------------------------------------------------------------
    def events(self):
        """A consistent copy of the current ring contents."""
        with self._lock:
            return list(self._buf)

    def clear(self):
        with self._lock:
            self._buf.clear()
            self._total = 0

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def export(self, path=None, extra_events=None):
        """The Chrome-trace payload dict; written as JSON to ``path``
        when given.  The ring only: the view joined with the device's
        operations is the profiler's own file, which holds the same
        spans as ``mx:<name>`` (see the module docstring)."""
        events = self.events()
        if extra_events:
            events.extend(extra_events)
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(payload, f)
        return payload


_annotation_cls = None


def _annotation(name, args):
    """``jax.profiler.TraceAnnotation("mx:<name>", **args)``; jax is
    imported on first use, so the ring alone stays jax-free."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls("mx:" + name, **args) if args \
        else _annotation_cls("mx:" + name)


class _LiveSpan:
    __slots__ = ("_tl", "_name", "_cat", "_args", "_t0", "_ann")

    def __init__(self, timeline, name, cat, args, mirror):
        self._tl = timeline
        self._name = name
        self._cat = cat
        self._args = args
        self._ann = _annotation(name, args) if mirror else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._tl.add_span_ns(self._name, self._t0, time.perf_counter_ns(),
                             cat=self._cat, args=self._args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False
