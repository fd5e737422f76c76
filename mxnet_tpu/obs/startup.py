"""The process's own account of its start, and of every compile.

Two things the rest of telemetry begins too late to see
(docs/observability.md, "Start-up and compiles"):

* **phases of the start** — ``obs.phase(name)`` around the package's own
  import (``import.self``, ``import.jax``, ``import.pallas``), the symbol
  builders, ``Module.bind`` / ``init_params`` / ``init_optimizer`` and the
  serving constructors (``build.*``): a timeline span of ``cat="setup"``
  whose seconds also go to the gauge family ``mx_setup_seconds{phase}``.
  As the loop's first top span opens (``serve.tick`` / ``fit_step``,
  :func:`top_span`) the account is closed: ``until_loop`` is the
  process's age at that moment, ``compile`` the compile stages booked so
  far under a program's name or ``(eager)``, ``outside`` what is left once
  every ``import*`` / ``build*`` phase and ``compile`` are taken out (the
  interpreter's start, the embedding program's own work, the backend's
  start), so the parts add up to ``until_loop``;
* **compile stages, by program** — the one place that listens to
  ``jax.monitoring``: tracing, lowering, backend compiles and persistent-
  cache reads land in ``mx_compile_seconds{program, stage}`` and
  ``mx_compiles_total{program, cache}``, and on the timeline as a span
  ``compile.<stage>`` ending at the event, so a compile inside a
  ``serve.tick`` is that tick's child.  ``program`` is the
  ``obs.program_span`` open on the calling thread, else ``(eager)`` under
  a phase or a top span, else ``(outside)``.

Nothing is counted twice: an interval (a phase or a stage) books what the
intervals that closed inside it on the same thread have not (:func:`_own`),
so a nested trace, a compile inside ``build.predictor`` and
``import.pallas`` inside a trace each take their seconds out of what
holds them.  Host-side only; with ``MXNET_TELEMETRY=0`` none of it is
recorded.
"""
from __future__ import annotations

import functools
import os
import sys
import threading
import time

from .. import obs as _o      # read when called: obs imports this module
from .trace import _LiveSpan, _annotation

__all__ = ["phase", "phased", "top_span", "pallas", "backend_compiles",
           "EAGER", "OUTSIDE"]

EAGER = "(eager)"
OUTSIDE = "(outside)"
# a stage's start is its end less a duration jax took on another clock:
# an interval that began this close before it still counts as inside it
SLACK_NS = 200_000
_KEEP = 4096        # closed intervals a thread keeps for a later parent
# a jit called while another is traced reports a trace of its own, some
# microseconds of cache lookup to some hundreds for a small jnp function,
# hundreds to thousands a program: left where they fall, in the trace
# that holds them
TRACE_FLOOR_S = 1e-3

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Thread(threading.local):
    program = None      # the open obs.program_span's name
    top = False         # a top span of the loop is open
    phases = 0          # open phases
    hit = None          # seconds of a cache read whose compile event is due
    done = None         # [(start_ns, ns)] closed, in no parent yet


_tls = _Thread()
_state = {"listening": False, "backend_compiles": 0, "loop_open": False,
          "born_ns": None}


def _families():
    reg = _o.registry
    return (reg.gauge("mx_setup_seconds",
                      "seconds of the process's start, by phase",
                      labels=("phase",)),
            reg.counter("mx_compile_seconds",
                        "seconds in jax's compile stages, by the program "
                        "that caused them", labels=("program", "stage")),
            reg.counter("mx_compiles_total",
                        "backend compiles and persistent-cache reads, by "
                        "program", labels=("program", "cache")))


def _own(t0_ns, t1_ns):
    """ns of ``[t0, t1)`` that no interval closed inside it on this thread
    has booked already; the interval then waits for a parent of its own.
    Intervals close in order of their ends, so the ones inside this one are
    the list's tail."""
    done = _tls.done
    if done is None:
        done = _tls.done = []
    inner = 0
    while done and done[-1][0] >= t0_ns - SLACK_NS:
        inner += done.pop()[1]
    done.append((t0_ns, t1_ns - t0_ns))
    if len(done) > _KEEP:
        del done[:_KEEP // 2]
    return max(t1_ns - t0_ns - inner, 0)


# ---------------------------------------------------------------------------
# phases of the start
# ---------------------------------------------------------------------------
class _Phase:
    """One phase: a ``cat="setup"`` span whose own seconds (less what
    closed inside it) are added to ``mx_setup_seconds{phase}``.  With
    ``program`` the thread's compile stages are booked under that name
    while it is open, as under a ``program_span``: for a phase that traces
    a program's graph and dispatches nothing (the paged shape probe)."""

    __slots__ = ("_name", "_t0", "_ann", "_program", "_outer")

    def __init__(self, name, mirror=True, t0_ns=None, program=None):
        self._name = name
        self._t0 = t0_ns        # a phase that began before it could say so
        self._ann = _annotation(name, None) if mirror else None
        self._program = program

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        _tls.phases += 1
        if self._program is not None:
            self._outer, _tls.program = _tls.program, self._program
        if self._t0 is None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _tls.phases -= 1
        if self._program is not None:
            _tls.program = self._outer
        own = _own(self._t0, t1) * 1e-9
        setup = _families()[0]
        setup.labels(phase=self._name).inc(own)
        if _state["loop_open"]:
            # booked after the account was closed (import.pallas inside
            # the first tick's trace, a second server): the parts then add
            # up to until_loop + after_loop
            setup.labels(phase="after_loop").inc(own)
        _o.timeline.add_span_ns(self._name, self._t0, t1, cat="setup")
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def phase(name, mirror=True, program=None):
    """Context manager around one phase of the start (no-op when
    telemetry is off).  ``mirror=False`` where jax may not be imported
    yet: the ``mx:<name>`` annotation would import it."""
    return _Phase(name, mirror, program=program) if _o.enabled() \
        else _o._NULL


def phased(name, program=None):
    """Decorator: the function's body is phase ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with phase(name, program=program):
                return fn(*args, **kwargs)
        return inner
    return wrap


def _imported(name, *modules):
    """Import ``modules`` under phase ``name`` unless the process holds
    them already."""
    if all(m in sys.modules for m in modules):
        return
    import importlib

    with phase(name, mirror=False):
        for m in modules:
            importlib.import_module(m)


def pallas(*more):
    """``(pl, pltpu)``: ``jax.experimental.pallas`` and its TPU module,
    where a kernel's function wants them (a module-level import would
    charge every process their seconds).  The process's first call is
    phase ``import.pallas``, inside whatever trace asked for the kernel;
    ``more`` names further modules of the family to import with them."""
    _imported("import.pallas", "jax.experimental.pallas",
              "jax.experimental.pallas.tpu", *more)
    return (sys.modules["jax.experimental.pallas"],
            sys.modules["jax.experimental.pallas.tpu"])


def _born_ns():
    """The process's start on ``perf_counter_ns``'s clock, from the
    kernel's record; None where ``/proc`` does not say."""
    if _state["born_ns"] is None:
        try:
            with open("/proc/self/stat") as f:
                ticks = int(f.read().rsplit(")", 1)[1].split()[19])
            with open("/proc/uptime") as f:
                uptime = float(f.read().split()[0])
            now = time.perf_counter_ns()
        except (OSError, ValueError, IndexError):
            return None
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        _state["born_ns"] = now - int(age * 1e9)
    return _state["born_ns"]


def begin_import(t0_ns):
    """``mxnet_tpu/__init__.py``'s first line: opens ``import.self`` as of
    ``t0_ns``, books ``before_import``, and takes ``import jax`` out as
    ``import.jax`` where this process has not imported it.  Returns the
    open phase; the last line closes it."""
    if not _o.enabled():
        return _o._NULL
    whole = _Phase("import.self", mirror=False, t0_ns=t0_ns)
    whole.__enter__()
    born = _born_ns()
    if born is not None:
        _families()[0].labels(phase="before_import").set(
            max(t0_ns - born, 0) * 1e-9)
    _imported("import.jax", "jax", "jax.numpy")
    install()
    return whole


# ---------------------------------------------------------------------------
# the loop's top span: closes the account, and names eager compiles
# ---------------------------------------------------------------------------
def _close_account(now_ns):
    """Once, as the first top span opens: ``until_loop``, ``compile`` and
    ``outside``.  The parts add up where the start ran on one thread, as
    both drivers' does: the families are the process's, so a phase or a
    compile on a second thread before the loop overlaps ``until_loop`` and
    is taken out of ``outside`` all the same."""
    _state["loop_open"] = True
    born = _born_ns()
    if born is None:
        return
    setup, seconds, _ = _families()
    until = (now_ns - born) * 1e-9
    named = sum(s.value for labels, s in setup.series()
                if labels[0].startswith(("import", "build")))
    compiled = sum(s.value for labels, s in seconds.series()
                   if labels[0] != OUTSIDE)
    setup.labels(phase="until_loop").set(until)
    setup.labels(phase="compile").set(compiled)
    setup.labels(phase="outside").set(max(until - named - compiled, 0.0))


class _TopSpan(_LiveSpan):
    """``_LiveSpan`` that says so on its thread while it is open."""

    __slots__ = ()

    def __enter__(self):
        _tls.top = True
        super().__enter__()
        if not _state["loop_open"]:
            _close_account(self._t0)
        return self

    def __exit__(self, *exc):
        _tls.top = False
        return super().__exit__(*exc)


def top_span(name, cat, args=None):
    """``obs.span`` for the loop's top span (``serve.tick``,
    ``fit_step``): the first to open closes the start's account, and
    while one is open an unnamed compile on its thread is ``(eager)``."""
    return _TopSpan(_o.timeline, name, cat, args, True) \
        if _o.enabled() else _o._NULL


# ---------------------------------------------------------------------------
# compile stages, by program: the one listener
# ---------------------------------------------------------------------------
def _on_duration(event, seconds, fun_name=None, **_):
    stage = _STAGES.get(event)
    if stage is None:
        if event == _RETRIEVAL:
            _tls.hit = seconds
        return
    if stage == "compile":
        _state["backend_compiles"] += 1
    elif stage == "trace" and seconds < TRACE_FLOOR_S:
        return
    if not _o.enabled():
        _tls.hit = None
        return
    now = time.perf_counter_ns()
    program = _tls.program or (
        EAGER if _tls.top or _tls.phases else OUTSIDE)
    _, book, count = _families()
    args = {"program": program, "fun": fun_name}
    if stage == "compile":
        # on a cache hit jax's backend-compile duration holds the read
        # (and the key's hashing): all of it is what the cache cost
        read, _tls.hit = _tls.hit, None
        if read is not None:
            stage = "cache_read"
            args["retrieval_s"] = read
        count.labels(program, "miss" if read is None else "hit").inc()
    t0 = now - int(seconds * 1e9)
    book.labels(program, stage).inc(_own(t0, now) * 1e-9)
    _o.timeline.add_span_ns("compile." + stage, t0, now, cat="compile",
                            args=args)


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _tls.hit = 0.0


def install():
    """Register the two listeners, once a process."""
    if not _state["listening"]:
        import jax

        _state["listening"] = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def backend_compiles():
    """Backend compiles this process has made (a persistent-cache read
    counts: it loads a program too), for the map readers."""
    install()
    return _state["backend_compiles"]
