"""What both loop drivers share: the clock a run starts on, the compile
counters, the measured window's opening and closing rites, and the side
file.  Nothing here knows a cell by name.
"""
from __future__ import annotations

import gc
import json
import os
import time

from . import manifest

OUT_DIR = os.path.join(manifest.ROOT, manifest.HERE, "out")


def process_age_s():
    """Seconds since this process was started, from the kernel's record:
    set-up time includes the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Phases:
    """Where set-up time went: ``mark(name)`` closes the phase that began at
    the previous mark (the first began with the process)."""

    def __init__(self):
        self._born = time.perf_counter() - process_age_s()
        self._last = self._born
        self.seconds = {}

    def mark(self, name):
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now

    def since_start(self, now):
        return now - self._born


class CompileCounters:
    """jax's own monitoring events: seconds in backend compiles (a
    persistent-cache read counts, as the time to fetch it), how often the
    cache answered, and how many compiles fell inside the open window."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.in_window = 0
        self.window_open = False

    def install(self):
        import jax

        def on_duration(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += seconds
                self.compiles += 1
                if self.window_open:
                    self.in_window += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self


def build_symbol(cfg, **overrides):
    """The system's own model builder, named by the configuration file and
    fed the configuration's sizes."""
    builder = manifest.load_named(cfg["builder"])
    kwargs = {arg: cfg[key] for arg, key in cfg["symbol_args"].items()}
    for k, v in kwargs.items():
        if isinstance(v, list):
            kwargs[k] = tuple(v)
    kwargs.update({k: v for k, v in overrides.items() if v is not None})
    return builder(**kwargs)


def quiesce():
    """Before the opening fence: collect now, then freeze what survives, so
    that no collection of the benchmark's own falls inside the window."""
    gc.collect()
    gc.freeze()


class Tracer:
    """The profiler around the window of a ``--trace 1`` run; inert when
    ``on`` is false.  ``start`` comes before the window's first clock
    reading and ``stop`` after its last."""

    def __init__(self, on, name):
        self.on = bool(on)
        self.dir = os.path.join(OUT_DIR, "trace-%s-%d" % (name, os.getpid()))
        self._window = None
        self.parsed = None
        self.trace_bytes = None

    def start(self):
        if not self.on:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call python events
        opts.host_tracer_level = 2
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("chipbench:window")
        self._window.__enter__()

    def span(self, what):
        import jax

        return jax.profiler.TraceAnnotation("chipbench:" + what)

    def stop(self, phases):
        """Close the trace and keep it, reduced (``trace.parse``'s dict), as
        ``self.parsed``.  Called right after the window's closing clock
        reading, so that the trace holds the window and little else.  What
        stopping and what loading cost go to ``phases``."""
        if not self.on:
            return
        import shutil

        import jax

        from . import trace

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        phases.mark("trace_stop")
        path = trace.find_xplane(self.dir)
        self.trace_bytes = os.path.getsize(path)
        self.parsed = trace.load(path)
        phases.mark("trace_load")
        if os.environ.get("CHIPBENCH_KEEP_TRACE"):
            print("trace kept in %s" % self.dir, flush=True)
        else:
            shutil.rmtree(self.dir, ignore_errors=True)


class MemoryPeak:
    """Peak device memory, sampled outside the window.  On the TPU the
    runtime counts a loaded program's scratch memory as *reserved*, apart
    from the buffers *in use* (ResNet-50's step: 1.2 GB in use, 10.2 GB
    reserved), and keeps a peak of each but not of their sum.  So the
    harness reads ``bytes_in_use + bytes_reserved`` when the window opens
    and when it has closed, while the step's program is loaded, and reports
    the largest reading or ``peak_bytes_in_use``, whichever is greater, on
    the fullest chip."""

    def __init__(self, chips):
        self.chips = int(chips)
        self.bytes = 0

    def _stats(self):
        import jax

        return [d.memory_stats() or {} for d in jax.devices()[:self.chips]]

    def sample(self):
        for st in self._stats():
            self.bytes = max(self.bytes, int(st.get("bytes_in_use", 0))
                             + int(st.get("bytes_reserved", 0)))

    def peak(self):
        self.sample()
        return max([self.bytes] + [int(st.get("peak_bytes_in_use", 0))
                                   for st in self._stats()])


def write_side_file(workload, seed, payload):
    """``chipbench/out/<workload>-<seed>-<pid>.json``: where in the window
    the time went.  No part of the contract line."""
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "%s-%d-%d.json"
                        % (workload, int(seed), os.getpid()))
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def program_counters(since=None):
    """``{"name{label=value,...}": count}`` of the program's own labelled
    counters (``obs.registry``) that have counted anything: which path a
    dispatch took, by its label.  With ``since``, an earlier reading, only
    what was counted after it: a driver reads once as it starts and again
    as its window opens (before the step statistics' reset zeroes them), so
    the side file holds this run's counts whatever the process did before."""
    from mxnet_tpu import obs

    out = {}
    for name, fam in obs.registry.snapshot().items():
        if fam["type"] != "counter" or not fam["label_names"]:
            continue
        for row in fam["series"]:
            labels = ",".join("%s=%s" % kv
                              for kv in sorted(row["labels"].items()))
            key = "%s{%s}" % (name, labels)
            value = row["value"] - (since or {}).get(key, 0)
            if value:
                out[key] = value
    return out


def gc_counts():
    return [s["collections"] for s in gc.get_stats()]
