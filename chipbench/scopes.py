"""Device time by layer: the join of a device trace with the program's own
scope maps.

A device trace names an operation by its HLO instruction (``fusion.123``),
the program names it by layer (``jax.named_scope("mx.<layer>/...")``, kept
as ``op_name`` metadata), and ``mxnet_tpu.obs.programs.scope_maps()`` reads
the second off the optimized HLO of the very executables that ran: ``{HLO
module name: {instruction name: "<layer>[/<sub>]"}}``.  Instruction names
are unique within one module only, so each ``XLA Ops`` event of the first
device is first assigned to the ``XLA Modules`` event that contains it; the
module's stem (``jit_step``) picks the map.

Nested events (a ``while`` around its body) count once: time goes to the
innermost event, so the layers sum to the device's busy time in the window.
A program without scope maps (the parent of the PR that added them) gives
None, and the metrics that read this leave their line out.
"""
from __future__ import annotations

import bisect
import json
import os

from . import harness, trace

UNSCOPED = "unscoped"


_compiles = {"seen": 0, "listening": False}


def _on_compile(event, _seconds, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["seen"] += 1


def program_maps():
    """``(maps, compiles)``: the running program's scope maps, or None where
    it has none, and how many backend compiles reading them took.  The maps
    are read off executables that are already loaded; a compile here would
    load a program a second time, so it is counted and shown."""
    import jax

    from mxnet_tpu import obs

    read = getattr(obs.programs, "scope_maps", None)
    if read is None:
        return None, None
    if not _compiles["listening"]:
        _compiles["listening"] = True
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    before = _compiles["seen"]
    maps = read()
    return maps, _compiles["seen"] - before


def self_times(events, lo, hi):
    """``{key: ns}`` for ``events`` ``[(key, start, end)]`` sorted by start:
    the time inside ``[lo, hi)`` in which ``key``'s event is the innermost
    one running.  Sums to the length of the union of the events there."""
    out, stack, t = {}, [], lo

    def advance(to):
        nonlocal t
        to = min(max(to, lo), hi)
        if to > t:
            if stack:
                key = stack[-1][0]
                out[key] = out.get(key, 0) + to - t
            t = to

    for key, s, e in events:
        if e <= lo or s >= hi:
            continue
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((key, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


def by_scope(parsed, maps):
    """Busy time of the first device inside the window, by scope:
    ``{"scopes": {"<layer>[/<sub>]": ns}, "busy_ns", "found_ns" (time of
    instructions some map lists), "unscoped_kinds": {HLO kind: ns}}``."""
    lo, hi = trace.window_of(parsed)
    first = parsed["devices"][sorted(parsed["devices"])[0]]
    modules = first.get(trace.MODULES_LINE, [])
    starts = [s for _, s, _ in modules]
    events = []
    for name, s, d in first[trace.OPS_LINE]:
        i = bisect.bisect_right(starts, s) - 1
        stem = None
        if i >= 0 and s < modules[i][1] + modules[i][2]:
            stem = trace.module_stem(modules[i][0])
        events.append(((stem, name), s, s + d))
    scopes, kinds, busy, found = {}, {}, 0, 0
    for (stem, name), ns in self_times(events, lo, hi).items():
        scope = maps.get(stem, {}).get(name)
        busy += ns
        if scope is not None:
            found += ns
        if scope is None or scope == UNSCOPED:
            scope = UNSCOPED
            kind = trace.op_stem(name)
            kinds[kind] = kinds.get(kind, 0) + ns
        scopes[scope] = scopes.get(scope, 0) + ns
    return {"scopes": scopes, "busy_ns": busy, "found_ns": found,
            "unscoped_kinds": kinds}


def layer_of(scope):
    return scope.split("/", 1)[0]


def table(facts):
    """The cell's device time by layer, computed once per run and kept in
    ``facts``: ``{"layers": {layer: pct}, "scopes": {scope: pct},
    "found_pct", "unscoped_kinds": {kind: pct}, "busy_s"}``, or None where
    the program has no scope maps or the trace no device.  Also written to
    ``chipbench/out/layers-<cell>-<pid>.json`` and printed, so that a traced
    run shows every layer and not only those with a metric."""
    if "_layer_table" in facts:
        return facts["_layer_table"]
    out = None
    maps, compiles = facts.get("scope_maps"), None
    if not maps:
        maps, compiles = program_maps()
    parsed = facts.get("trace")
    if maps and parsed and parsed.get("devices"):
        raw = by_scope(parsed, maps)
        busy = raw["busy_ns"]
        if busy > 0:
            pct = lambda d: {k: 100.0 * v / busy for k, v in
                             sorted(d.items(), key=lambda kv: -kv[1])}
            layers = {}
            for scope, ns in raw["scopes"].items():
                layers[layer_of(scope)] = layers.get(layer_of(scope), 0) + ns
            out = {"layers": pct(layers), "scopes": pct(raw["scopes"]),
                   "found_pct": 100.0 * raw["found_ns"] / busy,
                   "unscoped_kinds": pct(raw["unscoped_kinds"]),
                   "busy_s": busy / 1e9, "modules": sorted(maps),
                   "compiles_reading_maps": compiles}
            _publish(facts, out)
    facts["_layer_table"] = out
    return out


def _publish(facts, out):
    name = facts.get("cell", {}).get("name")
    if name is None:
        return
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    path = os.path.join(harness.OUT_DIR,
                        "layers-%s-%d.json" % (name, os.getpid()))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("device time by layer (%% of %.3f s busy; %.4f %% of it in a "
          "scope map; %s compile(s) reading the maps; %s): %s"
          % (out["busy_s"], out["found_pct"], out["compiles_reading_maps"],
             path, json.dumps({k: round(v, 2)
                               for k, v in out["layers"].items()})),
          flush=True)


def layer_pct(facts, layer):
    """Share of the window's device busy time under ``mx.<layer>`` (with
    its sub-scopes); 0 where the layer ran nothing, None without maps."""
    t = table(facts)
    return None if t is None else t["layers"].get(layer, 0.0)
