"""Device time by what an instruction is: the join of a device trace with
the program's instruction maps.

``chipbench/scopes.py`` says whose an instruction is (its layer's scope);
``mxnet_tpu.obs.programs.instruction_maps()`` also says what it is: its
opcode, its result shape with layout and memory space, whether it computes
anything (``moves``), and, for the copies the compiler makes under no
``mx.`` scope, what they carry (``src``: the entry parameter they descend
from, else their producer's scope) and what they feed (``feeds``: their
nearest scoped consumer): ``{HLO module name: {"source", "conflicts",
"instructions": {name: {...}}}}``, ``source`` saying whether the text was
the dispatched executable's or another compile's.

As ``scopes.by_scope`` does, each ``XLA Ops`` event of the first device is
assigned to the ``XLA Modules`` event that contains it and time goes to the
innermost event, so the rows sum to the device's busy time in the window.
A program without instruction maps (the parent of the PR that added them)
gives None, and the metrics that read this leave their line out.
"""
from __future__ import annotations

import bisect
import json
import os
import re

from . import harness, scopes, trace

TOP = 40
UNSCOPED = scopes.UNSCOPED


def program_maps():
    """``(maps, compiles)``: the running program's instruction maps, or
    None where it has none, and how many backend compiles reading them
    took.  ``scopes.program_maps`` reads the same programs first, counting
    with its one listener; what is left to read here compiles nothing."""
    from mxnet_tpu import obs

    read = getattr(obs.programs, "instruction_maps", None)
    if read is None:
        return None, None
    _, compiles = scopes.program_maps()
    return read(), compiles


def by_instruction(parsed):
    """``{(module stem, instruction name): ns}``: busy time of the first
    device inside the window, each moment to the innermost event."""
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
    return scopes.self_times(events, lo, hi)


def family(src):
    """``state.caches[3][0].scale`` -> ``state.caches[*][*].scale``,
    ``env['layer7_q_weight']`` -> ``env['layer*_q_weight']``: the same
    operand of every layer shares a row."""
    return None if src is None else re.sub(r"\d+", "*", src)


def reduce(times, maps, per):
    """The table of ``times`` (:func:`by_instruction`) joined with
    ``maps``; ``per``, the window's ticks or steps, turns a row's time
    into ms a tick or step."""
    busy = sum(times.values())
    rows, no_map = {}, {}
    moved = unscoped = named = joined = 0
    for (stem, name), ns in times.items():
        entry = maps.get(stem)
        what = entry["instructions"].get(name) if entry else None
        if entry is None:
            no_map[stem] = no_map.get(stem, 0) + ns
        elif what is not None and entry["source"] == "dispatched" \
                and not entry["conflicts"]:
            joined += ns
        if what is None:
            unscoped += ns
            key = (stem, trace.op_stem(name), UNSCOPED, None, None, None)
            size = 0
        else:
            if what["moves"]:
                moved += ns
            if what["scope"] == UNSCOPED:
                unscoped += ns
                if what["src"] or what["feeds"]:
                    named += ns
            key = (stem, what["opcode"], what["scope"], family(what["src"]),
                   what["feeds"], what["shape"])
            size = what["bytes"]
        row = rows.setdefault(key, [0, 0, size, bool(what and what["moves"])])
        row[0] += ns
        row[1] += 1
    share = lambda ns, of: 100.0 * ns / of if of else None
    table = []
    for key, (ns, count, size, moves) in sorted(
            rows.items(), key=lambda kv: -kv[1][0])[:TOP]:
        table.append(dict(
            zip(("module", "opcode", "scope", "src", "feeds", "shape"), key),
            bytes=size, moves=moves, instructions=count,
            ms_window=ns / 1e6, ms_each=ns / 1e6 / per if per else None))
    return {"rows": table, "busy_s": busy / 1e9, "each": per,
            "data_move_pct": share(moved, busy),
            "unscoped_pct": share(unscoped, busy),
            "unscoped_named_pct": share(named, unscoped),
            "join_found_pct": share(joined, busy),
            "no_map_ms": {str(k): v / 1e6 for k, v in sorted(
                no_map.items(), key=lambda kv: -kv[1])},
            "maps": {k: {"source": v["source"], "conflicts": v["conflicts"],
                         "instructions": len(v["instructions"])}
                     for k, v in sorted(maps.items())}}


def table(facts):
    """The cell's device time by what its instructions are, computed once
    per run and kept in ``facts``: the top rows *(module, opcode, scope,
    src, feeds, shape)* with their bytes, the instructions under each, ms a
    window and ms a tick or step (``src`` with its digits struck out, so
    that a layer's operand is one row); the shares the six readers report; the
    modules on the trace with no map; each map's ``source`` and
    ``conflicts``.  None where the program has no instruction maps or the
    trace no device.  Also written to ``chipbench/out/moves-<cell>-<pid>
    .json`` and printed."""
    if "_moves_table" in facts:
        return facts["_moves_table"]
    out = None
    maps, compiles = facts.get("instruction_maps"), None
    if not maps:
        maps, compiles = program_maps()
    parsed = facts.get("trace")
    if maps and parsed and parsed.get("devices"):
        times = by_instruction(parsed)
        if sum(times.values()) > 0:
            out = reduce(times, maps,
                         facts.get("ticks") or facts.get("steps"))
            out["compiles_reading_maps"] = compiles
            _publish(facts, out)
    facts["_moves_table"] = out
    return out


def _publish(facts, out):
    name = facts.get("cell", {}).get("name")
    if name is None:
        return
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    path = os.path.join(harness.OUT_DIR,
                        "moves-%s-%d.json" % (name, os.getpid()))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    fmt = lambda v: None if v is None else round(v, 3)
    print("device time by instruction (%.3f s busy; moves %s %%; unscoped "
          "%s %%, %s %% of it named; %s %% on a dispatched map; %s "
          "compile(s) reading the maps; no map: %s; %s)"
          % (out["busy_s"], fmt(out["data_move_pct"]),
             fmt(out["unscoped_pct"]), fmt(out["unscoped_named_pct"]),
             fmt(out["join_found_pct"]), out["compiles_reading_maps"],
             sorted(out["no_map_ms"]) or "none", path), flush=True)


def pct(facts, key):
    """One share of :func:`table`; None without instruction maps."""
    t = table(facts)
    return None if t is None else t[key]
