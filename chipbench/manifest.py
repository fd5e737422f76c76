"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

One cell = one entry of ``workloads``: a configuration (its ``file``), a
traffic mix (``chipbench/traffic/<traffic>.json``) and the per-layer metrics
that list the cell (``chipbench/layer_metrics/<metric>.py``).  Nothing here
knows a cell, a model or a metric by name.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = "chipbench"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# all that `reduced` may name: how many layers are run, or how many experts
# are held here (the chip's share of a stated deployment).  Any other key is
# refused, so that a width under a name nobody foresaw is refused too
REDUCIBLE = re.compile(r"^(\w+_)?(num|n)_(\w+_)?(layers?|experts?)$")
NAMED = re.compile(r"^[A-Za-z_][\w.]*:[A-Za-z_]\w*$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_named(spec):
    """The object a data file names as ``"package.module:attribute"``: a
    configuration's ``builder`` and each of its ``counts``."""
    mod, _, attr = spec.partition(":")
    return getattr(importlib.import_module(mod), attr)


def load_manifest(root=ROOT):
    return load_json(root, "BENCHMARK.json")


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError("%s %r is not in BENCHMARK.json (has: %s)"
                   % (what, name, ", ".join(e["name"] for e in entries)))


def load_cell(name, root=ROOT, manifest=None):
    """Everything one run needs: the workload entry, its configuration's
    sizes, its traffic parameters and the metrics it reports."""
    manifest = manifest or load_manifest(root)
    cell = dict(find(manifest["workloads"], name, "workload"))
    entry = find(manifest["configs"], cell["config"], "config")
    return {
        "cell": cell,
        "config": load_json(root, entry["file"]),
        "traffic": load_json(root, traffic_path(cell["traffic"])),
        "end_to_end": metrics_of(manifest, "end_to_end", name),
        "per_layer": metrics_of(manifest, "per_layer", name),
    }


def traffic_path(traffic):
    return "%s/traffic/%s.json" % (HERE, traffic)


def reader_path(metric):
    return "%s/layer_metrics/%s.py" % (HERE, metric)


def metrics_of(manifest, kind, cell_name):
    """The metrics of ``kind`` that ``cell_name`` reports: those with no
    ``workloads`` key, and those whose ``workloads`` list the cell."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(metric, root=ROOT):
    """The ``read(facts)`` function of a per-layer metric's own file.  Loaded
    by path: a metric's name may hold dots, a module's name may not."""
    path = os.path.join(root, reader_path(metric))
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(metrics, facts, root=ROOT, seconds=None):
    """``{name: {"value", "unit"}}`` for every reader that found something
    to read; a reader that returns None leaves its metric out.  ``seconds``,
    a dict, receives what each reader took."""
    out = {}
    for m in metrics:
        began = time.perf_counter()
        value = load_reader(m["name"], root)(facts)
        if seconds is not None:
            seconds[m["name"]] = time.perf_counter() - began
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def driver_path(driver):
    return "%s/drivers/%s.py" % (HERE, driver)


def config_problems(entry, cfg, drivers):
    """What is wrong with one configuration's file, given the loop drivers
    of the cells that run it: it names its builder, its reference and its
    counts; it states a limit, with the reason, for every driver that
    compares it with the reference; and each key it ``reduced`` is a count
    of layers or of experts (``REDUCIBLE``) and stands in the file at its published value beside the cut (a key
    that ends in ``_<key>``, no greater)."""
    bad, name = [], entry["name"]
    if not NAMED.match(str(cfg.get("builder", ""))):
        bad.append("config %s builder %r" % (name, cfg.get("builder")))
    if not isinstance(cfg.get("reference"), str):
        bad.append("config %s names no reference" % name)
    counts = cfg.get("counts")
    if not isinstance(counts, dict) or not counts or not all(
            NAMED.match(str(v)) for v in counts.values()):
        bad.append("config %s counts %r" % (name, counts))
    stated = cfg.get("limits")
    for driver in sorted(drivers):
        limits = stated.get(driver) if isinstance(stated, dict) else None
        if not isinstance(limits, dict) or not limits:
            bad.append("config %s states no limit for driver %s"
                       % (name, driver))
            continue
        for key, lim in limits.items():
            value = lim.get("value") if isinstance(lim, dict) else None
            if isinstance(value, bool) or not isinstance(
                    value, (int, float)) or value < 0 or not lim.get("why"):
                bad.append("config %s limit %s.%s %r"
                           % (name, driver, key, lim))
    for key in entry["reduced"]:
        if not REDUCIBLE.match(key):
            bad.append("config %s reduces %s: no count of layers or of "
                       "experts" % (name, key))
        cuts = [k for k in cfg if k != key and k.endswith("_" + key)]
        if key not in cfg or not cuts or any(
                not isinstance(cfg[k], int) or cfg[k] > cfg[key]
                for k in cuts):
            bad.append("config %s reduced key %s: the file needs the "
                       "published value and the cut (<prefix>_%s) side by "
                       "side" % (name, key, key))
    return bad


def traffic_problems(cell, traffic, root):
    bad = []
    driver = traffic.get("driver", "")
    if not NAME.match(str(driver)) or not os.path.exists(
            os.path.join(root, driver_path(driver))):
        bad.append("workload %s driver %r" % (cell, driver))
    mesh = traffic.get("mesh")
    if mesh is not None and not (
            isinstance(mesh, dict) and mesh and all(
                isinstance(v, int) and not isinstance(v, bool)
                for v in mesh.values())):
        bad.append("workload %s mesh %r" % (cell, mesh))
    ticks = traffic.get("trace_ticks")
    if ticks is not None and (not isinstance(ticks, int) or ticks < 1):
        bad.append("workload %s trace_ticks %r" % (cell, ticks))
    return bad


def validate(manifest, root=ROOT):
    """Problems with ``manifest`` and the files it names, as a list of
    strings (empty = sound).  The contract's limits that a test can check
    without the chip."""
    bad = []
    if set(manifest) != TOP_KEYS:
        bad.append("top-level keys %s" % sorted(set(manifest) ^ TOP_KEYS))
        return bad
    names = {}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[kind]:
            if not NAME.match(e.get("name", "")):
                bad.append("%s name %r" % (kind, e.get("name")))
            names.setdefault(kind if kind in ("configs", "workloads")
                             else "metrics", []).append(e["name"])
    for kind, have in names.items():
        dup = {n for n in have if have.count(n) > 1}
        if dup:
            bad.append("duplicate %s %s" % (kind, sorted(dup)))
    paths = manifest["paths"]
    under = lambda p: any(p == d or p.startswith(d.rstrip("/") + "/")
                          for d in paths)
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append("run_seconds %r" % manifest["run_seconds"])
    cells = [w["name"] for w in manifest["workloads"]]
    used = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append("config %s keys" % c["name"])
        if not under(c["file"]) or not os.path.exists(
                os.path.join(root, c["file"])):
            bad.append("config file %s" % c["file"])
    files = [c["file"] for c in manifest["configs"]]
    if len(set(files)) != len(files):
        bad.append("two configurations share a file")
    pairs, drivers = set(), {}
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append("workload %s keys" % w["name"])
        if w["chips"] not in (1, 4):
            bad.append("workload %s chips %r" % (w["name"], w["chips"]))
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append("workload %s why" % w["name"])
        if w["config"] not in names["configs"]:
            bad.append("workload %s config %s" % (w["name"], w["config"]))
        if not NAME.match(w["traffic"]) or not os.path.exists(
                os.path.join(root, traffic_path(w["traffic"]))):
            bad.append("workload %s traffic %s" % (w["name"], w["traffic"]))
        else:
            traffic = load_json(root, traffic_path(w["traffic"]))
            bad += traffic_problems(w["name"], traffic, root)
            drivers.setdefault(w["config"], set()).add(
                str(traffic.get("driver")))
        if (w["config"], w["traffic"]) in pairs:
            bad.append("pair %s/%s twice" % (w["config"], w["traffic"]))
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    if used != set(names["configs"]):
        bad.append("unused configs %s" % sorted(set(names["configs"]) - used))
    for c in manifest["configs"]:
        if os.path.exists(os.path.join(root, c["file"])):
            bad += config_problems(c, load_json(root, c["file"]),
                                   drivers.get(c["name"], ()))
    if not 1 <= len(cells) <= 24:
        bad.append("%d cells" % len(cells))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    if four > max(1, len(cells) // 4):
        bad.append("%d four-chip cells of %d" % (four, len(cells)))
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            want = {"name", "unit", "better", "source"} | (
                {"bound"} if kind == "end_to_end" else {"layer", "moves"})
            if set(m) - {"workloads"} != want:
                bad.append("%s keys %s" % (m["name"], sorted(m)))
                continue
            if not UNIT.match(m["unit"]):
                bad.append("%s unit %r" % (m["name"], m["unit"]))
            if m["better"] not in ("lower", "higher"):
                bad.append("%s better" % m["name"])
            if m["source"] not in SOURCES or (
                    kind == "end_to_end" and m["source"]
                    not in ("host_clock", "device_trace")):
                bad.append("%s source %s" % (m["name"], m["source"]))
            for w in m.get("workloads", ()):
                if w not in cells:
                    bad.append("%s lists unknown cell %s" % (m["name"], w))
            if kind == "end_to_end" and not 0 < m["bound"] <= 0.1:
                bad.append("%s bound %r" % (m["name"], m["bound"]))
    for m in manifest["per_layer"]:
        if "moves" not in m:
            continue
        if not os.path.exists(os.path.join(root, reader_path(m["name"]))):
            bad.append("%s has no reader file" % m["name"])
        target = e2e.get(m["moves"])
        if target is None:
            bad.append("%s moves unknown %s" % (m["name"], m["moves"]))
            continue
        for c in m.get("workloads", cells):
            if "workloads" in target and c not in target["workloads"]:
                bad.append("%s moves %s, which cell %s does not report"
                           % (m["name"], m["moves"], c))
    for c in cells:
        if not [m for m in metrics_of(manifest, "end_to_end", c)
                if m["name"] != "setup_s"]:
            bad.append("cell %s reports no end-to-end metric but setup_s" % c)
        if not metrics_of(manifest, "per_layer", c):
            bad.append("cell %s reports no per-layer metric" % c)
    return bad
