"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

One cell = one entry of ``workloads``: a configuration (its ``file``), a
traffic mix (``chipbench/traffic/<traffic>.json``) and the per-layer metrics
that list the cell (``chipbench/layer_metrics/<metric>.py``).  Nothing here
knows a cell, a model or a metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = "chipbench"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


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


def read_layer_metrics(metrics, facts, root=ROOT):
    """``{name: {"value", "unit"}}`` for every reader that found something
    to read; a reader that returns None leaves its metric out."""
    out = {}
    for m in metrics:
        value = load_reader(m["name"], root)(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


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
    pairs = set()
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
        if (w["config"], w["traffic"]) in pairs:
            bad.append("pair %s/%s twice" % (w["config"], w["traffic"]))
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    if used != set(names["configs"]):
        bad.append("unused configs %s" % sorted(set(names["configs"]) - used))
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
