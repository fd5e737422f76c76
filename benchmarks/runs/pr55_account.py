"""PR 55: one run of a cell exactly as ``python3 -m chipbench.run`` makes it
(the same arguments, the same process), then the program's own account of
its start and of its compiles beside the harness's phases of the same run.

    python3 benchmarks/runs/pr55_account.py --workload opt_serve_backlog \
        --seed 5500000101 --seconds 51 --trace 1

Printed after the run's own lines, and written to ``$PR55_DIR`` (default
``chiprun_out/pr55_account``) as ``<cell>-<seed>.json``:

* ``setup``: ``mx_setup_seconds`` by phase, and ``adds_up``: ``import* +
  build* + compile + outside`` against ``until_loop + after_loop``;
* ``harness``: the side file's ``setup_split_s`` (``import_and_bind``,
  ``weights_and_data``, ``compile_or_cache``, ``fill`` / ``warmup``), its
  ``setup_s`` and ``compiles_in_window``, with ``between``: whether ``until_loop`` lies between ``import_and_bind +
  weights_and_data`` and that sum plus ``compile_or_cache``;
* ``compile``: ``mx_compile_seconds`` by program and stage,
  ``mx_compiles_total`` by program and cache, ``reads``: of the program's
  cache reads, the seconds in jax's retrieval and in the whole backend
  step that held it, ``events``: how many stage
  events the listener booked (the ring's ``compile.*`` spans; the ring
  holds 65536 events, so a long window's ticks may have pushed the first
  out: ``dropped`` says how many);
* ``eager``: the ``(eager)`` rows by what compiled (jax's ``fun`` name),
  seconds and count, largest first; ``outside`` likewise;
* ``metrics``: the eight readers on the same snapshot.
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.getcwd())

NEW = ("setup_import_s", "setup_build_s", "setup_outside_s",
       "compile_trace_lower_s", "compile_cache_read_s", "compile_miss_s",
       "compiles_per_program", "compile_named_pct")


def by_fun(events, who):
    out = {}
    for e in events:
        if e.get("cat") == "compile" and e["args"]["program"] == who:
            row = out.setdefault("%s %s" % (e["name"][8:], e["args"]["fun"]),
                                 [0.0, 0])
            row[0] += e["dur"] * 1e-6
            row[1] += 1
    return sorted(([k, round(v[0], 4), v[1]] for k, v in out.items()),
                  key=lambda r: -r[1])[:25]


def main():
    from chipbench import run

    argv = sys.argv[1:]
    return run.main(argv) or account(run.parse_args(argv))


def account(args):
    from chipbench import manifest
    from mxnet_tpu import obs

    facts = {}
    metrics = {n: manifest.load_reader(n)(facts) for n in NEW}
    snap = facts["registry"]
    rows = lambda n: [(r["labels"], r["value"]) for r in snap[n]["series"]] \
        if n in snap else []
    setup = {l["phase"]: v for l, v in rows("mx_setup_seconds")}
    parts = sum(v for k, v in setup.items()
                if k.startswith(("import", "build"))) \
        + setup.get("compile", 0.0) + setup.get("outside", 0.0)
    whole = setup.get("until_loop", 0.0) + setup.get("after_loop", 0.0)
    side = sorted(glob.glob(os.path.join(
        "chipbench", "out", "%s-%d-%d.json"
        % (args.workload, args.seed, os.getpid()))))
    side = json.load(open(side[-1])) if side else {}
    split = side.get("setup_split_s", {})
    lo = split.get("import_and_bind", 0.0) + split.get("weights_and_data",
                                                       0.0)
    events = obs.timeline.events()
    reads = [(e["args"]["retrieval_s"], e["dur"] * 1e-6) for e in events
             if e["name"] == "compile.cache_read"
             and e["args"]["program"] != "(outside)"]
    out = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "setup": setup,
        "adds_up": {"parts": parts, "until_loop_plus_after": whole,
                    "differ": parts - whole},
        "harness": dict(split, setup_s=side.get("setup_s"),
                        compiles_in_window=side.get("compiles_in_window"),
                        between=[
            lo, setup.get("until_loop"),
            lo + split.get("compile_or_cache", 0.0)]),
        "compile": {
            "seconds": {"%s/%s" % (l["program"], l["stage"]): v
                        for l, v in rows("mx_compile_seconds")},
            "total": {"%s/%s" % (l["program"], l["cache"]): v
                      for l, v in rows("mx_compiles_total")},
            "reads": [sum(r[0] for r in reads), sum(r[1] for r in reads)],
            "events": sum(e.get("cat") == "compile" for e in events),
            "phases": sum(e.get("cat") == "setup" for e in events),
            "dropped": obs.timeline.dropped},
        "eager": by_fun(events, "(eager)"),
        "outside": by_fun(events, "(outside)"),
        "metrics": metrics,
    }
    where = os.environ.get("PR55_DIR", "chiprun_out/pr55_account")
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "%s-%d.json"
                           % (args.workload, args.seed)), "w") as f:
        json.dump(out, f, indent=1)
    for key in ("setup", "adds_up", "harness", "compile", "eager",
                "outside", "metrics"):
        print("pr55 %s: %s" % (key, json.dumps(out[key])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
