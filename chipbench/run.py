"""``python -m chipbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, on the TPU; one JSON line last.

Without a TPU, or with fewer chips than the cell asks for, it says so on
stderr and exits 1 with an empty stdout.  ``BENCH_RUN`` is not read.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="python -m chipbench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def device_facts():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(loaded, seed, seconds, trace_on, contexts, counters, phases,
             memory):
    """Drive one loaded cell on ``contexts`` and return the driver's result
    (the CPU tests enter here, with ``mx.cpu()`` and tiny files)."""
    from . import harness

    traffic = loaded["traffic"]
    if trace_on:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    driver = importlib.import_module(
        "chipbench.drivers." + traffic["driver"])
    return driver.run({
        "cell": loaded["cell"], "config": loaded["config"],
        "traffic": traffic, "seed": seed, "seconds": seconds,
        "contexts": contexts, "counters": counters, "phases": phases,
        "tracer": harness.Tracer(trace_on, loaded["cell"]["name"]),
        "memory": memory,
    })


def main(argv=None):
    args = parse_args(argv)
    from . import harness, manifest

    phases = harness.Phases()
    man = manifest.load_manifest()
    loaded = manifest.load_cell(args.workload, manifest=man)
    seconds = args.seconds if args.seconds is not None \
        else float(man["run_seconds"])
    chips = int(loaded["cell"]["chips"])

    from mxnet_tpu.cache_dirs import arm_compile_cache

    cache_dir = arm_compile_cache()
    import jax

    # sub-second programs (per-parameter copies, initialisers) are most of
    # a warm start: cache them too, in this process only
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = device_facts()
    if device["platform"] != "tpu" or device["count"] < chips:
        print("chipbench needs %d TPU chip(s); jax found platform=%r (%s) "
              "x %d" % (chips, device["platform"], device["kind"],
                        device["count"]), file=sys.stderr)
        return 1
    peaks = manifest.load_json(manifest.ROOT, manifest.HERE + "/peaks.json")
    if device["kind"] not in peaks:
        print("no peaks for device_kind %r in chipbench/peaks.json"
              % device["kind"], file=sys.stderr)
        return 1
    counters = harness.CompileCounters().install()

    import mxnet_tpu as mx

    contexts = [mx.tpu(i) for i in range(chips)]
    memory = harness.MemoryPeak(chips)
    result = run_cell(loaded, args.seed, seconds, bool(args.trace), contexts,
                      counters, phases, memory)

    device["memory_peak_bytes"] = memory.peak()
    correct = all(c["ok"] for c in result["checks"])
    units = {m["name"]: m["unit"] for m in loaded["end_to_end"]}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "device": device}
    side = dict(result["side"], workload=args.workload, seed=args.seed,
                trace=args.trace, setup_s=result["setup_s"],
                checks=result["checks"],
                compile_s=counters.compile_s, compiles=counters.compiles,
                cache_hits=counters.cache_hits,
                compiles_in_window=counters.in_window,
                compile_cache=cache_dir,
                memory_peak_bytes=device["memory_peak_bytes"],
                memory_stats=jax.devices()[0].memory_stats())
    if args.trace:
        from . import trace

        parsed = result["trace"]
        busy_s, window_s = trace.busy(parsed)
        device.update(busy_s=busy_s, window_s=window_s)
        facts = dict(result["facts"], trace=parsed, cell=loaded["cell"],
                     config=loaded["config"], traffic=loaded["traffic"],
                     peaks=peaks[device["kind"]], chips=chips,
                     compile_s=counters.compile_s,
                     compiles_in_window=counters.in_window,
                     memory_peak_bytes=device["memory_peak_bytes"])
        reader_s = {}
        line["metrics"] = manifest.read_layer_metrics(
            loaded["per_layer"], facts, seconds=reader_s)
        phases.mark("read_layer_metrics")
        line["breakdown"] = {"device_ops": trace.top_ops(parsed),
                             "idle_gaps": trace.idle_gaps(parsed)}
        phases.mark("breakdown")
        side["modules"] = trace.module_names(parsed)
        side["per_layer"] = line["metrics"]
        side["trace_events"] = trace.event_counts(parsed)
        side["trace_file_bytes"] = result["trace_bytes"]
        side["reader_s"] = reader_s
    else:
        values = dict(result["end_to_end"], setup_s=result["setup_s"])
        line["metrics"] = {n: {"value": float(values[n]), "unit": u}
                           for n, u in units.items()}
    # where the process's seconds went: the set-up phases up to the opening
    # fence, and from the window on (a traced run: stopping, loading and
    # reducing the trace; both: the comparison with the reference)
    names = list(phases.seconds)
    cut = names.index("window") if "window" in names else len(names)
    side["setup_split_s"] = {k: phases.seconds[k] for k in names[:cut]}
    side["run_split_s"] = dict(
        {k: phases.seconds[k] for k in names[cut:]},
        process_s=harness.process_age_s())
    print("run split (s): %s" % json.dumps(
        {k: round(v, 2) for k, v in side["run_split_s"].items()}),
        flush=True)
    print("side file: %s" % harness.write_side_file(
        args.workload, args.seed, side), flush=True)
    print("checks: %s" % json.dumps(result["checks"]), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
