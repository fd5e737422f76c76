#!/bin/sh
# PR 57, the refusal round (BENCHMARK_REFUSED.md: the cell's spread at the
# edge of what admits): six more plain runs of the cell from the tree the
# script is started from, their spread by the driver's rule and each run's
# ticks of 100-150 ms (the machine's stops, pr57_stops.py) beside its rate;
# then what brings a stop, by phase.
#   chiprun --timeout 1700 -- sh benchmarks/runs/pr57_refusal.sh
sh benchmarks/runs/cell.sh pr57 \
  runs:here:olmoh_serve_rollouts:0:5700000611,5700000612,5700000613,5700000614,5700000615,5700000616
python3 benchmarks/runs/pr57_spread.py chiprun_out/pr57_here_olmoh_serve_rollouts_570000061?_0.out
python3 - <<'P'
import glob, json
for f in sorted(glob.glob("chiprun_out/pr57_out/olmoh_serve_rollouts-570000061*.json")):
    d = json.load(open(f))
    edges, hist = d["gap_histogram_ms"]
    stops = sum(h for e, h in zip(edges, hist) if e >= 80) / 96.0
    print("seed %d: %.2f tokens/s, %d ticks, %.1f ticks of 80 ms or more, "
          "longest %.1f ms" % (d["seed"], d["tokens"] / d["window_s"],
                               d["ticks"], stops, 1e3 * d["longest_tick_s"]))
P
python3 benchmarks/runs/pr57_stops.py --phases 60
