"""PR 59: the spread of each end-to-end metric over a set of plain runs by
both rules ISSUE 59 asks for: the contract's, (Q3 - Q1) / median of all the
runs by ``statistics.quantiles(n=4)``, and the same with the run farthest
from the median left out (as the driver has read such sets); beside each run
its gap samples over ``STOP_MS`` (100 ms by default, the histogram's edge;
the machine's stops, hazard 7; a chunk tick that is itself longer counts
too, so set the edge above the cell's chunk ticks) from the histogram the run
prints.

    [STOP_MS=150] python3 benchmarks/runs/pr59_spread.py <run outputs ...>
"""
import json
import os
import statistics
import sys

STOP_MS = float(os.environ.get("STOP_MS", 100))


def spread(v):
    q, med = statistics.quantiles(v, n=4), statistics.median(v)
    return med, q[2] - q[0], 100 * (q[2] - q[0]) / med


def long_ticks(path):
    """Gap samples over ``STOP_MS``, from the run's printed histogram (the
    driver weighs a tick by its active slots: samples / slots = ticks)."""
    edges = hist = None
    for line in open(path):
        if line.startswith("gaps:") and "histogram (ms edges" in line:
            edges = json.loads(line.split("edges ", 1)[1].split(")")[0])
            hist = json.loads(line.rsplit(": ", 1)[1])
    if not edges:
        return None
    return sum(h for e, h in zip(edges, hist) if e >= STOP_MS)


files = sorted(sys.argv[1:])
lines = [json.loads(open(f).read().strip().splitlines()[-1]) for f in files]
print(len(lines), "plain runs, correct", all(l["correct"] for l in lines),
      "failed", sum(l["failed"] for l in lines), "device",
      sorted({l["device"]["kind"] for l in lines}), "peak GB",
      " ".join("%.3f" % (l["device"]["memory_peak_bytes"] / 1e9)
               for l in lines))
print("  gap samples over %g ms a run:" % STOP_MS,
      [long_ticks(f) for f in files])
for m in sorted(lines[0]["metrics"]):
    v = [l["metrics"][m]["value"] for l in lines]
    med, iqr, pct = spread(v)
    far = max(v, key=lambda x: abs(x - med))
    rest = list(v)
    rest.remove(far)
    print("  %s median %.4f iqr %.4f (%.3f %%; the farthest, %.3f, left out "
          "%.3f %%): %s" % (m, med, iqr, pct, far, spread(rest)[2],
                            " ".join("%.3f" % x for x in v)))
