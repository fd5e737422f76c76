"""PR 57: the spread of each end-to-end metric over a set of plain runs, as
the driver reads it: (Q3 - Q1) / median by ``statistics.quantiles(n=4)``.

    python3 benchmarks/runs/pr57_spread.py <run outputs ...>
"""
import json
import statistics
import sys

lines = [json.loads(open(f).read().strip().splitlines()[-1])
         for f in sorted(sys.argv[1:])]
print(len(lines), "plain runs, correct", all(l["correct"] for l in lines),
      "failed", sum(l["failed"] for l in lines), "device",
      sorted({l["device"]["kind"] for l in lines}))
for m in sorted(lines[0]["metrics"]):
    v = [l["metrics"][m]["value"] for l in lines]
    q, med = statistics.quantiles(v, n=4), statistics.median(v)
    print("  %s median %.4f iqr %.4f (%.3f %%): %s"
          % (m, med, q[2] - q[0], 100 * (q[2] - q[0]) / med,
             " ".join("%.3f" % x for x in v)))
