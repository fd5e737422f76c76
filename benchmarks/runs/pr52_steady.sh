#!/bin/sh
# PR 52, benchmark round: is opt_serve_backlog steady on the change?  The cell
# runs no line this PR changed (no gated expert layer; decode.py's additions
# run while a program is traced).  Parent and change on the same seeds, the
# order turned round each seed, one call, then each side's quartile spread of
# every end-to-end metric (statistics.quantiles, n=4, over the median).
#   chiprun --timeout 3300 -- sh benchmarks/runs/pr52_steady.sh [seeds...]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
C=opt_serve_backlog
run() { # tree seed
  (cd $T/$1 && python3 -m chipbench.run --workload $C --seed $2 --seconds 51 \
      --trace 0 > $R/pr52_steady_$1_$2.out 2> $R/pr52_steady_$1_$2.err
   echo "$1 seed $2 rc=$?"); tail -1 $R/pr52_steady_$1_$2.out | cut -c1-600
}
i=0
for s in ${@:-5200000201 5200000202 5200000203 5200000204 5200000205 5200000206}; do
  if [ $((i % 2)) = 0 ]; then run parent $s; run change $s
  else run change $s; run parent $s; fi
  i=$((i + 1))
done
python3 - $R <<'PY'
import glob, json, statistics, sys
for tree in ("parent", "change"):
    lines = [json.loads(open(f).read().strip().splitlines()[-1])
             for f in sorted(glob.glob(sys.argv[1] + "/pr52_steady_%s_*.out" % tree))]
    print(tree, len(lines), "runs, correct", all(l["correct"] for l in lines),
          "failed", sum(l["failed"] for l in lines))
    for m in ("serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"):
        v = [l["metrics"][m]["value"] for l in lines]
        q = statistics.quantiles(v, n=4); med = statistics.median(v)
        far = max(v, key=lambda x: abs(x - med)); w = list(v); w.remove(far)
        qw = statistics.quantiles(w, n=4)
        print("  %s median %.4f min %.4f max %.4f iqr %.4f (%.3f %%), farthest left out %.4f (%.3f %%)"
              % (m, med, min(v), max(v), q[2] - q[0], 100 * (q[2] - q[0]) / med,
                 qw[2] - qw[0], 100 * (qw[2] - qw[0]) / med))
        print("   ", " ".join("%.3f" % x for x in v))
PY
