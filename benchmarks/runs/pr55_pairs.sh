#!/bin/sh
# PR 55: six warm pairs of opt_serve_backlog, parent (scratch/parent, git
# archive HEAD) beside change (scratch/change, git archive $(git write-tree)),
# untraced, the order turned round each pair, after one run of each tree that
# is not counted (a tree's first run of a cell compiles).  Prints each run's
# line and each side's median and quartile spread of every end-to-end metric.
#   chiprun --timeout 3000 -- sh benchmarks/runs/pr55_pairs.sh [seeds...]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
C=${CELL:-opt_serve_backlog}
run() { # tree seed tag
  (cd $T/$1 && python3 -m chipbench.run --workload $C --seed $2 --seconds 51 --trace 0 \
      > $R/pr55_pairs_$3_$1_$2.out 2> $R/pr55_pairs_$3_$1_$2.err
   echo "$1 seed $2 rc=$?")
  grep '^{"correct"' $R/pr55_pairs_$3_$1_$2.out | cut -c1-700
}
run parent 5500000400 first; run change 5500000400 first
i=0
for s in ${@:-5500000401 5500000402 5500000403 5500000404 5500000405 5500000406}; do
  if [ $((i % 2)) = 0 ]; then run parent $s pair; run change $s pair
  else run change $s pair; run parent $s pair; fi
  i=$((i + 1))
done
python3 - $R <<'PY'
import glob, json, statistics, sys
for tree in ("parent", "change"):
    lines = [json.loads([l for l in open(f) if l.startswith('{"correct"')][-1])
             for f in sorted(glob.glob(sys.argv[1] + "/pr55_pairs_pair_%s_*.out" % tree))]
    print(tree, len(lines), "runs, correct", all(l["correct"] for l in lines),
          "failed", sum(l["failed"] for l in lines))
    for m in ("serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"):
        v = [l["metrics"][m]["value"] for l in lines]
        q = statistics.quantiles(v, n=4); med = statistics.median(v)
        print("  %s median %.4f iqr %.4f (%.3f %%): %s" % (
            m, med, q[2] - q[0], 100 * (q[2] - q[0]) / med,
            " ".join("%.3f" % x for x in v)))
PY
