#!/bin/sh
# PR 53, benchmark round, the last call, from scratch/change (git archive
# $(git write-tree) with the fork run at serve_open): opt_serve_backlog and
# solar2_serve_agent traced once each (every per-layer metric still read,
# `correct`), then six more plain runs of opt_serve_backlog on seeds of their
# own: the change's second set of six, with each run's compiles_in_window.
#   chiprun --timeout 2400 -- sh benchmarks/runs/pr53_ninth.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
cd scratch/change || exit 1
run() { # cell seed trace
  began=$(date +%s)
  python3 -m chipbench.run --workload $1 --seed $2 --seconds 51 --trace $3 \
    > $R/pr53_ninth_$1_$2.out 2> $R/pr53_ninth_$1_$2.err
  echo "$1 seed $2 trace $3 rc=$? after $(( $(date +%s) - began )) s"
  grep "^checks\|^gaps:" $R/pr53_ninth_$1_$2.out | cut -c1-400
  tail -1 $R/pr53_ninth_$1_$2.out | cut -c1-${4:-600}
  grep -h "compiles_in_window" chipbench/out/$1-$2-*.json | tr -d '\n'; echo
  grep -v "^WARNING\|^$" $R/pr53_ninth_$1_$2.err | tail -2 | cut -c1-300
}
run opt_serve_backlog 5300000931 1 4000
run solar2_serve_agent 5300000932 1 5000
for s in 5300000941 5300000942 5300000943 5300000944 5300000945 5300000946; do
  run opt_serve_backlog $s 0
done
python3 - $R <<'PY'
import glob, json, statistics, sys
lines = [json.loads(open(f).read().strip().splitlines()[-1])
         for f in sorted(glob.glob(sys.argv[1] + "/pr53_ninth_opt_serve_backlog_530000094*.out"))]
print(len(lines), "plain runs, correct", all(l["correct"] for l in lines),
      "failed", sum(l["failed"] for l in lines))
for m in ("serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"):
    v = [l["metrics"][m]["value"] for l in lines]
    q = statistics.quantiles(v, n=4); med = statistics.median(v)
    far = max(v, key=lambda x: abs(x - med)); w = list(v); w.remove(far)
    qw = statistics.quantiles(w, n=4)
    print("  %s median %.4f iqr %.4f (%.3f %%), farthest left out %.4f (%.3f %%)"
          % (m, med, q[2] - q[0], 100 * (q[2] - q[0]) / med,
             qw[2] - qw[0], 100 * (qw[2] - qw[0]) / med))
    print("   ", " ".join("%.3f" % x for x in v))
PY
