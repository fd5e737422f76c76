#!/bin/sh
# PR 53, benchmark round: is opt_serve_backlog steady on the change?  The
# cell's window runs no line this PR changed but one `"kda_rows" in note` a
# tick (DecodeServer._note_counts); its programs lower to the parent's text
# (`hashes.py` since PR 61).  As pr52_steady.sh: parent (scratch/parent, git archive
# HEAD) and change (scratch/change, git archive $(git write-tree)) on the
# same seeds, the order turned round each seed, one call, then each side's
# quartile spread of every end-to-end metric (statistics.quantiles, n=4,
# over the median; all runs, and the farthest left out as the driver reads
# it).  Beside each run: the gaps' histogram (its late ticks) and the side
# file's compiles_in_window (the first call also asked the machine's cgroup
# how long the process was held off the CPU: cpu.stat, cpu.max and
# /proc/pressure/cpu are not readable there, and the reading was taken out).
# With TICKS=1 each run goes through benchmarks/runs/pr53_ticks.py (copied into
# scratch/parent beside the parent's own files): the same process with every
# tick's length kept (chiprun_out/pr53_ticks_<tree>/), so that the two sides'
# ticks can be laid side by side, index by index, on the same seed.
#   chiprun --timeout 3300 -- sh benchmarks/runs/pr53_steady.sh [seeds...]
#   chiprun --timeout 3300 -- env TICKS=1 sh benchmarks/runs/pr53_steady.sh 5300000911 ...
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
C=${CELL:-opt_serve_backlog}
if [ -n "$TICKS" ]; then P=benchmarks/runs/pr53_ticks.py; else P="-m chipbench.run"; fi
run() { # tree seed
  (cd $T/$1 && PR53_TICKS_DIR=$R/pr53_ticks_$1 python3 $P --workload $C --seed $2 --seconds 51 \
      --trace 0 > $R/pr53_steady_$1_$2.out 2> $R/pr53_steady_$1_$2.err
   echo "$1 seed $2 rc=$?")
  grep "^gaps:" $R/pr53_steady_$1_$2.out | cut -c1-400
  grep '^{"correct"' $R/pr53_steady_$1_$2.out | cut -c1-600
  grep "^ticks:" $R/pr53_steady_$1_$2.out | cut -c1-330
  grep "^longest:" $R/pr53_steady_$1_$2.out | cut -c1-400
  grep -h "compiles_in_window" $T/$1/chipbench/out/$C-$2-*.json | tr -d '\n'; echo
}
i=0
for s in ${@:-5300000901 5300000902 5300000903 5300000904 5300000905 5300000906}; do
  if [ $((i % 2)) = 0 ]; then run parent $s; run change $s
  else run change $s; run parent $s; fi
  i=$((i + 1))
done
python3 - $R <<'PY'
import glob, json, statistics, sys
for tree in ("parent", "change"):
    lines = [json.loads([l for l in open(f) if l.startswith('{"correct"')][-1])
             for f in sorted(glob.glob(sys.argv[1] + "/pr53_steady_%s_*.out" % tree))]
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
