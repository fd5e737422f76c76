#!/bin/sh
# PR 50, benchmark round: why serve_gap_p95_ms of mistral4_serve_longdoc
# spreads.  Four runs, two seeds twice, each tick's gap kept
# (benchmarks/runs/pr50_gaps.py), so that the same tick can be held against
# itself under the same seed and under another.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr50_gaps.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
i=0
for s in 5000000901 5000000902 5000000901 5000000902; do
  i=$((i + 1))
  GAPS_OUT=$R/pr50_gaps_${i}_$s.json python3 benchmarks/runs/pr50_gaps.py \
      --workload mistral4_serve_longdoc --seed $s --seconds 51 --trace 0 \
      > $R/pr50_gaps_${i}_$s.out 2> $R/pr50_gaps_${i}_$s.err
  echo "run $i seed $s rc=$?"
  grep "^gaps" $R/pr50_gaps_${i}_$s.out | cut -c1-120
  tail -1 $R/pr50_gaps_${i}_$s.out | cut -c1-500
done
