#!/bin/sh
# PR 53, benchmark round: what is the tick of 280 ms (1.0-1.3 s in a tree's
# first run) that pr53_steady.sh's tick stamps show at the SAME index on both
# sides (seed 5300000916: tick 584; ...914: 3277; ...911: 1155)?  One run of
# the PARENT (scratch/parent + pr53_ticks.py) with jax logging what it traces
# and compiles, the records inside the window printed with their tick, and
# where the loop's thread stood at the watcher's wakes inside the longest ticks.
#   chiprun --timeout 900 -- sh benchmarks/runs/pr53_inwindow.sh [tree seed]...
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
[ $# = 0 ] && set -- parent 5300000916
while [ $# -ge 2 ]; do
  tree=$1; seed=$2; shift 2
  (cd scratch/$tree || exit 1
   PR53_LOG_COMPILES=1 PR53_TICKS_DIR=$R/pr53_ticks_log_$tree python3 benchmarks/runs/pr53_ticks.py \
     --workload opt_serve_backlog --seed $seed --seconds 51 --trace 0 \
     > $R/pr53_inwindow_${tree}_$seed.out 2> $R/pr53_inwindow_${tree}_$seed.err
   echo "$tree seed $seed rc=$?"
   cp chipbench/out/opt_serve_backlog-$seed-*.json $R/ 2>/dev/null
   grep -h "compiles_in_window" chipbench/out/opt_serve_backlog-$seed-*.json)
  grep "^in window" $R/pr53_inwindow_${tree}_$seed.out | cut -c1-700 | head -40
  grep '^{"correct"\|^logged\|^longest\|the loop stood' $R/pr53_inwindow_${tree}_$seed.out | cut -c1-900
done
