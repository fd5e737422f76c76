#!/bin/sh
# PR 53, the review round: two further sets of six runs of solar2_serve_agent
# from scratch/change (git archive $(git write-tree), README.md), each run
# through benchmarks/runs/pr53_ticks.py: chipbench.run's own process with every
# tick's stamp kept and a watcher thread beside it, so that a stalled tick has
# a reading of the process and the host behind it (chiprun_out/pr53_ticks/).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr53_seventh.sh [seed ...]
mkdir -p chiprun_out/pr53_ticks
R=$(pwd)/chiprun_out
export PR53_TICKS_DIR=$R/pr53_ticks
seeds=${*:-5300000701 5300000702 5300000703 5300000704 5300000705 5300000706 \
5300000711 5300000712 5300000713 5300000714 5300000715 5300000716}
cd scratch/change || exit 1
for seed in $seeds; do
  began=$(date +%s)
  python3 benchmarks/runs/pr53_ticks.py --workload solar2_serve_agent \
    --seed $seed --seconds 51 --trace 0 \
    > $R/pr53_ticks_$seed.out 2> $R/pr53_ticks_$seed.err
  echo "seed $seed rc=$? after $(( $(date +%s) - began )) s"
  grep "^checks" $R/pr53_ticks_$seed.out | cut -c1-400
  grep '^{"correct"' $R/pr53_ticks_$seed.out | cut -c1-600
  grep "^ticks:" $R/pr53_ticks_$seed.out | cut -c1-1600
  cp chipbench/out/solar2_serve_agent-$seed-*.json $R/pr53_ticks/ 2>/dev/null
  grep -v "^WARNING\|^$" $R/pr53_ticks_$seed.err | tail -2 | cut -c1-300
done
