#!/bin/sh
# PR 55: three untraced runs of opt_serve_backlog from scratch/change through
# pr55_account.py: the side file's compiles_in_window over a whole 51-s window
# (a traced window is 96 ticks), the gaps' histogram, and the account.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr55_untraced.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
for seed in 5500000601 5500000602 5500000603; do
  (cd $T/change && env PR55_DIR=$R/pr55_account_untraced python3 benchmarks/runs/pr55_account.py \
      --workload opt_serve_backlog --seed $seed --seconds 51 --trace 0 \
      > $R/pr55_untraced_$seed.out 2> $R/pr55_untraced_$seed.err; echo "untraced seed $seed rc=$?")
  grep -h "^gaps:\|^pr55 harness\|^pr55 metrics\|^pr55 compile\|^{\"correct\"" $R/pr55_untraced_$seed.out | cut -c1-1500
done
