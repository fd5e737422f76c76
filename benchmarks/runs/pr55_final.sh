#!/bin/sh
# PR 55, last call: the committed files alone (scratch/change = git archive
# $(git write-tree) of the final tree): opt_serve_backlog traced twice through
# pr55_account.py (the second run reads what the first left in jax's cache),
# with the retrieval's share of each cache read (`reads`).
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr55_final.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
for seed in 5500000501 5500000502; do
  (cd $T/change && env PR55_DIR=$R/pr55_account_final python3 benchmarks/runs/pr55_account.py \
      --workload opt_serve_backlog --seed $seed --seconds 51 --trace 1 \
      > $R/pr55_final_$seed.out 2> $R/pr55_final_$seed.err; echo "final seed $seed rc=$?")
  grep -h "^pr55 \|^{\"correct\"" $R/pr55_final_$seed.out | cut -c1-3000
done
