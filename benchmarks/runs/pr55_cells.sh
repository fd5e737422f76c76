#!/bin/sh
# PR 55: a cell traced twice from scratch/change (git archive $(git
# write-tree)), first COLD (jax's cache directory moved aside: a fresh, empty
# one) and then WARM (the directory the cold run just filled), each through
# pr55_account.py, which prints the program's account beside the harness's
# phases of the same run.
#   chiprun --timeout 3000 -- sh benchmarks/runs/pr55_cells.sh opt_serve_backlog rn50_train_bs256 sala_serve_longctx
#   chiprun --chips 4 --timeout 1500 -- sh benchmarks/runs/pr55_cells.sh rn50_train_dp4
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
seed=${SEED:-5500000200}
for cell in "$@"; do
  dir=$T/pr55_cache_$cell
  rm -rf $dir; mkdir -p $dir
  for how in cold warm; do
    seed=$((seed + 1))
    (cd $T/change && env PR55_DIR=$R/pr55_account_$how JAX_COMPILATION_CACHE_DIR=$dir \
        python3 benchmarks/runs/pr55_account.py --workload $cell --seed $seed --seconds 51 \
        --trace 1 > $R/pr55_${cell}_$how.out 2> $R/pr55_${cell}_$how.err
     echo "$cell $how seed $seed rc=$?")
    grep -h "^pr55 \|^{\"correct\"\|^run split" $R/pr55_${cell}_$how.out | cut -c1-3500
    tail -2 $R/pr55_${cell}_$how.err | cut -c1-300
  done
done
