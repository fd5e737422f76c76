#!/bin/sh
# PR 55, first call (one chip): what the account costs on the chip's host
# (both trees), one traced run of opt_serve_backlog and of rn50_train_bs256
# with the account printed beside the harness's phases (scratch/change), a
# compile forced inside a traced window (pr55_inwindow.py), and one traced
# run of an old cell on the parent under this PR's benchmark files
# (scratch/parent_bench: the eight readers must read 0.0 there, not fail).
#   chiprun --timeout 1800 -- sh benchmarks/runs/pr55_first.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
for tree in change parent change parent; do
  (cd $T/$tree && PYTHONPATH=. python3 $T/change/benchmarks/runs/pr55_cost.py)
done
account() { # cell seed trace [env...]
  (cd $T/change && env PR55_DIR=$R/pr55_account $4 python3 benchmarks/runs/pr55_account.py \
      --workload $1 --seed $2 --seconds 51 --trace $3 > $R/pr55_$1_$2.out 2> $R/pr55_$1_$2.err
   echo "$1 seed $2 trace $3 rc=$?")
  grep -h "^pr55 \|^{\"correct\"\|^run split" $R/pr55_$1_$2.out | cut -c1-3000
}
account opt_serve_backlog 5500000101 1
(cd $T/change && python3 benchmarks/runs/pr55_inwindow.py 5500000301 > $R/pr55_inwindow.out 2> $R/pr55_inwindow.err; echo "inwindow rc=$?")
grep -v "^side file\|^trace kept" $R/pr55_inwindow.out | cut -c1-2500 | tail -40
tail -5 $R/pr55_inwindow.err
(cd $T/parent_bench && python3 -m chipbench.run --workload opt_serve_backlog --seed 5500000102 \
    --seconds 51 --trace 1 > $R/pr55_parent_bench.out 2> $R/pr55_parent_bench.err; echo "parent_bench rc=$?")
grep -h "^{\"correct\"" $R/pr55_parent_bench.out | cut -c1-3000
tail -3 $R/pr55_parent_bench.err
account rn50_train_bs256 5500000103 1
