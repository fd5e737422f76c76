#!/bin/sh
# PR 50, review round: mistral4_serve_longdoc from the files git would commit
# (scratch/change; README.md says how the trees are unpacked), the new cell
# and one old cell on the parent under this PR's benchmark files
# (scratch/parent_bench), and the one accepted cell with a prefix cache,
# parent, change, change, parent.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr50_cell.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
last() { tail -1 $1 | cut -c1-${2:-700}; }
cell() { # tree tag cell seed trace
  (cd $T/$1 && python3 -m chipbench.run --workload $3 --seed $4 --seconds 51 \
      --trace $5 > $R/pr50_$2_$3_$4.out 2> $R/pr50_$2_$3_$4.err
   echo "$2 $3 seed $4 trace $5 rc=$?")
}
# the parent cannot run the new cell: it has to fail at once, not hang
(cd $T/parent_bench && timeout 600 python3 -m chipbench.run \
    --workload mistral4_serve_longdoc --seed 5000000801 --seconds 51 \
    --trace 0 > $R/pr50_parent_new_cell.out 2> $R/pr50_parent_new_cell.err
 echo "parent new cell rc=$?"; tail -2 $R/pr50_parent_new_cell.err | cut -c1-200)
# the traced run: the four new metrics by the readers as they now are
cell change change mistral4_serve_longdoc 5000000811 1
grep -v "^WARNING" $R/pr50_change_mistral4_serve_longdoc_5000000811.err | tail -3 | cut -c1-300
grep "^device time\|^checks" $R/pr50_change_mistral4_serve_longdoc_5000000811.out | cut -c1-900
last $R/pr50_change_mistral4_serve_longdoc_5000000811.out 6000
mkdir -p $R/pr50_out; cp $T/change/chipbench/out/*.json $R/pr50_out/ 2>/dev/null
# an old cell traced on the parent under this PR's benchmark files
cell parent_bench parent_bench opt_serve_backlog 5000000821 1
last $R/pr50_parent_bench_opt_serve_backlog_5000000821.out 2500
# six seeds of the new cell
for s in 5000000831 5000000832 5000000833 5000000834 5000000835 5000000836; do
  cell change change mistral4_serve_longdoc $s 0
  grep "^checks" $R/pr50_change_mistral4_serve_longdoc_$s.out | cut -c1-300
  last $R/pr50_change_mistral4_serve_longdoc_$s.out
done
# the accepted cell with a prefix cache: parent, change, change, parent
cell parent parent opt_serve_backlog 5000000841 0; last $R/pr50_parent_opt_serve_backlog_5000000841.out
cell change change opt_serve_backlog 5000000841 0; last $R/pr50_change_opt_serve_backlog_5000000841.out
cell change change opt_serve_backlog 5000000842 0; last $R/pr50_change_opt_serve_backlog_5000000842.out
cell parent parent opt_serve_backlog 5000000842 0; last $R/pr50_parent_opt_serve_backlog_5000000842.out
