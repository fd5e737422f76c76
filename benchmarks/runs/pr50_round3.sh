#!/bin/sh
# PR 50, benchmark round: the cell as it now stands in BENCHMARK.json (off
# serve_gap_p95_ms's list), from the files git would commit (scratch/change;
# README.md says how the trees are unpacked): one traced run, six seeds
# untraced; and on the parent under this PR's benchmark files
# (scratch/parent_bench) the new cell, which has to fail at once, and one
# accepted cell traced, whose lists this round touched.
#   chiprun --timeout 3000 -- sh benchmarks/runs/pr50_round3.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
cell() { # tree tag cell seed trace
  (cd $T/$1 && python3 -m chipbench.run --workload $3 --seed $4 --seconds 51 \
      --trace $5 > $R/pr50_r3_$2_$3_$4.out 2> $R/pr50_r3_$2_$3_$4.err
   echo "$2 $3 seed $4 trace $5 rc=$?")
}
(cd $T/parent_bench && timeout 600 python3 -m chipbench.run \
    --workload mistral4_serve_longdoc --seed 5000001001 --seconds 51 \
    --trace 0 > $R/pr50_r3_parent_new_cell.out 2> $R/pr50_r3_parent_new_cell.err
 echo "parent new cell rc=$?"; tail -1 $R/pr50_r3_parent_new_cell.err | cut -c1-200)
cell change change mistral4_serve_longdoc 5000001011 1
grep -v "^WARNING" $R/pr50_r3_change_mistral4_serve_longdoc_5000001011.err | tail -2 | cut -c1-300
tail -1 $R/pr50_r3_change_mistral4_serve_longdoc_5000001011.out | cut -c1-4000
for s in 5000001031 5000001032 5000001033 5000001034 5000001035 5000001036; do
  cell change change mistral4_serve_longdoc $s 0
  grep "^gaps" $R/pr50_r3_change_mistral4_serve_longdoc_$s.out | cut -c1-90
  tail -1 $R/pr50_r3_change_mistral4_serve_longdoc_$s.out | cut -c1-500
done
cell parent_bench parent_bench exaone_serve_reason 5000001021 1
tail -1 $R/pr50_r3_parent_bench_exaone_serve_reason_5000001021.out | cut -c1-3500
