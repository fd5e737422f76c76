#!/bin/sh
# PR 50, review round: the new cell once more, traced, from the files git
# would commit on the FINAL tree (scratch/change, README.md): after
# pr50_cell.sh only the limit's `why`, the docs and the records changed.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr50_final.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
cd scratch/change
python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000851 \
    --seconds 51 --trace 1 > $R/pr50_final.out 2> $R/pr50_final.err
echo "final traced rc=$?"
grep -v "^WARNING" $R/pr50_final.err | tail -3 | cut -c1-300
grep "^checks" $R/pr50_final.out | cut -c1-400
tail -1 $R/pr50_final.out | cut -c1-3000
python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000852 \
    --seconds 51 --trace 0 > $R/pr50_final2.out 2> $R/pr50_final2.err
echo "final untraced rc=$?"
grep "^checks" $R/pr50_final2.out | cut -c1-400
tail -1 $R/pr50_final2.out | cut -c1-700
