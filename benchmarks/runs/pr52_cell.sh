#!/bin/sh
# PR 52: mistral4_serve_longdoc parent, change, change, parent on two seeds a
# part (scratch/parent = git archive HEAD, scratch/change = git archive $(git
# write-tree); README.md says how the trees are unpacked), a traced run of the
# change (its layers-*.json kept under chiprun_out/pr52_out), every tick's gap
# of one run of the change, and the same P C C P on the two other cells that
# run the gated layer (their programs are the parent's: under the crossover).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr52_cell.sh [parts]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
last() { tail -1 $1 | cut -c1-${2:-700}; }
cell() { # tree cell seed trace
  (cd $T/$1 && python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 \
      --trace $4 > $R/pr52_$1_$2_$3_$4.out 2> $R/pr52_$1_$2_$3_$4.err
   echo "$1 $2 seed $3 trace $4 rc=$?")
  grep "^checks" $R/pr52_$1_$2_$3_$4.out | cut -c1-300
  last $R/pr52_$1_$2_$3_$4.out ${5:-700}
}
pccp() { # cell seed-a seed-b
  cell parent $1 $2 0; cell change $1 $2 0
  cell change $1 $3 0; cell parent $1 $3 0
}
for c in ${1:-mistral traced}; do
  case $c in
    mistral) pccp mistral4_serve_longdoc 5200000101 5200000102 ;;
    mistral2) pccp mistral4_serve_longdoc 5200000103 5200000104 ;;
    mistral3) pccp mistral4_serve_longdoc 5200000105 5200000106 ;;
    mistral4) pccp mistral4_serve_longdoc 5200000107 5200000108 ;;
    traced) cell change mistral4_serve_longdoc ${TRACED_SEED:-5200000111} 1 6000
            grep "^device time" $R/pr52_change_mistral4_serve_longdoc_${TRACED_SEED:-5200000111}_1.out | cut -c1-1500
            mkdir -p $R/pr52_out; cp $T/change/chipbench/out/*.json $R/pr52_out/ 2>/dev/null ;;
    traced_parent) cell parent mistral4_serve_longdoc ${TRACED_SEED:-5200000111} 1 6000 ;;
    mimo) pccp mimo_serve_longshort 5200000131 5200000132 ;;
    exaone) pccp exaone_serve_reason 5200000141 5200000142 ;;
    gaps) # every tick's gap of one run of the change, and the forms its programs took
          (cd $T/change && GAPS_OUT=$R/pr52_gaps_change.json python3 \
              benchmarks/runs/pr52_forms.py --workload mistral4_serve_longdoc \
              --seed 5200000151 --seconds 51 --trace 0 \
              > $R/pr52_gaps_change.out 2> $R/pr52_gaps_change.err
           echo "gaps rc=$?"); last $R/pr52_gaps_change.out
          grep "^moe_forms\|^mx_moe" $R/pr52_gaps_change.err | cut -c1-600 ;;
  esac
done
