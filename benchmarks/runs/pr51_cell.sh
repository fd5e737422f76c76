#!/bin/sh
# PR 51: mistral4_serve_longdoc parent, change, change, parent on two seeds
# (scratch/parent = git archive HEAD, scratch/change = git archive $(git
# write-tree); README.md says how the trees are unpacked), a traced run of the
# change, and the same P C C P on opt_serve_backlog and mimo_serve_longshort,
# whose decode rows take the other kernel through the shared list and combine.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr51_cell.sh [cells]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
last() { tail -1 $1 | cut -c1-${2:-700}; }
cell() { # tree cell seed trace
  (cd $T/$1 && python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 \
      --trace $4 > $R/pr51_$1_$2_$3_$4.out 2> $R/pr51_$1_$2_$3_$4.err
   echo "$1 $2 seed $3 trace $4 rc=$?")
  grep "^checks" $R/pr51_$1_$2_$3_$4.out | cut -c1-300
  last $R/pr51_$1_$2_$3_$4.out ${5:-700}
}
pccp() { # cell seed-a seed-b
  cell parent $1 $2 0; cell change $1 $2 0
  cell change $1 $3 0; cell parent $1 $3 0
}
for c in ${1:-mistral traced opt mimo}; do
  case $c in
    mistral) pccp mistral4_serve_longdoc 5100000101 5100000102 ;;
    mistral2) pccp mistral4_serve_longdoc 5100000103 5100000104 ;;
    mistral3) pccp mistral4_serve_longdoc 5100000105 5100000106 ;;
    traced) cell change mistral4_serve_longdoc ${TRACED_SEED:-5100000111} 1 6000
            grep "^device time" $R/pr51_change_mistral4_serve_longdoc_${TRACED_SEED:-5100000111}_1.out | cut -c1-1500
            mkdir -p $R/pr51_out; cp $T/change/chipbench/out/*.json $R/pr51_out/ 2>/dev/null ;;
    opt) pccp opt_serve_backlog 5100000121 5100000122 ;;
    mimo) pccp mimo_serve_longshort 5100000131 5100000132 ;;
    exaone) pccp exaone_serve_reason 5100000141 5100000142 ;;
    gaps) # every tick's gap of one run of the change (pr50_gaps.py keeps them)
          (cd $T/change && GAPS_OUT=$R/pr51_gaps_change.json python3 \
              benchmarks/runs/pr50_gaps.py --workload mistral4_serve_longdoc \
              --seed 5100000151 --seconds 51 --trace 0 \
              > $R/pr51_gaps_change.out 2> $R/pr51_gaps_change.err
           echo "gaps rc=$?"); last $R/pr51_gaps_change.out ;;
  esac
done
