#!/bin/sh
# PR 54: a cell parent, change, change, parent on two seeds a part
# (scratch/parent = git archive HEAD, scratch/change = git archive $(git
# write-tree); README.md says how the trees are unpacked), traced runs of
# either tree (layers-*.json kept under chiprun_out/pr54_out).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr54_cell.sh [parts]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
last() { tail -1 $1 | cut -c1-${2:-700}; }
cell() { # tree cell seed trace
  (cd $T/$1 && python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 \
      --trace $4 > $R/pr54_$1_$2_$3_$4.out 2> $R/pr54_$1_$2_$3_$4.err
   echo "$1 $2 seed $3 trace $4 rc=$?")
  grep "^checks" $R/pr54_$1_$2_$3_$4.out | cut -c1-300
  last $R/pr54_$1_$2_$3_$4.out ${5:-700}
}
pccp() { # cell seed-a seed-b
  cell parent $1 $2 0; cell change $1 $2 0
  cell change $1 $3 0; cell parent $1 $3 0
}
traced() { # tree cell seed
  cell $1 $2 $3 1 9000
  grep "^device time" $R/pr54_$1_$2_$3_1.out | cut -c1-1500
  mkdir -p $R/pr54_out/$1; cp $T/$1/chipbench/out/*.json $R/pr54_out/$1/ 2>/dev/null
}
for c in ${1:-sala traced}; do
  case $c in
    sala) pccp sala_serve_longctx 5400000101 5400000102 ;;
    sala2) pccp sala_serve_longctx 5400000103 5400000104 ;;
    sala3) pccp sala_serve_longctx 5400000105 5400000106 ;;
    sala4) pccp sala_serve_longctx 5400000107 5400000108 ;;
    traced) traced change sala_serve_longctx ${TRACED_SEED:-5400000111} ;;
    traced_parent) traced parent sala_serve_longctx ${TRACED_SEED:-5400000111} ;;
    solar) pccp solar2_serve_agent 5400000121 5400000122 ;;
    solar_traced) traced change solar2_serve_agent 5400000123 ;;
    solar_seed) # the traced run's seed, untraced, on both trees: its reading of the comparison
        cell parent solar2_serve_agent 5400000123 0; cell change solar2_serve_agent 5400000123 0 ;;
    solar_more) # the comparison's reading with the change on four more seeds
        for n in 5400000124 5400000125 5400000126 5400000127; do cell change solar2_serve_agent $n 0; done ;;
    falcon) pccp falconh1_serve_chat 5400000131 5400000132 ;;
    exaone) pccp exaone_serve_reason 5400000141 5400000142 ;;
    mimo) pccp mimo_serve_longshort 5400000151 5400000152 ;;
    opt) pccp opt_serve_backlog 5400000161 5400000162 ;;
    mistral) pccp mistral4_serve_longdoc 5400000171 5400000172 ;;
  esac
done
