#!/bin/sh
# PR 56: a cell parent, change, change, parent on two seeds a part
# (scratch/parent = git archive HEAD, scratch/change = git archive $(git
# write-tree); benchmarks/runs/README.md says how the trees are unpacked),
# and traced runs of either tree (layers-*.json and moves-*.json kept under
# chiprun_out/pr56_out/<tree>).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr56_cell.sh "parts"
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
last() { tail -1 $1 | cut -c1-${2:-700}; }
cell() { # tree cell seed trace
  (cd $T/$1 && python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 \
      --trace $4 > $R/pr56_$1_$2_$3_$4.out 2> $R/pr56_$1_$2_$3_$4.err
   echo "$1 $2 seed $3 trace $4 rc=$?")
  grep "^checks" $R/pr56_$1_$2_$3_$4.out | cut -c1-300
  last $R/pr56_$1_$2_$3_$4.out ${5:-700}
}
pccp() { # cell seed-a seed-b
  cell parent $1 $2 0; cell change $1 $2 0
  cell change $1 $3 0; cell parent $1 $3 0
}
traced() { # tree cell seed
  rm -f $T/$1/chipbench/out/*.json
  cell $1 $2 $3 1 9000
  mkdir -p $R/pr56_out/$1
  cp $T/$1/chipbench/out/*.json $R/pr56_out/$1/ 2>/dev/null
}
for c in ${1:-sala traced traced_parent}; do
  case $c in
    sala) pccp sala_serve_longctx 5600000101 5600000102 ;;
    sala2) pccp sala_serve_longctx 5600000103 5600000104 ;;
    sala3) pccp sala_serve_longctx 5600000105 5600000106 ;;
    traced) traced change sala_serve_longctx 5600000111 ;;
    traced_parent) traced parent sala_serve_longctx 5600000111 ;;
    solar) pccp solar2_serve_agent 5600000121 5600000122 ;;
    solar_traced) traced change solar2_serve_agent 5600000123 ;;
    falcon) pccp falconh1_serve_chat 5600000131 5600000132 ;;
    falcon_traced) traced change falconh1_serve_chat 5600000133 ;;
    exaone) pccp exaone_serve_reason 5600000141 5600000142 ;;
    exaone_traced) traced change exaone_serve_reason 5600000143 ;;
    mimo) pccp mimo_serve_longshort 5600000151 5600000152 ;;
    mimo_traced) traced change mimo_serve_longshort 5600000153 ;;
    opt) pccp opt_serve_backlog 5600000161 5600000162 ;;
    opt_traced) traced change opt_serve_backlog 5600000163 ;;
    opt_traced_parent) traced parent opt_serve_backlog 5600000163 ;;
    opt2) pccp opt_serve_backlog 5600000261 5600000262 ;;
    sala4) pccp sala_serve_longctx 5600000201 5600000202 ;;
    falcon2) pccp falconh1_serve_chat 5600000231 5600000232 ;;
    mimo2) pccp mimo_serve_longshort 5600000251 5600000252 ;;
    solar2) cell parent solar2_serve_agent 5600000221 0; cell change solar2_serve_agent 5600000221 0 ;;
    exaone2) cell parent exaone_serve_reason 5600000241 0; cell change exaone_serve_reason 5600000241 0 ;;
    mistral) pccp mistral4_serve_longdoc 5600000171 5600000172 ;;
  esac
done
