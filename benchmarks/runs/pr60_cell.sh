#!/bin/sh
# PR 60: runs of the cells whose decode row takes the grouped body (and of
# the other cells, parent against change) from unpacked trees: scratch/parent = git archive HEAD,
# scratch/change = git archive $(git write-tree); this PR has no benchmark
# files of its own (README.md says how trees are unpacked).  A part is
#   runs:<tree>:<cell>:<trace>:<seed>,<seed>,...   one run a seed
#   pccp:<cell>:<seed-a>:<seed-b>                  parent change change parent
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr60_cell.sh <part> ...
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
cell() { # tree cell seed trace ("here": the tree the script runs from)
  dir=$T/$1; [ $1 = here ] && dir=$(pwd)
  began=$(date +%s)
  (cd $dir && python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 \
      --trace $4 > $R/pr60_$1_$2_$3_$4.out 2> $R/pr60_$1_$2_$3_$4.err
   echo "$1 $2 seed $3 trace $4 rc=$? after $(( $(date +%s) - began )) s")
  grep "^checks\|^gaps" $R/pr60_$1_$2_$3_$4.out | cut -c1-400
  [ $4 = 1 ] && grep "^device time" $R/pr60_$1_$2_$3_$4.out | cut -c1-1500
  tail -1 $R/pr60_$1_$2_$3_$4.out | cut -c1-${LAST:-900}
  mkdir -p $R/pr60_out; cp $dir/chipbench/out/*.json $R/pr60_out/ 2>/dev/null
  if [ $4 = 1 ]; then
    LAST=6000; tail -1 $R/pr60_$1_$2_$3_$4.out | cut -c900-6000
  fi
  grep -v "^WARNING\|^$" $R/pr60_$1_$2_$3_$4.err | tail -3 | cut -c1-300
}
for part in "$@"; do
  IFS=: read kind a b c d <<EOF
$part
EOF
  case $kind in
    runs) for seed in $(echo $d | tr , ' '); do cell $a $b $seed $c; done ;;
    pccp) cell parent $a $b 0; cell change $a $b 0
          cell change $a $c 0; cell parent $a $c 0 ;;
  esac
done
