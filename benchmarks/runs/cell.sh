#!/bin/sh
# Runs of cells from unpacked trees: scratch/parent = git archive HEAD,
# scratch/change = git archive $(git write-tree), scratch/parent_bench = the
# parent under the change's benchmark files (README.md says how trees are
# unpacked).  The first argument is the prefix of what it writes under
# chiprun_out/ (pr61: chiprun_out/pr61_<tree>_<cell>_<seed>_<trace>.out); a
# part is
#   runs:<tree>:<cell>:<trace>:<seed>,<seed>,...   one run a seed
#   pccp:<cell>:<seed-a>:<seed-b>                  parent change change parent
#   chiprun --timeout 3550 -- sh benchmarks/runs/cell.sh <prefix> <part> ...
P=$1; shift
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
T=$(pwd)/scratch
cell() { # tree cell seed trace ("here": the tree the script runs from)
  dir=$T/$1; [ $1 = here ] && dir=$(pwd)
  out=$R/${P}_$1_$2_$3_$4
  began=$(date +%s)
  (cd $dir && python3 -m chipbench.run --workload $2 --seed $3 --seconds 51 \
      --trace $4 > $out.out 2> $out.err
   echo "$1 $2 seed $3 trace $4 rc=$? after $(( $(date +%s) - began )) s")
  grep "^checks\|^gaps" $out.out | cut -c1-400
  [ $4 = 1 ] && grep "^device time" $out.out | cut -c1-1500
  tail -1 $out.out | cut -c1-${LAST:-900}
  mkdir -p $R/${P}_out; cp $dir/chipbench/out/*.json $R/${P}_out/ 2>/dev/null
  if [ $4 = 1 ]; then
    LAST=6000; tail -1 $out.out | cut -c900-6000
  fi
  grep -v "^WARNING\|^$" $out.err | tail -3 | cut -c1-300
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
