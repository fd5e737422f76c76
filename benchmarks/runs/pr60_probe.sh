#!/bin/sh
# PR 60: the decode row's kernel alone at the serving cells' shapes
# (benchmarks/bench_decode_kernel.py: a third, two thirds and all of the
# view), the parent's tree first (scratch/parent = git archive HEAD, with
# this PR's probe copied over its own: the probe's list gained two cells),
# then the tree the script is started from.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr60_probe.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
for tree in parent here; do
  dir=$(pwd)/scratch/$tree; [ $tree = here ] && dir=$(pwd)
  began=$(date +%s)
  (cd $dir && python3 benchmarks/bench_decode_kernel.py \
      > $R/pr60_probe_$tree.out 2> $R/pr60_probe_$tree.err
   echo "probe $tree rc=$? after $(( $(date +%s) - began )) s")
  grep '"phase"' $R/pr60_probe_$tree.err | sed "s/^/$tree /" | cut -c1-700
done
