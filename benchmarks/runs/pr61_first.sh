#!/bin/sh
# PR 61, the first call (scratch/parent = git archive HEAD, scratch/change =
# git archive $(git write-tree)): a training and a serving cell traced on
# each tree (do the per-layer metrics still come from scope_maps() /
# instruction_maps()?), then each cell parent, change, change, parent.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr61_first.sh
sh benchmarks/runs/cell.sh pr61 \
  runs:parent:rn50_train_bs256:1:6100000101 \
  runs:change:rn50_train_bs256:1:6100000101 \
  runs:parent:opt_serve_backlog:1:6100000102 \
  runs:change:opt_serve_backlog:1:6100000102 \
  pccp:opt_serve_backlog:6100000111:6100000112 \
  pccp:rn50_train_bs256:6100000121:6100000122
