#!/bin/sh
# PR 62, the first look at the cell: a traced run of each tree on one seed
# (scratch/parent = git archive HEAD, scratch/change = git archive
# $(git write-tree)).
#   chiprun --timeout 3000 -- sh benchmarks/runs/pr62_first.sh
sh benchmarks/runs/cell.sh pr62 \
  runs:parent:mistral4_serve_longdoc:1:6200000111 \
  runs:change:mistral4_serve_longdoc:1:6200000111
