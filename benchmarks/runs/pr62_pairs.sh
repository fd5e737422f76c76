#!/bin/sh
# PR 62, the pairs: a traced run of the change in the claimed cell (the
# parent's is pr62_first.sh's, on the same seed), then the cell parent,
# change, change, parent on two seeds a part, twice over (scratch/parent =
# git archive HEAD, scratch/change = git archive $(git write-tree)).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr62_pairs.sh
sh benchmarks/runs/cell.sh pr62 \
  runs:change:mistral4_serve_longdoc:1:6200000111 \
  pccp:mistral4_serve_longdoc:6200000211:6200000212 \
  pccp:mistral4_serve_longdoc:6200000213:6200000214
