#!/bin/sh
# PR 62, the final tree (scratch/parent = git archive HEAD, scratch/change =
# git archive $(git write-tree)): every tick's gap of an untraced window of
# the claimed cell, parent then change (pr62_gaps.sh), a traced run of the
# change, and one cell of another configuration whose chunk goes through
# _attend_live_blocks (sala_serve_longctx: chunk_live_blocks), parent then
# change.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr62_final.sh
sh benchmarks/runs/pr62_gaps.sh
sh benchmarks/runs/cell.sh pr62f \
  runs:change:mistral4_serve_longdoc:1:6200000411 \
  runs:parent:sala_serve_longctx:0:6200000511 \
  runs:change:sala_serve_longctx:0:6200000511
