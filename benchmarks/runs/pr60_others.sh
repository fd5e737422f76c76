#!/bin/sh
# PR 60, the two other cells whose decode row takes the grouped body, parent
# change change parent on two seeds each, and a traced run of each tree in
# each (scratch/parent = git archive HEAD, scratch/change = git archive
# $(git write-tree)).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr60_others.sh
sh benchmarks/runs/cell.sh pr60 \
  pccp:mimo_serve_longshort:6000000231:6000000232 \
  pccp:falconh1_serve_chat:6000000241:6000000242 \
  runs:parent:mimo_serve_longshort:1:6000000233 \
  runs:change:mimo_serve_longshort:1:6000000233 \
  runs:parent:falconh1_serve_chat:1:6000000243 \
  runs:change:falconh1_serve_chat:1:6000000243
