#!/bin/sh
# PR 60, the pairs: a traced run of each tree in the claimed cell on one
# seed, then the cell parent, change, change, parent on two seeds a part,
# twice over (scratch/parent = git archive HEAD, scratch/change = git
# archive $(git write-tree)).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr60_pairs.sh
sh benchmarks/runs/cell.sh pr60 \
  runs:parent:solar2_serve_agent:1:6000000222 \
  runs:change:solar2_serve_agent:1:6000000222 \
  pccp:solar2_serve_agent:6000000211:6000000212 \
  pccp:solar2_serve_agent:6000000213:6000000214
