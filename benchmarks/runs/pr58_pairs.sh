#!/bin/sh
# PR 58, the pairs: both claimed cells parent, change, change, parent on two
# seeds a part, twice over, then a traced run of each tree in each cell
# (scratch/parent = git archive HEAD, scratch/change = git archive $(git
# write-tree)).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr58_pairs.sh
sh benchmarks/runs/cell.sh pr58 \
  pccp:olmoh_serve_rollouts:5800000201:5800000202 \
  pccp:solar2_serve_agent:5800000211:5800000212 \
  pccp:olmoh_serve_rollouts:5800000203:5800000204 \
  pccp:solar2_serve_agent:5800000213:5800000214 \
  runs:parent:olmoh_serve_rollouts:1:5800000221 \
  runs:change:olmoh_serve_rollouts:1:5800000221 \
  runs:parent:solar2_serve_agent:1:5800000222 \
  runs:change:solar2_serve_agent:1:5800000222
