#!/bin/sh
# PR 60, the last call, from the committed files (scratch/change = git
# archive $(git write-tree) of the final tree, scratch/parent = git archive
# HEAD): a third set of pairs in the claimed cell, then the two cells whose
# decode row takes the kernel with H = H_kv (the parent's program: the
# control), parent beside change.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr60_final.sh
sh benchmarks/runs/cell.sh pr60 \
  pccp:solar2_serve_agent:6000000215:6000000216 \
  pccp:opt_serve_backlog:6000000251:6000000252 \
  pccp:olmoh_serve_rollouts:6000000261:6000000262
