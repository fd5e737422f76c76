#!/bin/sh
# PR 61, the second call, from the committed files (scratch/change = git
# archive $(git write-tree) of the final code, scratch/parent = git archive
# HEAD): one cell of three more configurations, parent change change parent:
# a state group beside pages (solar2), the self-drafting programs (exaone),
# the LM train step.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr61_second.sh
sh benchmarks/runs/cell.sh pr61 \
  pccp:solar2_serve_agent:6100000211:6100000212 \
  pccp:exaone_serve_reason:6100000221:6100000222 \
  pccp:opt_train_t2048:6100000231:6100000232
