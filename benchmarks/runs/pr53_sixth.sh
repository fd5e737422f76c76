#!/bin/sh
# PR 53, last call, from the trees unpacked anew after the last edit of the
# program (scratch/change = git archive $(git write-tree)): the cell once
# traced and twice plain, then pr53_fifth.sh's two accepted cells, parent,
# change, change, parent.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr53_sixth.sh
sh benchmarks/runs/cell.sh pr53 runs:change:solar2_serve_agent:1:5300000501 \
  runs:change:solar2_serve_agent:0:5300000502,5300000503
sh benchmarks/runs/pr53_fifth.sh
