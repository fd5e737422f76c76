#!/bin/sh
# PR 54, the final tree (scratch/change = git archive $(git write-tree)):
# the probe's table and rows sweep from the files git would commit, a
# seventh and eighth pair of sala_serve_longctx, solar2_serve_agent's traced
# seed untraced on both trees, and P C C P of the cells
# whose programs lower to the parent's text (falcon-h1, opt, mimo,
# mistral4: their chunks keep the walk).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr54_final.sh
mkdir -p chiprun_out
(cd scratch/change && sh benchmarks/runs/pr54_probe.sh "table rows" \
    > ../../chiprun_out/pr54_probe_final.out 2>&1)
grep -a '"phase"' chiprun_out/pr54_probe_final.out | cut -c1-420
sh benchmarks/runs/pr54_cell.sh "${1:-sala4 solar_seed falcon opt mimo mistral}"
