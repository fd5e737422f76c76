#!/bin/sh
# PR 52, the final tree (scratch/change = git archive $(git write-tree)):
# exaone_serve_reason P C C P (its chunk program of 512 rows takes the grouped
# form too), a seventh and eighth pair of mistral4_serve_longdoc, a traced run
# of the parent and of the change on one seed, and the probe's table at the
# constants the tree holds, from the files git would commit.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr52_final.sh
TRACED_SEED=5200000113 sh benchmarks/runs/pr52_cell.sh "exaone mistral4 traced traced_parent"
(cd scratch/change && sh benchmarks/runs/pr52_probe.sh table && cp chiprun_out/pr52_probe_table.out ../../chiprun_out/pr52_probe_table_final.out)
