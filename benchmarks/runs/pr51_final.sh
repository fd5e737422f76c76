#!/bin/sh
# PR 51, the final tree (scratch/change = git archive $(git write-tree)): a
# third P C C P of mistral4_serve_longdoc (pairs five and six) and a traced
# run, from the files git would commit.
#   chiprun --timeout 3000 -- sh benchmarks/runs/pr51_final.sh
TRACED_SEED=5100000112 sh benchmarks/runs/pr51_cell.sh "mistral3 traced"
