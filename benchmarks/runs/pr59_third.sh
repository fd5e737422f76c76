#!/bin/sh
# PR 59, third call: set A, six plain runs of the cell from the committed
# tree alone (scratch/change = git archive $(git write-tree)), a seed each,
# and one traced run of it.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr59_third.sh
sh benchmarks/runs/cell.sh pr59 runs:change:nemotron3_serve_agent:0:5900000201,5900000202,5900000203,5900000204,5900000205,5900000206
python3 benchmarks/runs/pr59_spread.py chiprun_out/pr59_change_nemotron3_serve_agent_590000020*_0.out
sh benchmarks/runs/cell.sh pr59 runs:change:nemotron3_serve_agent:1:5900000301
