#!/bin/sh
# PR 59, final call, from the committed tree alone (scratch/change): set B,
# six more plain runs of the cell; the forms its programs' routed products
# took; then the accepted cell that runs the most of the code this PR
# changed (the share form with a shared expert), parent change change parent
# (scratch/parent = git archive HEAD).
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr59_final.sh
sh benchmarks/runs/cell.sh pr59 runs:change:nemotron3_serve_agent:0:5900000211,5900000212,5900000213,5900000214,5900000215,5900000216
python3 benchmarks/runs/pr59_spread.py chiprun_out/pr59_change_nemotron3_serve_agent_590000021*_0.out
(cd scratch/change && sh benchmarks/runs/pr59_probe.sh 5900000704 0 _final)
sh benchmarks/runs/cell.sh pr59 pccp:exaone_serve_reason:5900000501:5900000502
