#!/bin/sh
# PR 53, first call: every planted fault and the control on one seed, then
# the cell once traced from the working tree, then the cell on the parent
# (scratch/parent = git archive HEAD under this PR's benchmark files), which
# has to fail soon and cleanly.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr53_first.sh
sh benchmarks/runs/pr53_probe.sh 5300000101 1 _first
sh benchmarks/runs/cell.sh pr53 runs:here:solar2_serve_agent:1:5300000111
sh benchmarks/runs/cell.sh pr53 runs:parent:solar2_serve_agent:0:5300000111
