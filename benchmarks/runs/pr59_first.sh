#!/bin/sh
# PR 59, first call: the routed product alone by how its stacks are stored
# (benchmarks/probe_moe_width.py), the cell once traced from the working
# tree, every planted fault and the control on one seed, then the cell on the
# parent (scratch/parent_bench = git archive HEAD under this PR's benchmark
# files), which has to fail soon and cleanly.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr59_first.sh
mkdir -p chiprun_out
python3 benchmarks/probe_moe_width.py 2> chiprun_out/pr59_width.err | tee chiprun_out/pr59_width.out
grep -v "^WARNING\|^$" chiprun_out/pr59_width.err | tail -3 | cut -c1-300
sh benchmarks/runs/cell.sh pr59 runs:here:nemotron3_serve_agent:1:5900000111
sh benchmarks/runs/pr59_probe.sh 5900000101 1 _first
sh benchmarks/runs/cell.sh pr59 runs:parent_bench:nemotron3_serve_agent:0:5900000111
