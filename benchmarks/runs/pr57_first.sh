#!/bin/sh
# PR 57, first call: the cell once traced from the working tree, every
# planted fault and the control on one seed, the reference's own float8
# control (python -m chipbench.control), then the cell on the parent
# (scratch/parent_bench = git archive HEAD under this PR's benchmark files),
# which has to fail soon and cleanly.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr57_first.sh
sh benchmarks/runs/cell.sh pr57 runs:here:olmoh_serve_rollouts:1:5700000111
sh benchmarks/runs/pr57_probe.sh 5700000101 1 _first
python3 -m chipbench.control --workload olmoh_serve_rollouts \
    --seeds 5700000101,5700000102 2>/dev/null | tail -1
sh benchmarks/runs/cell.sh pr57 runs:parent_bench:olmoh_serve_rollouts:0:5700000111
sh benchmarks/runs/cell.sh pr57 runs:here:olmoh_serve_rollouts:0:5700000112
