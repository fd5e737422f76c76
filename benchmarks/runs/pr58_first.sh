#!/bin/sh
# PR 58, first call: the step alone in both forms and the kernel's head-block
# sweep at both cells' shapes (pr58_probe.sh), then a traced run of each cell
# from the tree the script is started from.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr58_first.sh
sh benchmarks/runs/pr58_probe.sh
sh benchmarks/runs/cell.sh pr58 runs:here:olmoh_serve_rollouts:1:5800000101 runs:here:solar2_serve_agent:1:5800000102
