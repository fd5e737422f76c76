#!/bin/sh
# PR 60, first call: the kernel alone on both trees (pr60_probe.sh), then a
# traced run of the claimed cell from the tree the script is started from.
#   chiprun --timeout 1800 -- sh benchmarks/runs/pr60_first.sh
sh benchmarks/runs/pr60_probe.sh
sh benchmarks/runs/cell.sh pr60 runs:here:solar2_serve_agent:1:6000000101
