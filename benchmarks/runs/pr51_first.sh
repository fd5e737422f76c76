#!/bin/sh
# PR 51, first cell call: the probe with the sweep's fourth step, then
# mistral4_serve_longdoc P C C P on two seeds and a traced run of the change.
#   chiprun --timeout 3000 -- sh benchmarks/runs/pr51_first.sh
sh benchmarks/runs/pr51_probe.sh | cut -c1-330
sh benchmarks/runs/pr51_cell.sh "mistral traced"
