#!/bin/sh
# PR 58: the delta rule's decode step alone at both cells' shapes: both forms
# through mix, the kernel's head-block sweep, and the blocks moved with
# nothing computed.
#   chiprun -- sh benchmarks/runs/pr58_probe.sh
mkdir -p chiprun_out
python3 benchmarks/probe_delta_step.py > chiprun_out/pr58_probe.out 2> chiprun_out/pr58_probe.err
echo "probe rc=$?"; cut -c1-400 chiprun_out/pr58_probe.out
grep -v "^WARNING\|^$" chiprun_out/pr58_probe.err | tail -5 | cut -c1-300
