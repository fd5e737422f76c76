#!/bin/sh
# PR 53, second call: the op alone at the cell's shapes (its three forms
# against the recurrence, and what each takes), the comparison's sound reading
# on three more seeds, then the cell at warmup_ticks 768: once traced, twice
# plain, from the working tree.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr53_second.sh
mkdir -p chiprun_out
python3 benchmarks/probe_kda_forms.py > chiprun_out/pr53_forms.out 2> chiprun_out/pr53_forms.err
echo "forms rc=$?"; cut -c1-600 chiprun_out/pr53_forms.out
sh benchmarks/runs/pr53_probe.sh 5300000102,5300000103,5300000104 0 _sound
sh benchmarks/runs/cell.sh pr53 runs:here:solar2_serve_agent:1:5300000112 runs:here:solar2_serve_agent:0:5300000113,5300000114
