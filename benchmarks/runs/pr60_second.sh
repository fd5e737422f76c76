#!/bin/sh
# PR 60, second call: what a step is bound by (probe_decode_body.py: the
# kernel beside copies of it with one part taken out, at Solar-Open2's and
# Falcon-H1's nodes), then the kernel alone at every cell's node from the
# tree the script is started from.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr60_second.sh
mkdir -p chiprun_out
python3 benchmarks/probe_decode_body.py "$@" > chiprun_out/pr60_body.out 2> chiprun_out/pr60_body.err
echo "body rc=$?"
grep '"phase"' chiprun_out/pr60_body.err | cut -c1-400
python3 benchmarks/bench_decode_kernel.py > chiprun_out/pr60_probe_here2.out 2> chiprun_out/pr60_probe_here2.err
echo "probe rc=$?"
grep '"phase"' chiprun_out/pr60_probe_here2.err | cut -c1-120,250-520
