#!/bin/sh
# PR 62: the expanded latent chunk alone at mistral4_serve_longdoc's shapes
# (2048 rows x 32 heads, the chunk's last row at 16k / 32k / 64k): the walk,
# the walk by segments with the Pallas kernel as its step (segment, block and
# tile of rows swept), and the form that did not land, the expansion inside
# the kernel.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr62_probe.sh [probe's options]
mkdir -p chiprun_out
python3 benchmarks/probe_latent_chunk.py "$@" > chiprun_out/pr62_probe.out 2> chiprun_out/pr62_probe.err
echo "probe rc=$?"
grep '^{' chiprun_out/pr62_probe.err | cut -c1-600
grep -v '^{\|^WARNING\|^W0\|^I0' chiprun_out/pr62_probe.err | tail -15 | cut -c1-400
