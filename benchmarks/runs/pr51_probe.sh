#!/bin/sh
# PR 51: the absorbed decode row alone at mistral4_serve_longdoc's shapes:
# the walk (a page a row, as the parent stored the plane; and over the plane
# as it is stored now) beside the kernel by steps of 512, 1024 and 2048.
#   chiprun --timeout 1200 -- sh benchmarks/runs/pr51_probe.sh
mkdir -p chiprun_out
python3 benchmarks/probe_latent_decode.py > chiprun_out/pr51_probe.out 2> chiprun_out/pr51_probe.err
echo "probe rc=$?"
grep '^{' chiprun_out/pr51_probe.err | cut -c1-600
grep -v '^{\|^WARNING\|^W0\|^I0' chiprun_out/pr51_probe.err | tail -15 | cut -c1-400
