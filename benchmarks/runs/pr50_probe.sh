#!/bin/sh
# PR 50, review round: the planted faults and the float8 control of
# mistral4_serve_longdoc by the cell's own comparison, at the cell's size.
# The readings in chipbench/configs/mistral-small-4-119b.json's limits are
# this script's (chiprun_out/pr50_probe.out); the probe refuses to run
# without a TPU and names the device in every line.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr50_probe.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
python3 benchmarks/probe_mistral4_faults.py \
    --seeds 5000000701,5000000702,5000000703 --faults 2 \
    > $R/pr50_probe.out 2> $R/pr50_probe.err
echo "probe rc=$?"
grep -v "^WARNING" $R/pr50_probe.err | tail -5 | cut -c1-300
cut -c1-420 $R/pr50_probe.out
