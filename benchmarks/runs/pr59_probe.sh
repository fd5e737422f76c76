#!/bin/sh
# PR 59: the planted faults and the float8 control of nemotron3_serve_agent by
# the cell's own comparison, at the cell's size.  The readings in
# chipbench/configs/nemotron-3-nano-30b.json's limits are this script's
# (chiprun_out/pr59_probe*.out); the probe refuses to run without a TPU and
# names the device in every line.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr59_probe.sh [seeds] [faults] [tag] [only]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
SEEDS=${1:-5900000701,5900000702,5900000703}
python3 benchmarks/probe_nemotron3_faults.py --seeds $SEEDS --faults ${2:-2} \
    ${4:+--only $4} > $R/pr59_probe$3.out 2> $R/pr59_probe$3.err
echo "probe rc=$?"
grep -v "^WARNING" $R/pr59_probe$3.err | tail -5 | cut -c1-300
cut -c1-420 $R/pr59_probe$3.out
