#!/bin/sh
# PR 57: the planted faults and the float8 control of olmoh_serve_rollouts by
# the cell's own comparison, at the cell's size.  The readings in
# chipbench/configs/olmo-hybrid-7b.json's limits are this script's
# (chiprun_out/pr57_probe*.out); the probe refuses to run without a TPU and
# names the device in every line.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr57_probe.sh [seeds] [faults] [tag] [only]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
SEEDS=${1:-5700000701,5700000702,5700000703}
python3 benchmarks/probe_olmoh_faults.py --seeds $SEEDS --faults ${2:-2} \
    ${4:+--only $4} > $R/pr57_probe$3.out 2> $R/pr57_probe$3.err
echo "probe rc=$?"
grep -v "^WARNING" $R/pr57_probe$3.err | tail -5 | cut -c1-300
cut -c1-420 $R/pr57_probe$3.out
