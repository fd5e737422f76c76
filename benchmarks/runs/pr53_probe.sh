#!/bin/sh
# PR 53: the planted faults and the float8 control of solar2_serve_agent by
# the cell's own comparison, at the cell's size.  The readings in
# chipbench/configs/solar-open2-250b.json's limits are this script's
# (chiprun_out/pr53_probe*.out); the probe refuses to run without a TPU and
# names the device in every line.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr53_probe.sh [seeds] [faults] [tag]
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
SEEDS=${1:-5300000701,5300000702,5300000703}
python3 benchmarks/probe_solar2_faults.py --seeds $SEEDS --faults ${2:-2} \
    > $R/pr53_probe$3.out 2> $R/pr53_probe$3.err
echo "probe rc=$?"
grep -v "^WARNING" $R/pr53_probe$3.err | tail -5 | cut -c1-300
cut -c1-420 $R/pr53_probe$3.out
