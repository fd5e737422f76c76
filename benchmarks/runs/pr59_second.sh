#!/bin/sh
# PR 59, second call, under the limit set from the first call's readings: THE
# ROUND THAT COUNTS of the planted faults and the float8 control (the serving
# programs over weights rounded to float8_e4m3fn, by the cell's own
# comparison: python -m chipbench.control compares by the maximum, which a
# cell of this driver is not held to), two seeds each, a third seed sound.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr59_second.sh
sh benchmarks/runs/pr59_probe.sh 5900000701,5900000702,5900000703 2 _second
