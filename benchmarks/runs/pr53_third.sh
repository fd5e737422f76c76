#!/bin/sh
# PR 53, third call: the round of the probe that the limit quotes (every
# fault and the control on two seeds, sound on a third, under the limit as
# the configuration now states it), then two sets of six runs of the cell,
# each run a seed of its own, from the working tree.
#   chiprun --timeout 3500 -- sh benchmarks/runs/pr53_third.sh
sh benchmarks/runs/pr53_probe.sh 5300000701,5300000702,5300000703 2
sh benchmarks/runs/cell.sh pr53 \
  runs:here:solar2_serve_agent:0:5300000201,5300000202,5300000203,5300000204,5300000205,5300000206 \
  runs:here:solar2_serve_agent:0:5300000211,5300000212,5300000213,5300000214,5300000215,5300000216
