#!/bin/sh
# PR 57, the last call, from the unpacked trees (README.md): scratch/change =
# git archive $(git write-tree), what is handed in; scratch/parent = git
# archive HEAD; scratch/parent_bench = the parent under this PR's benchmark
# files.  The cell traced once and its second set of six plain runs from
# scratch/change; an accepted cell traced on the parent under this PR's
# benchmark files (what the driver lays over it); solar2_serve_agent, which
# shares ops.kda._step, ops.ssm._conv and the int8 pools with the new cell,
# parent change change parent.
#   chiprun --timeout 3500 -- sh benchmarks/runs/pr57_final.sh
sh benchmarks/runs/cell.sh pr57 runs:change:olmoh_serve_rollouts:1:5700000301 \
  runs:change:olmoh_serve_rollouts:0:5700000211,5700000212,5700000213,5700000214,5700000215,5700000216
python3 benchmarks/runs/pr57_spread.py chiprun_out/pr57_change_olmoh_serve_rollouts_570000021?_0.out
LAST=2500 sh benchmarks/runs/cell.sh pr57 runs:parent_bench:opt_serve_backlog:1:5700000401
sh benchmarks/runs/cell.sh pr57 pccp:solar2_serve_agent:5700000501:5700000502
