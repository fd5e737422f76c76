#!/bin/sh
# PR 57, second call, the limit at 0.1 (set from the first call's readings
# alone): the round that counts (every planted fault and the control on two
# seeds, sound on a third), the op alone at the cell's shapes at each
# precision its chunk's products could take, then the first set of six plain
# runs of the cell from the working tree.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr57_second.sh
sh benchmarks/runs/pr57_probe.sh 5700000701,5700000702,5700000703 2
python3 benchmarks/probe_gdn_forms.py 2>/dev/null | cut -c1-700
sh benchmarks/runs/cell.sh pr57 \
  runs:here:olmoh_serve_rollouts:0:5700000201,5700000202,5700000203,5700000204,5700000205,5700000206
python3 benchmarks/runs/pr57_spread.py chiprun_out/pr57_here_olmoh_serve_rollouts_570000020?_0.out
