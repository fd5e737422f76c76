#!/bin/sh
# PR 52: the gated layer's routed product alone at the three serving cells'
# shapes: the dense form beside the grouped form at 64-2048 rows, uniform and
# skewed routing; the grouped product's tiles swept at 512 and 2048 rows; the device's
# largest operations of one grouped call.  (The first call also ran
# jax.lax.ragged_dot in megablox gmm's place, from a scratch file that
# patched it in: not kept; its readings are beside ops.moe.GROUPED_TILES.)
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr52_probe.sh [parts]
mkdir -p chiprun_out
R=chiprun_out
for part in ${1:-table tiles ops}; do
  case $part in
    table) python3 benchmarks/probe_moe_grouped.py > $R/pr52_probe_table.out 2> $R/pr52_probe_table.err
           echo "table rc=$?"; cut -c1-400 $R/pr52_probe_table.out ;;
    tiles) python3 benchmarks/probe_moe_grouped.py --rows ${ROWS:-512,2048} \
             --tiles "${TILES:-128,1024,512;128,2048,512;128,4096,512;128,2048,1024;256,2048,512}" \
             > $R/pr52_probe_tiles.out 2> $R/pr52_probe_tiles.err
           echo "tiles rc=$?"; cut -c1-400 $R/pr52_probe_tiles.out ;;
    ops) python3 benchmarks/probe_moe_grouped.py --shapes mistral4 --ops > $R/pr52_probe_ops.out 2> $R/pr52_probe_ops.err
         echo "ops rc=$?"; cut -c1-1500 $R/pr52_probe_ops.out ;;
  esac
  grep -v '^WARNING\|^W0\|^I0' $R/pr52_probe_$part.err | tail -5 | cut -c1-300
done
