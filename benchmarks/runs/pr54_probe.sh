#!/bin/sh
# PR 54: the chunk's kernel alone against the walk
#   chiprun -- sh benchmarks/runs/pr54_probe.sh [parts]
# table: at the cells' own shapes, the rule's own tiles and threshold;
# rows:  chunks of 128 / 256 / 512 / 1024 rows laid over the same nodes at
#        1 k and 4 k positions, the threshold lifted: CHUNK_MIN_ROWS' table;
# tiles: the kernel's tile of query rows fixed at 128 / 256 / 512.
mkdir -p chiprun_out
P="python3 benchmarks/probe_chunk_kernel.py"
lines() { grep -a '"phase"\|Error\|error' | cut -c1-700; }
for part in ${1:-table rows}; do
  case $part in
    table) $P 2>&1 | lines ;;
    rows) for n in 128 256 512 1024; do
            $P --chunk $n --contexts 1024,4096 --min-rows 0 \
               --cells sala_serve_longctx,solar2_serve_agent,falconh1_serve_chat 2>&1 | lines
          done ;;
    tiles) for rows in 128 256 512; do
             $P --rows $rows --cells sala_serve_longctx,solar2_serve_agent 2>&1 | lines
           done ;;
  esac
done
