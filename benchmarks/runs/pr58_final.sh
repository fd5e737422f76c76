#!/bin/sh
# PR 58, the last call: a third set of pairs in each claimed cell, then the
# two other cells whose graphs carry a recurrent state through the walk this
# PR touched (decode.py's state branch), parent beside change.
#   chiprun --timeout 3550 -- sh benchmarks/runs/pr58_final.sh
sh benchmarks/runs/cell.sh pr58 \
  pccp:olmoh_serve_rollouts:5800000205:5800000206 \
  pccp:solar2_serve_agent:5800000215:5800000216 \
  pccp:falconh1_serve_chat:5800000231:5800000232 \
  runs:parent:sala_serve_longctx:0:5800000241 \
  runs:change:sala_serve_longctx:0:5800000241
