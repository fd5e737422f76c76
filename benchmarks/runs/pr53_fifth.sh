#!/bin/sh
# PR 53, fifth call: two accepted cells that run code this PR touched
# (decoder_lm's builder, decode.py's state ops and counters), parent, change,
# change, parent, from the unpacked trees.  Their programs lower to the
# parent's text (`hashes.py` since PR 61); this is the check on the chip.
#   chiprun --timeout 3500 -- sh benchmarks/runs/pr53_fifth.sh
sh benchmarks/runs/cell.sh pr53 pccp:falconh1_serve_chat:5300000401:5300000402 \
  pccp:mimo_serve_longshort:5300000411:5300000412
