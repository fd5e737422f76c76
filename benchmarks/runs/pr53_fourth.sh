#!/bin/sh
# PR 53, fourth call, from the unpacked trees (what git would commit): the
# cell on the parent under this PR's benchmark files (it has to fail soon and
# cleanly), an accepted cell traced on that same parent (the new readers find
# nothing there and say nothing), then the cell on the change: once traced,
# three times plain.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr53_fourth.sh
sh benchmarks/runs/cell.sh pr53 runs:parent:solar2_serve_agent:0:5300000300 \
  runs:parent:falconh1_serve_chat:1:5300000305 \
  runs:change:solar2_serve_agent:1:5300000301 \
  runs:change:solar2_serve_agent:0:5300000302,5300000303,5300000304
