#!/bin/sh
# PR 54, second call: the probe's table at the rule's own tiles (512 rows at
# 32 heads, 256 at 64), the rows sweep (and the head loop unrolled by 2 and
# by 4, which Mosaic's fori_loop refuses: only 1 or all; the part is gone
# from pr54_probe.sh), then
# sala_serve_longctx P C C P and a traced run of the change.
sh benchmarks/runs/pr54_probe.sh "table rows unroll" > chiprun_out/pr54_probe_second.out 2>&1
cat chiprun_out/pr54_probe_second.out | cut -c1-420
sh benchmarks/runs/pr54_cell.sh "sala traced"
