#!/bin/sh
# PR 59: the cell's comparison on sound programs alone, a seed a reading (how
# far the readings of one tree lie apart: a token's six experts are the top
# of 128 scores a few thousandths apart, and a rounding that flips one moves
# that token by a whole expert's part), the float8 control on two more seeds,
# then the cell on the parent under this PR's benchmark files, which has to
# fail soon.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr59_sound.sh
sh benchmarks/runs/pr59_probe.sh 5900000102,5900000103,5900000104,5900000105,5900000106,5900000107,5900000108,5900000109,5900000110,5900000112 0 _sound
sh benchmarks/runs/pr59_probe.sh 5900000113,5900000114 2 _control fp8_weights
sh benchmarks/runs/cell.sh pr59 runs:parent_bench:nemotron3_serve_agent:0:5900000111
