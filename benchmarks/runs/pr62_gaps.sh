#!/bin/sh
# PR 62: every tick's gap of an untraced window of mistral4_serve_longdoc,
# parent then change on one seed (scratch/parent = git archive HEAD,
# scratch/change = git archive $(git write-tree)), and the latent forms each
# process traced.
#   chiprun --timeout 1500 -- sh benchmarks/runs/pr62_gaps.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
G=$(pwd)/benchmarks/runs/pr62_gaps.py
s=6200000311
for tree in parent change; do
  (cd scratch/$tree && GAPS_OUT=$R/pr62_gaps_${tree}_$s.json python3 $G \
      --workload mistral4_serve_longdoc --seed $s --seconds 51 --trace 0 \
      > $R/pr62_gaps_${tree}_$s.out 2> $R/pr62_gaps_${tree}_$s.err
   echo "$tree seed $s rc=$?")
  grep "^latent forms" $R/pr62_gaps_${tree}_$s.out
  grep '^{"correct"' $R/pr62_gaps_${tree}_$s.out | cut -c1-500
done
