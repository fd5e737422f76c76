#!/bin/sh
# PR 53, the review round's last call, from scratch/change (git archive
# $(git write-tree) after the last edit of the program: the op's block and
# beta's factor are constants now, the lowered text is the same): the cell
# traced once, the probe's beta_not_doubled (planted through kda.BETA_SCALE
# now) beside sound on a seed whose readings pr53_probe.sh printed, and one
# accepted cell traced.
#   chiprun --timeout 1700 -- sh benchmarks/runs/pr53_eighth.sh
mkdir -p chiprun_out
R=$(pwd)/chiprun_out
cd scratch/change || exit 1
run() { # cell seed
  began=$(date +%s)
  python3 -m chipbench.run --workload $1 --seed $2 --seconds 51 --trace 1 \
    > $R/pr53_eighth_$1_$2.out 2> $R/pr53_eighth_$1_$2.err
  echo "$1 seed $2 traced rc=$? after $(( $(date +%s) - began )) s"
  grep "^checks" $R/pr53_eighth_$1_$2.out | cut -c1-400
  tail -1 $R/pr53_eighth_$1_$2.out | cut -c1-5000
  grep -v "^WARNING\|^$" $R/pr53_eighth_$1_$2.err | tail -2 | cut -c1-300
}
run solar2_serve_agent 5300000801
python3 benchmarks/probe_solar2_faults.py --seeds 5300000701 --faults 1 \
  --only beta_not_doubled > $R/pr53_probe_eighth.out 2> $R/pr53_probe_eighth.err
echo "probe rc=$?"; cut -c1-420 $R/pr53_probe_eighth.out
grep -v "^WARNING\|^$" $R/pr53_probe_eighth.err | tail -3 | cut -c1-300
run falconh1_serve_chat 5300000802
