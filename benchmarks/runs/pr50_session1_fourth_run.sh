# PR 50, first session: scratch/fourth_run.sh as it was handed to the chip tool (`chiprun -- sh scratch/fourth_run.sh`; its trees were scratch/parent = the parent under this PR's benchmark files, scratch/parent_src = the parent, scratch/change = the files git would commit).
mkdir -p chiprun_out
R=/root/repo/chiprun_out
cd /root/repo/scratch/change
for s in 5000000401 5000000402 5000000403 5000000404 5000000405 5000000406; do
  python3 -m chipbench.run --workload mistral4_serve_longdoc --seed $s --seconds 51 --trace 0 > $R/setB_$s.out 2> $R/setB_$s.err; echo "setB $s rc=$?"; grep "^gaps\|^checks\|^run split" $R/setB_$s.out | cut -c1-500; tail -1 $R/setB_$s.out | cut -c1-600
done
python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000407 --seconds 51 --trace 1 > $R/setB_traced.out 2> $R/setB_traced.err; echo "traced rc=$?"; grep -v "^WARNING" $R/setB_traced.err | tail -3 | cut -c1-300; tail -14 $R/setB_traced.out | cut -c1-6000
mkdir -p $R/out_fourth; cp chipbench/out/*.json $R/out_fourth/ 2>/dev/null
run_opt() { # dir tag seed
  cd $1; python3 -m chipbench.run --workload opt_serve_backlog --seed $3 --seconds 51 --trace 0 > $R/opt_$2_$3.out 2> $R/opt_$2_$3.err; echo "opt $2 $3 rc=$?"; tail -1 $R/opt_$2_$3.out | cut -c1-600
}
run_opt /root/repo/scratch/parent_src parent 5000000501
run_opt /root/repo/scratch/change change 5000000501
run_opt /root/repo/scratch/change change 5000000502
run_opt /root/repo/scratch/parent_src parent 5000000502
