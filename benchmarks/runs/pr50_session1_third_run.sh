# PR 50, first session: scratch/third_run.sh as it was handed to the chip tool (`chiprun -- sh scratch/third_run.sh`; its trees were scratch/parent = the parent under this PR's benchmark files, scratch/parent_src = the parent, scratch/change = the files git would commit).
mkdir -p chiprun_out
R=/root/repo/chiprun_out
python3 benchmarks/probe_mistral4_faults.py --seeds 5000000104,5000000105,5000000106 --faults 1 > $R/probe_2.out 2> $R/probe_2.err; echo "probe rc=$?"; grep -v "^WARNING" $R/probe_2.err | tail -3 | cut -c1-300; cat $R/probe_2.out | cut -c1-400
cd /root/repo/scratch/change
for s in 5000000201 5000000202 5000000203 5000000204 5000000205 5000000206; do
  python3 -m chipbench.run --workload mistral4_serve_longdoc --seed $s --seconds 51 --trace 0 > $R/setA_$s.out 2> $R/setA_$s.err; echo "setA $s rc=$?"; grep "^gaps\|^checks\|^run split" $R/setA_$s.out | cut -c1-400; tail -1 $R/setA_$s.out | cut -c1-600
done
python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000207 --seconds 51 --trace 1 > $R/setA_traced.out 2> $R/setA_traced.err; echo "traced rc=$?"; grep -v "^WARNING" $R/setA_traced.err | tail -3 | cut -c1-300; tail -14 $R/setA_traced.out | cut -c1-5000
mkdir -p $R/out_third; cp chipbench/out/*.json $R/out_third/ 2>/dev/null
cd /root/repo/scratch/parent
python3 -m chipbench.run --workload opt_serve_backlog --seed 5000000301 --seconds 51 --trace 1 > $R/parent_old_traced.out 2> $R/parent_old_traced.err; echo "parent old traced rc=$?"; tail -1 $R/parent_old_traced.out | cut -c1-2500
