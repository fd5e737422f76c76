# PR 50, first session: scratch/fifth_run.sh as it was handed to the chip tool (`chiprun -- sh scratch/fifth_run.sh`; its trees were scratch/parent = the parent under this PR's benchmark files, scratch/parent_src = the parent, scratch/change = the files git would commit).
mkdir -p chiprun_out
R=/root/repo/chiprun_out
(cd /root/repo/scratch/parent && timeout 600 python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000601 --seconds 51 --trace 0 > $R/parent_new_cell2.out 2> $R/parent_new_cell2.err; echo "parent new cell rc=$?"; tail -2 $R/parent_new_cell2.err | cut -c1-200)
cd /root/repo/scratch/change
for s in 5000000611 5000000612 5000000613 5000000614 5000000615 5000000616; do
  python3 -m chipbench.run --workload mistral4_serve_longdoc --seed $s --seconds 51 --trace 0 > $R/setC_$s.out 2> $R/setC_$s.err; echo "setC $s rc=$?"; grep "^checks" $R/setC_$s.out | cut -c1-300; tail -1 $R/setC_$s.out | cut -c1-600
done
