# PR 50, first session: scratch/first_run.sh as it was handed to the chip tool (`chiprun -- sh scratch/first_run.sh`; its trees were scratch/parent = the parent under this PR's benchmark files, scratch/parent_src = the parent, scratch/change = the files git would commit).
set -x
mkdir -p chiprun_out
(cd scratch/parent && timeout 600 python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000001 --seconds 51 --trace 0 > /root/repo/chiprun_out/parent_new_cell.out 2> /root/repo/chiprun_out/parent_new_cell.err; echo "parent rc=$?"; tail -3 /root/repo/chiprun_out/parent_new_cell.err)
python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000002 --seconds 51 --trace 0 > chiprun_out/run_a.out 2> chiprun_out/run_a.err; echo "run_a rc=$?"; tail -5 chiprun_out/run_a.err; tail -8 chiprun_out/run_a.out | cut -c1-3000
python3 -m chipbench.run --workload mistral4_serve_longdoc --seed 5000000003 --seconds 51 --trace 1 > chiprun_out/run_b.out 2> chiprun_out/run_b.err; echo "run_b rc=$?"; tail -5 chiprun_out/run_b.err; tail -12 chiprun_out/run_b.out | cut -c1-6000
cp -r chipbench/out chiprun_out/out_first 2>/dev/null
