#!/bin/sh
# PR 59: a trial of an init (the file's, as the tree holds it when this runs):
# sound on six seeds, the four faults that read nearest the control and the
# control on the first two.
#   chiprun --timeout 3400 -- sh benchmarks/runs/pr59_trial.sh [tag]
sh benchmarks/runs/pr59_probe.sh 5900000101,5900000103,5900000104,5900000109,5900000106,5900000111 2 _trial$1 rotation_applied,no_d_skip,no_scaling_factor,chunk_from_zero_state,fp8_weights
