#!/bin/sh
# PR 54, third call: four more pairs of sala_serve_longctx, then
# solar2_serve_agent (its chunk program takes the kernel too) P C C P and a
# traced run of the change.
sh benchmarks/runs/pr54_cell.sh "sala2 sala3 solar solar_traced"
