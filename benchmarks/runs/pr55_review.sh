#!/bin/sh
# PR 55, review round (one chip): the cells named, each traced cold and warm
# from scratch/change (pr55_cells.sh), then what the account costs on the
# chip's host now that top_span calls its base's enter and exit (both trees,
# turned round; last, and kept in chiprun_out/pr55_cost.out: the second call
# printed it first and the tail of its output no longer held it).  The calls
# of the round:
#   chiprun --timeout 3000 -- env SEED=5500000800 sh benchmarks/runs/pr55_cells.sh \
#       mistral4_serve_longdoc opt_train_t256 opt_serve_backlog
#   chiprun --timeout 3600 -- env SEED=5500000900 sh benchmarks/runs/pr55_review.sh \
#       mimo_serve_longshort falconh1_serve_chat exaone_serve_reason \
#       solar2_serve_agent opt_train_t2048 opt_train_t1024
#   chiprun --timeout 1800 -- env SEED=5500001000 sh benchmarks/runs/pr55_review.sh \
#       exaone_serve_reason          (the head's width probed under a name too)
sh benchmarks/runs/pr55_cells.sh "$@"
T=$(pwd)/scratch
for tree in change parent parent change; do
  (cd $T/$tree && PYTHONPATH=. python3 $T/change/benchmarks/runs/pr55_cost.py)
done | tee chiprun_out/pr55_cost.out
