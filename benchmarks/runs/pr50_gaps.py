"""PR 50, benchmark round: one run of a serving cell through
``chipbench.run`` as it is, with every tick's gap and weight written to
``$GAPS_OUT`` beside the result line (``timing.gaps`` is wrapped, nothing is
changed).  For holding two runs' ticks against each other, index by index:
the order of the backlog is fixed, so tick i does the same work in every run.

    GAPS_OUT=chiprun_out/gaps.json python3 benchmarks/runs/pr50_gaps.py \
        --workload mistral4_serve_longdoc --seed 1 --seconds 51 --trace 0
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import run, timing  # noqa: E402

_gaps = timing.gaps


def gaps(stamps, t0, active_before):
    values, weights = _gaps(stamps, t0, active_before)
    with open(os.environ["GAPS_OUT"], "w") as f:
        json.dump({"values_s": values, "weights": weights}, f)
    return values, weights


timing.gaps = gaps
sys.exit(run.main())
