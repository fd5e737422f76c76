"""PR 52: one run of a serving cell through ``chipbench.run`` as it is
(``pr50_gaps.py``'s wrapper: every tick's gap and weight go to ``$GAPS_OUT``),
and after it, on stderr, the form each program's gated expert layers took
(``DecodePredictor._moe_forms``: rows a slot -> a form a layer, in the walk's
order) and what ``mx_moe_dispatch_total{form}`` holds.

    GAPS_OUT=chiprun_out/gaps.json python3 benchmarks/runs/pr52_forms.py \
        --workload mistral4_serve_longdoc --seed 1 --seconds 51 --trace 0
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import run, timing  # noqa: E402
from mxnet_tpu import decode, obs  # noqa: E402

_gaps, _init, preds = timing.gaps, decode.DecodePredictor.__init__, []


def gaps(stamps, t0, active_before):
    values, weights = _gaps(stamps, t0, active_before)
    with open(os.environ["GAPS_OUT"], "w") as f:
        json.dump({"values_s": values, "weights": weights}, f)
    return values, weights


def init(self, *args, **kw):
    preds.append(self)
    return _init(self, *args, **kw)


timing.gaps = gaps
decode.DecodePredictor.__init__ = init
rc = run.main()
for pred in preds:
    print("moe_forms", json.dumps(
        {str(rows): forms for rows, forms in pred._moe_forms.items()}),
        file=sys.stderr)
print("mx_moe_dispatch_total", json.dumps(
    obs.registry.snapshot().get("mx_moe_dispatch_total", {}).get("series")),
    file=sys.stderr)
sys.exit(rc)
