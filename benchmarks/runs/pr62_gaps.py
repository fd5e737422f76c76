"""PR 62: one run of a serving cell through ``chipbench.run`` as it is, from
the tree it is started in, with every tick's gap and weight written to
``$GAPS_OUT`` (as ``pr50_gaps.py`` does: ``timing.gaps`` is wrapped, nothing
is changed) and, after the result line, the forms
``mx_attn_latent_dispatch_total{form}`` counted over the whole process.

    cd scratch/change && GAPS_OUT=... python3 ../../benchmarks/runs/pr62_gaps.py \
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
rc = run.main()
from mxnet_tpu import obs  # noqa: E402

counter = obs.registry.counter("mx_attn_latent_dispatch_total",
                               labels=("form",))
print("latent forms traced:", json.dumps({
    form: counter.labels(form=form).get()
    for form in ("expanded", "expanded-kernel", "absorbed",
                 "absorbed-kernel")}))
sys.exit(rc)
