"""``python -m chipbench.control --workload <name> --seeds a,b,c``: what the
comparison that decides ``correct`` reads when the thing compared is wrong
in the way a later PR is most tempted to make it wrong.

The control is the cell's plain reference put in the program's place with
its weight matrices rounded to the precision below the one the
configuration states: 8 bits (``float8_e4m3fn``: 3 bits of mantissa) where
the configuration computes in bfloat16, bfloat16 where it computes in
float32.  Only the matrices are rounded; activations and products stay as
the reference computes them, so this is the least that a program in that
precision would differ by, not all of it.  It is compared, as a run's system
output is and over the same rows (the loop driver's ``control_case``), with
the float32 reference on the same seeded weights and the same seeded inputs
at the cell's own size.  A cell's limit has to lie under the smallest reading
printed here and over the largest ``max_abs_dlogp`` its sound runs print
(``checks:`` in every run's output); ``PERF.md`` keeps both beside each
limit.  No benchmark run calls this; it needs the chip only for its speed
and its matmul precision, and runs no code of the program under test.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys

BELOW = {"bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn",
         "float32": "bfloat16"}


def coarsen(params, dtype):
    """``params`` rounded to ``dtype`` and back: matrices only, as a
    quantised program keeps its scales and biases wide."""
    return {k: (v.astype(dtype).astype(v.dtype) if v.ndim >= 2 else v)
            for k, v in params.items()}


def reading(loaded, seed, below=None):
    """``max |log p_control - log p_reference|`` for one seed, over the rows
    the cell's own comparison reads.  The cell's loop driver says what those
    are (its ``control_case``): nothing here knows a driver or a model."""
    import jax
    import jax.numpy as jnp

    from . import correct

    driver = importlib.import_module(
        "chipbench.drivers." + loaded["traffic"]["driver"])
    case = driver.control_case(loaded["config"], loaded["traffic"], seed)
    params, fwd = case["params"], case["forward"]
    jax.block_until_ready(params)
    want = fwd(params)
    got = fwd(coarsen(params, jnp.dtype(below or BELOW[case["dtype"]])))
    v = want.shape[-1]
    out = correct.compare_logp(
        jax.nn.softmax(got.reshape(-1, v).astype(jnp.float32), axis=-1),
        want.reshape(-1, v), 0.0)
    return out["max_abs_dlogp"]


def main(argv=None):
    p = argparse.ArgumentParser(prog="python -m chipbench.control")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated whole numbers")
    args = p.parse_args(argv)
    from . import manifest

    loaded = manifest.load_cell(args.workload)
    import jax

    dev = jax.devices()[0]
    seeds = [int(s) for s in args.seeds.split(",")]
    readings = [reading(loaded, s) for s in seeds]
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "control_max_abs_dlogp": readings,
                      "smallest": min(readings),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
