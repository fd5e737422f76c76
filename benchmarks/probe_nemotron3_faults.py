"""What the comparison that decides ``correct`` in ``nemotron3_serve_agent``
reads when one of the mechanisms the configuration adds is at fault, at the
cell's own size on the chip.

For each seed, on the cell's seeded weights and by the cell's own comparison
(``serve_ticks.check_against_reference``: 6144 prompt tokens in three chunks
of 2048 through the state rows and the int8 pages, the mixers' chunked scan
and the grouped routed product; then 8 decoded positions, the step form and
the dense product; log-probabilities against the plain float32 reference's
one pass over the sequence that variant decoded):

``sound``
    the serving programs as they are (what a run's ``checks:`` prints);
``relu_not_squared``
    an expert (and the shared one) is ``relu(x W_u) W_d``;
``no_scaling_factor``
    the routed weights are not multiplied by 2.5 (a graph built with
    ``routed_scaling_factor`` 1);
``no_shared_expert``
    the shared expert is left out (a graph built without it);
``weights_not_normalised``
    the six routed weights are the raw sigmoids (``norm_topk_prob`` false);
``rotation_applied``
    the attention layers rotate q and k at ``rope_theta`` (a graph built
    with the rotation the file's ``rope_theta`` would give);
``norm_over_whole``
    the gated norm is taken over all 4096 channels, not 8 groups of 512;
``no_d_skip``
    ``y = S C`` without ``D x``;
``chunk_from_zero_state``
    a chunk starts from a zeroed state whatever the slot carries;
``tail_not_carried``
    a chunk's convolution starts from a zero tail: the first three positions
    of every chunk read zeros for what the chunk before held;
``fp8_weights``
    the serving programs as they are over weights rounded to float8_e4m3fn
    (the control for "a lower precision would fail").

Every reading is the cell's own comparison's, made as a run of the cell makes
it (``serve_ticks_rows``: ``serve_ticks_mtp.compare_rows`` against the
configuration's ``limits.serve_ticks_rows``): ``ok`` is what the run's
``correct`` would have been.  ``--faults N`` plants the faults on the first N
seeds.  Every variant builds its own predictor and drops it after its reading
(a loaded program keeps its scratch reserved, and two sets do not fit beside
the pools).  One process, the chip's: it refuses to start without one, and
every line names the device it ran on; one JSON line a (seed, variant);
nothing of the benchmark calls this.

    chiprun --timeout 3400 -- sh benchmarks/runs/pr59_probe.sh
"""
import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from chipbench import correct, harness, manifest
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf, serve_ticks_rows
from mxnet_tpu.ops import moe, ssm

from probe_mistral4_faults import READINGS, _coarse, sound_tree_after

CELL = "nemotron3_serve_agent"
PATCHED = ("relu_not_squared", "norm_over_whole", "no_d_skip",
           "chunk_from_zero_state", "tail_not_carried")
# a graph built from the file with one key changed
BUILT = {"no_scaling_factor": {"routed_scaling_factor": 1.0},
         "no_shared_expert": {"n_shared_experts": 0},
         "weights_not_normalised": {"norm_topk_prob": False},
         "rotation_applied": {"attn_use_rope": True}}
WEIGHTS = ("fp8_weights",)
FAULTS = PATCHED + tuple(BUILT) + WEIGHTS


@contextlib.contextmanager
def planted(which):
    """``ops.ssm`` or ``ops.moe`` with one fault while a variant's predictor
    is built (it reads ``ssm.mix`` through ``decode.state_ops()`` then) and
    its programs trace."""
    mix, gate_norm, relu2 = ssm.mix, ssm._gate_norm, moe.BODIES["relu2"]

    def no_d_skip(attrs, *ins, **kw):
        ins = list(ins)
        ins[5] = jnp.zeros_like(ins[5])
        return mix(attrs, *ins, **kw)

    def chunk_start(keep_tail, keep_state):
        def faulty(attrs, *ins, state=None, nvalid=None, **kw):
            if state is not None and nvalid is not None:
                state = (state[0] if keep_tail else jnp.zeros_like(state[0]),
                         state[1] if keep_state else jnp.zeros_like(state[1]))
            return mix(attrs, *ins, state=state, nvalid=nvalid, **kw)
        return faulty

    if which == "relu_not_squared":
        moe.BODIES["relu2"] = (relu2[0], lambda u: jnp.maximum(u, 0),
                               relu2[2])
    elif which == "norm_over_whole":
        ssm._gate_norm = lambda y, z, gamma, groups, eps: gate_norm(
            y, z, gamma, 1, eps)
    elif which == "no_d_skip":
        ssm.mix = no_d_skip
    elif which == "chunk_from_zero_state":
        ssm.mix = chunk_start(True, False)
    elif which == "tail_not_carried":
        ssm.mix = chunk_start(False, True)
    elif which not in ("sound",) + tuple(BUILT) + WEIGHTS:
        raise ValueError("unknown fault %r" % which)
    try:
        yield
    finally:
        ssm.mix, ssm._gate_norm, moe.BODIES["relu2"] = mix, gate_norm, relu2


def reading(cfg, traffic, params, seed, which, ctx, atol):
    """One variant's reading.  ``fp8_weights`` empties ``params`` as it
    rounds them (a leaf at a time: the chip never holds both trees whole):
    plant it last."""
    host, served = None, params
    if which == "fp8_weights":
        host = jax.device_get(params)
        served = {n: _coarse(params.pop(n)) for n in list(params)}
    with planted(which):
        sym = harness.build_symbol(dict(cfg, **BUILT.get(which, {})))
        nd = {n: mx.nd.NDArray(served[n], ctx) for n in sym.list_arguments()
              if n in served}
        pred = serve_ticks.build_server(sym, traffic, nd, ctx)[0]
        del nd, served
        with serve_ticks_rows._by_rows(), sound_tree_after(pred, host):
            got = serve_ticks.check_against_reference(
                pred, cfg, traffic, params if host is None else host, seed,
                atol)[0]
    pred._manager = None
    pred._env = {}
    return got


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--faults", type=int, default=1)
    p.add_argument("--only", default="",
                   help="comma-separated faults to plant (default: all)")
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "probe_nemotron3_faults reads the cell's comparison at the "
            "cell's size on the chip: jax.devices()[0] is %s (%s), not a "
            "TPU; the CPU test of every fault is "
            "tests/chipbench/test_nemotron_3_nano.py"
            % (dev.platform, dev.device_kind))
    ctx = mx.tpu(0)
    loaded = manifest.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    atol = correct.limit(cfg, serve_ticks_rows.NAME,
                         "logp_atol." + traffic["kv_dtype"])
    shapes = serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)
    faults = [n for n in FAULTS         # fp8_weights last: it eats the tree
              if not args.only or n in args.only.split(",")]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = serve_ticks_by_leaf.make_params(shapes, cfg, seed,
                                                 cfg["serve_dtype"])
        jax.block_until_ready(params)
        for which in ["sound"] + (faults if i < args.faults else []):
            got = reading(cfg, traffic, params, seed, which, ctx, atol)
            print(json.dumps(dict(
                {k: got[k] for k in READINGS}, seed=seed, variant=which,
                device={"platform": dev.platform,
                        "kind": dev.device_kind})), flush=True)
            gc.collect()
        del params
    # which form each traced node's routed product took (a chunk's and a
    # decode row's, every variant's programs together)
    print(json.dumps({"forms": {
        k: v for k, v in harness.program_counters().items()
        if k.startswith("mx_moe_dispatch_total")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
