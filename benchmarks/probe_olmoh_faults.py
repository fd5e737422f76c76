"""What the comparison that decides ``correct`` in ``olmoh_serve_rollouts``
reads when one of the mechanisms the configuration adds is at fault, at the
cell's own size on the chip.

For each seed, on the cell's seeded weights and by the cell's own comparison
(``serve_ticks.check_against_reference``: 1536 prompt tokens in three chunks
of 512 through the state rows and the int8 pages, Gated DeltaNet's matrix
chunk form; then 8 decoded positions, its step form; the maximum |log p - log
p_ref| against the plain float32 reference's one pass over the sequence that
variant decoded):

``sound``
    the serving programs as they are (what a run's ``checks:`` prints);
``beta_not_doubled``
    beta = sigmoid(.), in (0, 1): ``linear_allow_neg_eigval`` not honoured;
``no_decay``
    the state is never decayed (alpha = 1 a head);
``no_l2_norm``
    q and k go into the rule as the convolution left them;
``gate_sigmoid``
    the output gate is sigmoid(x W_g) and not silu(x W_g);
``norm_before``
    the norm stands before each sublayer (a graph built with ``norm_after``
    false: pre-norm over the same gains);
``qk_norm_by_head``
    the attention layers' q and k are normed a HEAD (a graph built with
    ``attn_qk_norm`` true, each gain's first 128 entries for every head) and
    not over the whole projection;
``tail_not_carried``
    a chunk's convolution starts from a zero tail: the first three positions
    of every chunk read zeros for what the chunk before held;
``corrected_before_decay``
    a step corrects the state by the delta rule first and decays it after:
    ``S <- alpha ((I - beta k k^T) S + beta k v^T)`` (a token at a time in
    the chunk program too);
``chunk_default_precision``
    the chunk form's products at the default precision (one bfloat16 pass on
    the chip) and not at ``ops.gdn.PRECISION``;
``fp8_weights``
    the serving programs as they are over weights rounded to float8_e4m3fn
    (the control for "a lower precision would fail").

Every reading is the cell's own comparison's, made as a run of the cell makes
it (``correct.compare_logp`` against the configuration's
``limits.serve_ticks``): ``ok`` is what the run's ``correct`` would have
been.  A limit that sees a mechanism reads ``ok`` true on every ``sound`` line
and false on that fault's; ``fp8_weights`` has to read false.  ``--faults N``
plants the faults on the first N seeds.  Every variant builds its own
predictor and drops it after its reading (a loaded program keeps its scratch
reserved, and two sets do not fit beside the pools).  One process, the
chip's: it refuses to start without one, and every line names the device it
ran on; one JSON line a (seed, variant); nothing of the benchmark calls this.

    chiprun --timeout 3400 -- sh benchmarks/runs/pr57_probe.sh
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
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf
from mxnet_tpu.ops import gdn

from probe_mistral4_faults import _coarse, sound_tree_after
from probe_solar2_faults import _step_corrected_first, masked

CELL = "olmoh_serve_rollouts"
PATCHED = ("beta_not_doubled", "no_decay", "no_l2_norm", "gate_sigmoid",
           "tail_not_carried", "corrected_before_decay",
           "chunk_default_precision")
BUILT = ("norm_before", "qk_norm_by_head")
WEIGHTS = ("fp8_weights",)
FAULTS = PATCHED + BUILT + WEIGHTS
READINGS = ("ok", "atol", "max_abs_dlogp", "positions")
SILU_ONE = 1.278464542761074    # silu(SILU_ONE) = 1


def _chunk_corrected_first(q, k, v, g, beta, s0, layer="gdn"):
    """A chunk a token at a time by :func:`_step_corrected_first` (g = 0 and
    beta = 0 past ``nvalid`` are the identity in either order)."""
    def step(s, x):
        o, s = _step_corrected_first(*x, s)
        return s, o

    s, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g[..., None], beta)))
    return jnp.moveaxis(o, 0, 1), s


@contextlib.contextmanager
def planted(which):
    """``ops.gdn`` with one fault while a variant's predictor is built (it
    reads ``gdn.mix`` through ``decode.state_ops()`` then) and its programs
    trace."""
    saved = {n: getattr(gdn, n) for n in ("mix", "_unit", "_delta_step",
                                          "_chunked", "BETA_SCALE",
                                          "PRECISION")}
    mix = saved["mix"]

    def no_decay(attrs, *ins, **kw):
        ins = list(ins)
        ins[7] = jnp.full_like(ins[7], -jnp.inf)    # A_log: -exp(.) = 0
        return mix(attrs, *ins, **kw)

    def tail_not_carried(attrs, *ins, state=None, nvalid=None, **kw):
        if state is not None and nvalid is not None:
            state = (jnp.zeros_like(state[0]),) + tuple(state[1:])
        return mix(attrs, *ins, state=state, nvalid=nvalid, **kw)

    def gate_sigmoid(attrs, *ins, **kw):
        ins = list(ins)
        gate, ins[5] = ins[5], jnp.full_like(ins[5], SILU_ONE)
        out, state, rows = mix(attrs, *ins, **kw)
        out = out.astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return out.astype(gate.dtype), state, rows

    if which == "beta_not_doubled":
        gdn.BETA_SCALE = 1.0
    elif which == "no_decay":
        gdn.mix = no_decay
    elif which == "tail_not_carried":
        gdn.mix = tail_not_carried
    elif which == "gate_sigmoid":
        gdn.mix = gate_sigmoid
    elif which == "corrected_before_decay":
        gdn._delta_step = masked(_step_corrected_first)
        gdn._chunked = _chunk_corrected_first
    elif which == "no_l2_norm":
        gdn._unit = lambda x, eps: x
    elif which == "chunk_default_precision":
        gdn.PRECISION = "default"
    elif which not in ("sound",) + BUILT + WEIGHTS:
        raise ValueError("unknown fault %r" % which)
    try:
        yield
    finally:
        for n, fn in saved.items():
            setattr(gdn, n, fn)


def built(cfg, params, which):
    """The configuration and the served tree of a fault that is a graph."""
    if which == "norm_before":
        return dict(cfg, norm_after=False), params
    if which == "qk_norm_by_head":
        hd = int(cfg["head_dim"])
        return dict(cfg, attn_qk_norm=True), {
            n: v[:hd] if n.endswith(("_q_norm_gamma", "_k_norm_gamma"))
            else v for n, v in params.items()}
    return cfg, params


def reading(cfg, traffic, params, seed, which, ctx, atol):
    """One variant's reading.  ``fp8_weights`` empties ``params`` as it
    rounds them (a leaf at a time: the chip never holds both trees whole):
    plant it last."""
    host, served = None, params
    if which == "fp8_weights":
        host = jax.device_get(params)
        served = {n: _coarse(params.pop(n)) for n in list(params)}
    graph, served = built(cfg, served, which)
    with planted(which):
        nd = {n: mx.nd.NDArray(v, ctx) for n, v in served.items()}
        pred = serve_ticks.build_server(harness.build_symbol(graph), traffic,
                                        nd, ctx)[0]
        del nd, served
        with sound_tree_after(pred, host):
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
            "probe_olmoh_faults reads the cell's comparison at the cell's "
            "size on the chip: jax.devices()[0] is %s (%s), not a TPU; the "
            "CPU test of every fault is tests/chipbench/test_olmo_hybrid.py"
            % (dev.platform, dev.device_kind))
    ctx = mx.tpu(0)
    loaded = manifest.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    atol = correct.limit(cfg, "serve_ticks",
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
