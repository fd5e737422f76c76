"""What the comparison that decides ``correct`` in ``mistral4_serve_longdoc``
reads when one of the mechanisms the configuration adds is at fault, at the
cell's own size on the chip.

For each seed, on the cell's seeded weights and by the cell's own comparison
(``serve_ticks.check_against_reference``: 12288 prompt tokens in chunks of
2048 through the latent pool, the expanded form; then 8 decoded positions,
the absorbed form; log-probabilities against the plain float32 reference's
one pass over the sequence that variant decoded):

``sound``
    the serving programs as they are (what a run's ``checks:`` prints);
``no_temperature``
    the query is not multiplied by 1 + beta ln(1 + floor(p / 8192));
``plain_rotary``
    plain rotary at ``rope_theta`` in place of YaRN's blended frequencies;
``no_mscale``
    the softmax scale without m(factor, mscale_all_dim)^2;
``rope_key_by_head``
    the rotary key part taken a head and not shared: head h reads it turned
    round by 2 h dims;
``latent_unnormed``
    the latent goes into the cache and the up-projection without its
    RMSNorm (a graph built without the ``_kv_a_norm`` nodes);
``absorbed_skips_rope``
    the absorbed form scores q_nope . W_k c alone (the decode rows read no
    rotary part);
``decode_rows_unrotated``
    a decode row appends its own rotary key part unrotated: the 8 decoded
    positions' keys are stale by their rotation, the prompt's are sound;
``fp8_weights``
    the serving programs as they are over weights rounded to float8_e4m3fn
    (the control for "a lower precision would fail").

Every reading is the cell's own comparison's, made as a run of the cell makes
it (``serve_ticks_rows``: ``serve_ticks_mtp.compare_rows`` against the
configuration's ``limits.serve_ticks_rows``): ``ok`` is what the run's
``correct`` would have been, by the median over the compared rows of a row's
root-mean-square difference over the vocabulary (``row_rms_median``), with
that difference's mean and maximum and the maximum |d log p| beside it.  A
limit that sees a mechanism reads ``ok`` true on every ``sound`` line and
false on that fault's; ``fp8_weights`` has to read false.  ``--faults N``
plants the faults on the first N seeds (each faulty variant compiles its own
tick and chunk, and drops them after its reading); the rest read ``sound``
alone, under programs compiled once.  One process, the chip's: it refuses to
start without one, and every line names the device it ran on; one JSON line
a (seed, variant); nothing of the benchmark calls this.  The readings the
configuration's limit quotes are ``benchmarks/runs/pr50_probe.sh``'s.

    chiprun --timeout 3400 -- sh benchmarks/runs/pr50_probe.sh
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
from mxnet_tpu.models import decoder_lm
from mxnet_tpu.ops import attention as attn

CELL = "mistral4_serve_longdoc"
PATCHED = ("no_temperature", "plain_rotary", "no_mscale", "rope_key_by_head",
           "absorbed_skips_rope", "decode_rows_unrotated")
BUILT = ("latent_unnormed",)
WEIGHTS = ("fp8_weights",)
FAULTS = PATCHED + BUILT + WEIGHTS
READINGS = ("ok", "limit", "statistic", "row_rms_median", "row_rms_mean",
            "row_rms_max", "max_abs_dlogp")


@contextlib.contextmanager
def planted(which):
    """``ops.attention`` (or, for ``latent_unnormed``, the builder) with one
    fault while a variant's graph is built and its programs trace."""
    saved = {n: getattr(attn, n) for n in (
        "latent_spec", "latent_query_scale", "latent_attend",
        "latent_rotate")}
    norm = decoder_lm.sym.RMSNorm
    spec_of, attend, rotate = (saved["latent_spec"], saved["latent_attend"],
                               saved["latent_rotate"])

    def plain_rotary(attrs):
        spec = spec_of(attrs)
        return spec._replace(trig_scale=1.0, inv_freq=tuple(
            float(x) for x in attn.rope_frequencies(
                spec.rope, float(attrs.get("rope_theta", 10000.0)))))

    def no_mscale(attrs):
        spec = spec_of(attrs)
        return spec._replace(scale=float(spec.nope + spec.rope) ** -0.5)

    def by_head(q_nope, q_rope, *rest, **kw):
        # q_h . roll(k, 2h) == roll(q_h, -2h) . k
        turned = jnp.stack([jnp.roll(q_rope[:, :, h], -2 * h, axis=-1)
                            for h in range(q_rope.shape[2])], axis=2)
        return attend(q_nope, turned, *rest, **kw)

    def skips_rope(q_nope, q_rope, *rest, **kw):
        if attn.latent_form(q_nope.shape[1]) == "absorbed":
            q_rope = jnp.zeros_like(q_rope)
        return attend(q_nope, q_rope, *rest, **kw)

    def decode_rows_unrotated(x, positions, heads, spec):
        if heads == 1 and x.shape[1] == 1:
            return x
        return rotate(x, positions, heads, spec)

    def unnormed(x, name="", **kw):
        return x if name.endswith("_kv_a_norm") else norm(x, name=name, **kw)

    if which == "no_temperature":
        attn.latent_query_scale = lambda positions, spec: None
    elif which == "plain_rotary":
        attn.latent_spec = plain_rotary
    elif which == "no_mscale":
        attn.latent_spec = no_mscale
    elif which == "rope_key_by_head":
        attn.latent_attend = by_head
    elif which == "absorbed_skips_rope":
        attn.latent_attend = skips_rope
    elif which == "decode_rows_unrotated":
        attn.latent_rotate = decode_rows_unrotated
    elif which == "latent_unnormed":
        decoder_lm.sym.RMSNorm = unnormed
    elif which not in ("sound",) + WEIGHTS:
        raise ValueError("unknown fault %r" % which)
    try:
        yield
    finally:
        for n, fn in saved.items():
            setattr(attn, n, fn)
        decoder_lm.sym.RMSNorm = norm


def _coarse(v):
    return v.astype(jnp.float8_e4m3fn).astype(v.dtype) if v.ndim >= 2 else v


def coarse(params):
    """The matrices rounded to float8_e4m3fn and back (the gains as they
    are): what serving in the nearest precision below would hold."""
    return {n: _coarse(v) for n, v in params.items()}


@contextlib.contextmanager
def sound_tree_after(pred, host):
    """Two trees do not fit the chip: while the programs serve the rounded
    one the sound one waits on the host (``host``; None: nothing to do), and
    comes back for the reference once the rounded one has gone."""
    plain = serve_ticks.reference_rows

    def rows(cfg, traffic):
        fwd = plain(cfg, traffic)

        def call(params, seq):
            pred._env = {}
            gc.collect()
            return fwd(jax.device_put(params), seq)

        return call

    if host is not None:
        serve_ticks.reference_rows = rows
    try:
        yield
    finally:
        serve_ticks.reference_rows = plain


def reading(cfg, traffic, params, seed, which, ctx, atol, pred=None):
    """One variant's reading, and its predictor (for ``sound``, to keep).
    ``fp8_weights`` empties ``params`` as it rounds them (a leaf at a time:
    the chip never holds both trees whole): plant it last."""
    host, served = None, params
    if which == "fp8_weights":
        host = jax.device_get(params)
        served = {n: _coarse(params.pop(n)) for n in list(params)}
    with planted(which):
        if pred is None:
            nd = {n: mx.nd.NDArray(v, ctx) for n, v in served.items()}
            pred = serve_ticks.build_server(harness.build_symbol(cfg),
                                            traffic, nd, ctx)[0]
            del nd
        else:
            pred._env = dict(served)
        del served
        with serve_ticks_rows._by_rows(), sound_tree_after(pred, host):
            got = serve_ticks.check_against_reference(
                pred, cfg, traffic, params if host is None else host, seed,
                atol)[0]
    pred._manager = None
    return got, pred


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
            "probe_mistral4_faults reads the cell's comparison at the cell's "
            "size on the chip: jax.devices()[0] is %s (%s), not a TPU; the "
            "CPU test of every fault is tests/test_latent_attention.py"
            % (dev.platform, dev.device_kind))
    ctx = mx.tpu(0)
    loaded = manifest.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    atol = correct.limit(cfg, serve_ticks_rows.NAME,
                         "logp_atol." + traffic["kv_dtype"])
    shapes = serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)
    faults = [n for n in FAULTS         # fp8_weights last: it eats the tree
              if not args.only or n in args.only.split(",")]
    sound = None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = serve_ticks_by_leaf.make_params(shapes, cfg, seed,
                                                 cfg["serve_dtype"])
        jax.block_until_ready(params)
        for which in ["sound"] + (faults if i < args.faults else []):
            # the sound programs stay loaded from seed to seed; a faulty
            # variant's are dropped with it (a loaded program keeps its
            # scratch reserved)
            got, pred = reading(cfg, traffic, params, seed, which, ctx, atol,
                                pred=sound if which == "sound" else None)
            print(json.dumps(dict(
                {k: got[k] for k in READINGS}, seed=seed, variant=which,
                device={"platform": dev.platform,
                        "kind": dev.device_kind})), flush=True)
            pred._env = {}
            if which == "sound":
                sound = pred
            del pred
            gc.collect()
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
