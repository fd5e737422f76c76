"""What the comparison that decides ``correct`` in ``exaone_serve_reason``
reads when one of the mechanisms the configuration adds is at fault, at the
cell's own size on the chip.

For each seed, on the cell's seeded weights and by the cell's own comparison
(``serve_ticks_mtp.check_against_reference``: 1000 prompt tokens in chunks of
512, then 16 self-drafting ticks; the stack's log-probabilities at every
committed position and the block's at every drafted one, each against the
plain float32 reference over the sequence that variant committed):

``sound``
    the serving programs as they are (what a run's ``checks:`` prints);
``no_shared`` / ``no_factor`` / ``rope_on_full`` / ``no_qk_norm``
    the serving programs built with ``n_shared_experts`` 0, with
    ``routed_scaling_factor`` 1, with rotary on the full-attention layers
    too, without the per-head RMSNorm on q and k;
``ring_by_index``
    the window nodes attend every ring slot the length has reached (the
    ring masked by index and not by position: keys out of the window, a
    rejected draft's among them, stay in sight);
``stale_block_cache``
    the block writes only its second row a tick: the position of its first
    keeps what the last tick left there (a rejected draft's key after a
    rejection).

A limit that sees each mechanism lies over every ``sound`` reading and under
every other.  ``--control`` reads, in a process of its own, the reference with its
matrices rounded to float8_e4m3fn against itself over the same rows.
``--faults N`` plants the faults on the first N seeds (each
faulty variant compiles its own tick and chunk, and drops them after its
reading); the rest read ``sound`` alone, under programs compiled once.  One
process, the chip's; one JSON line a (seed, variant); nothing of the
benchmark calls this.

    chiprun --timeout 3000 -- python3 benchmarks/probe_exaone_faults.py \\
        --seeds 4600000101,4600000102,4600000103 --faults 2
"""
import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import mxnet_tpu as mx
from chipbench import correct, harness, manifest
from chipbench.drivers import serve_ticks_mtp as driver
from mxnet_tpu.ops import attention as attn

CELL = "exaone_serve_reason"
BUILT = {"sound": {}, "no_shared": {"n_shared_experts": 0},
         "no_factor": {"routed_scaling_factor": 1.0},
         "rope_on_full": {"full_attn_use_rope": True},
         "no_qk_norm": {"attn_qk_norm": False}}
PATCHED = ("ring_by_index", "stale_block_cache")
READINGS = ("max_abs_dlogp", "row_rms_median", "row_rms_mean", "row_rms_max")


def control(cfg, traffic, seed):
    """The reference with its matrices rounded to float8_e4m3fn against
    itself, over the rows the comparison reads (``chipbench.control``'s
    rounding and the driver's ``control_case``; the weights on the host:
    two trees do not fit the chip): every reading, the stack's rows and the
    block's apart."""
    import jax.numpy as jnp

    from chipbench.control import coarsen

    case = driver.control_case(cfg, traffic, seed)
    want = case["forward"](case["params"])
    got = case["forward"](coarsen(case["params"], jnp.float8_e4m3fn))
    n_stack = 2 * int(traffic["check_decode"]) + 1
    parts = {"stack": slice(0, n_stack), "block": slice(n_stack, None)}
    return {what: {k: v for k, v in driver.compare_rows(
        jax.nn.softmax(got[rows], axis=-1), want[rows], 0.0).items()
        if k in READINGS} for what, rows in parts.items()}


@contextlib.contextmanager
def planted(which):
    """``ops.attention`` with one fault while a variant's programs trace."""
    attend, append = attn.paged_attend, attn.paged_append_kv

    def ring_by_index(q, kp, vp, table, total_len, window=0, **kw):
        return attend(q, kp, vp, table, total_len, window=0, **kw)

    def second_row_only(kp, vp, table, k, v, start_pos, layer="attn", **kw):
        if layer == "mtp" and k.shape[1] == 2:
            return append(kp, vp, table, k[:, 1:], v[:, 1:],
                          jax.numpy.asarray(start_pos) + 1, layer=layer,
                          **kw)
        return append(kp, vp, table, k, v, start_pos, layer=layer, **kw)

    if which == "ring_by_index":
        attn.paged_attend = ring_by_index
    elif which == "stale_block_cache":
        attn.paged_append_kv = second_row_only
    try:
        yield
    finally:
        attn.paged_attend, attn.paged_append_kv = attend, append


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--faults", type=int, default=1)
    p.add_argument("--only", default="",
                   help="comma-separated faults to plant (default: all)")
    p.add_argument("--control", action="store_true",
                   help="read the float8 control alone (its own process: "
                        "its weights live on the host)")
    args = p.parse_args(argv)
    loaded = manifest.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    if args.control:
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps(dict(control(cfg, traffic, seed), seed=seed,
                                  variant="control")), flush=True)
        return 0
    ctx = mx.tpu(0) if jax.devices()[0].platform == "tpu" else mx.cpu()
    atol = correct.limit(cfg, driver.NAME, "logp_atol." + traffic["kv_dtype"])
    sym = harness.build_symbol(cfg)
    shapes = driver.weight_shapes(sym, cfg)
    preds = {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = driver.make_params(shapes, cfg, seed, cfg["serve_dtype"])
        jax.block_until_ready(params)
        nd = {n: mx.nd.NDArray(v, ctx) for n, v in params.items()}
        faults = [n for n in list(BUILT)[1:] + list(PATCHED)
                  if not args.only or n in args.only.split(",")]
        names = ["sound"] + (faults if i < args.faults else [])
        for name in names:
            # the sound programs stay loaded from seed to seed; a faulty
            # variant's are dropped with it (a loaded program keeps its
            # scratch reserved: seven variants' would not fit the chip)
            pred = preds.get(name) or driver.build_server(
                harness.build_symbol(cfg, **BUILT.get(name, {})), traffic,
                nd, ctx)[0]
            if name == "sound":
                preds.setdefault(name, pred)
            pred._env = dict(params)
            with planted(name):
                stack, block = driver.check_against_reference(
                    pred, cfg, traffic, params, seed, atol)
            pred._manager = None
            print(json.dumps({
                "seed": seed, "variant": name,
                "stack": {k: stack[k] for k in READINGS},
                "block": {k: block[k] for k in READINGS},
                "accepted": stack["ticks_accepted"],
                "rejected": stack["ticks_rejected"]}), flush=True)
            pred._env = {}
            del pred
            gc.collect()
        del params, nd
    return 0


if __name__ == "__main__":
    sys.exit(main())
