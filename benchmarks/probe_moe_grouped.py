"""The gated layer's routed product alone on the chip, in its two forms, at
the three serving cells' shapes: the dense form (every held expert over every
row, ``ops.moe._experts_dense``) beside the grouped form (the held (row,
expert) pairs sorted by expert, three grouped products over a buffer of the
worst case, ``ops.moe._experts_grouped``).  What it is for is the crossover
``ops.moe.GROUPED_MIN_ROWS`` and the tiles ``ops.moe.GROUPED_TILES``.

THE CELL'S TRACE DECIDES, NOT THIS PROBE (``bench_decode_kernel.py`` says
why): a form alone has the chip's fast memory and its weights' stream to
itself.

Shapes (16 held experts each; read 0.98 / 0.98 / 1.47 ms at the HBM's peak):
``mistral4`` 4096 x 2048, top 4 of 128; ``mimo`` the same matrices, top 8 of
256; ``exaone`` 6144 x 2048, top 8 of 128.  Rows 64 / 256 / 512 / 1024 /
2048.  Routing ``uniform`` (each row k distinct experts of all E, drawn from
the seed: k x 16 / E of a row's pairs are held) and ``skewed`` (every row to
held experts 0 and 1, its other choices elsewhere: two groups of n rows).
One JSON line a (shape, rows, routing) on stdout: ``dense_ms`` and
``grouped_ms`` are device milliseconds a call by the host's clock, twenty
calls dispatched back to back and fenced once, the best of three; both sides
include what turns the routing into their operands (the dense form's weights
a held expert, the grouped form's sort, gather and sum back) and neither the
router's scores nor the shared expert.  ``--tiles`` sweeps the grouped
product's tiles in place of the table; ``--ops`` lists the
device's largest operations of one grouped call from a trace.  It refuses to
start without a TPU and names its device on every line.  Nothing of the
benchmark calls this.

    chiprun -- sh benchmarks/runs/pr52_probe.sh
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# name: (d, h, held, k, experts)
SHAPES = {"mistral4": (4096, 2048, 16, 4, 128),
          "mimo": (4096, 2048, 16, 8, 256),
          "exaone": (6144, 2048, 16, 8, 128)}
ROWS = (64, 256, 512, 1024, 2048)


def routing(kind, n, k, held, experts, seed):
    """``(choice, weight)`` (n, k): what ``ops.moe._scores`` would hand on."""
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        choice = np.argsort(rng.random((n, experts)), axis=1)[:, :k]
    else:
        choice = np.tile(np.concatenate(
            [[0, 1], held + np.arange(k - 2)]), (n, 1))
    weight = rng.random((n, k)).astype(np.float32)
    return choice.astype(np.int32), weight / weight.sum(1, keepdims=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--tiles", default="",
                    help="'128,1024,512;256,1024,512': the grouped form "
                         "alone at each of these tiles")
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--seed", type=int, default=52)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_moe_grouped times kernels: %s is not a TPU"
                         % dev.platform)
    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import moe

    arm_compile_cache()

    def ms(form, operands, calls=20):
        # a function of its own a call: jit's cache knows a function by
        # name, and the tiles are read as it is traced
        fn = jax.jit(lambda *a: form(*a))
        jax.block_until_ready(fn(*operands))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn(*operands)
            jax.block_until_ready(out)
            took = (time.perf_counter() - t0) / calls * 1e3
            best = took if best is None else min(best, took)
        return round(best, 4), fn

    def dense(xt, wg, wu, wd, choice, weight):
        here = choice[:, :, None] == jnp.arange(wg.shape[0])[None, None, :]
        return moe._experts_dense(xt, (wg, wu, wd), moe.BODIES["swiglu"],
                                  here, weight, "moe")

    def grouped(xt, wg, wu, wd, choice, weight):
        here = choice[:, :, None] == jnp.arange(wg.shape[0])[None, None, :]
        return moe._experts_grouped(xt, (wg, wu, wd), moe.BODIES["swiglu"],
                                    here, weight, "moe", False)

    def line(**kw):
        print(json.dumps(dict(kw, device=dev.device_kind)), flush=True)

    for name in args.shapes.split(","):
        d, h, held, k, experts = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        wg = 0.02 * jax.random.normal(keys[0], (held, d, h), jnp.bfloat16)
        wu = 0.02 * jax.random.normal(keys[1], (held, d, h), jnp.bfloat16)
        wd = 0.02 * jax.random.normal(keys[2], (held, h, d), jnp.bfloat16)
        sweep = [tuple(int(v) for v in t.split(","))
                 for t in args.tiles.split(";") if t]
        for n in ([2048] if args.ops
                  else [int(r) for r in args.rows.split(",")]):
            xt = jax.random.normal(keys[3], (n, d), jnp.bfloat16)
            for kind in ("uniform", "skewed"):
                choice, weight = routing(kind, n, k, held, experts,
                                         args.seed + n)
                operands = (xt, wg, wu, wd, jnp.asarray(choice),
                            jnp.asarray(weight))
                pairs = int((choice < held).sum())
                if sweep:
                    for tiles in sweep:
                        moe.GROUPED_TILES = tiles
                        try:
                            took = ms(grouped, operands)[0]
                        except Exception as e:          # the compiler's no
                            took = "%s: %s" % (type(e).__name__,
                                               str(e)[:160])
                        line(shape=name, rows=n, routing=kind, pairs=pairs,
                             tiles=tiles, grouped_ms=took)
                    continue
                g_ms, g_fn = ms(grouped, operands)
                if args.ops:
                    from chipbench import trace

                    logdir = tempfile.mkdtemp()
                    with jax.profiler.trace(logdir):
                        for _ in range(5):
                            out = g_fn(*operands)
                        jax.block_until_ready(out)
                    lines = next(iter(trace.load(trace.find_xplane(logdir))[
                        "devices"].values()))
                    by = {}
                    for op, _, dur in lines[trace.OPS_LINE]:
                        by[op] = by.get(op, 0) + dur / 5e6
                    line(shape=name, rows=n, routing=kind, pairs=pairs,
                         grouped_ms=g_ms, ops_ms={
                             op: round(v, 4) for op, v in sorted(
                                 by.items(), key=lambda kv: -kv[1])[:14]})
                    continue
                d_ms, d_fn = ms(dense, operands)
                a = np.asarray(d_fn(*operands), np.float32)
                b = np.asarray(g_fn(*operands), np.float32)
                line(shape=name, rows=n, routing=kind, pairs=pairs,
                     buffer_rows=n * min(k, held), dense_ms=d_ms,
                     grouped_ms=g_ms, tiles=moe.GROUPED_TILES,
                     max_abs_diff=round(float(np.abs(a - b).max()), 6),
                     max_abs=round(float(np.abs(a).max()), 4))


if __name__ == "__main__":
    main()
