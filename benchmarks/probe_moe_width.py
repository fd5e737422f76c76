"""The routed product of experts whose width is no whole number of lane tiles
(Nemotron-3-Nano: 2688 x 1856, two matrices, relu^2; 64 held of 128, top 6),
alone on the chip, by how the two stacks are stored:

``rows``       both (held, 1856, 2688), a hidden unit a row: what the tree
               holds (``ops.moe.BODIES["relu2"]``); the grouped product reads
               W_u through megablox gmm's ``transpose_rhs``;
``columns``    W_u as (held, 2688, 1856): the chip keeps such a stack with
               2688 minor, and a call of the kernel pays a transposing copy;
``padded``     W_u (held, 2688, 1920) and W_d (held, 1920, 2688), the pad
               zero (``relu(0)^2 = 0``: exact): ISSUE 59's other choice.

For each, the grouped form at a chunk's rows and the dense form at a decode
tick's and at a chunk's (every held expert over every row: what the lane rule
alone would have given this model).  One JSON line a (layout, form, rows):
device milliseconds a call by the host's clock, twenty calls dispatched back
to back and fenced once, the best of three, routing uniform from the seed;
``read_ms``: the stacks' bytes at the HBM's peak.  THE CELL'S TRACE DECIDES,
NOT THIS PROBE.  It refuses to start without a TPU and names its device on
every line.  Nothing of the benchmark calls this.

    chiprun -- sh benchmarks/runs/pr59_probe_width.sh
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from probe_moe_grouped import routing

D, H, HELD, K, EXPERTS = 2688, 1856, 64, 6, 128
PAD = 1920


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_moe_width times kernels: %s is not a TPU"
                         % dev.platform)
    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import moe

    arm_compile_cache()
    parts, act, _ = moe.BODIES["relu2"]
    bodies = {"rows": (parts, act, True), "columns": (parts, act, False),
              "padded": (parts, act, False)}
    key = jax.random.key(59)
    draw = lambda i, *shape: (0.02 * jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)).astype(jnp.bfloat16)
    up, down = draw(0, HELD, H, D), draw(1, HELD, H, D)
    stacks = {
        "rows": (up, down),
        "columns": (jnp.swapaxes(up, 1, 2), down),
        "padded": (jnp.pad(jnp.swapaxes(up, 1, 2),
                           ((0, 0), (0, 0), (0, PAD - H))),
                   jnp.pad(down, ((0, 0), (0, PAD - H), (0, 0))))}
    jax.block_until_ready(stacks)

    def ms(fn, operands, calls=20):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*operands))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn(*operands)
            jax.block_until_ready(out)
            took = (time.perf_counter() - t0) / calls * 1e3
            best = took if best is None else min(best, took)
        return round(best, 4), out

    want = {}
    for rows in (64, 2048):
        xt = draw(2 + rows, rows, D) * 50
        choice, weight = routing("uniform", rows, K, HELD, EXPERTS, 59)
        choice, weight = jnp.asarray(choice), jnp.asarray(weight)
        for layout, ws in stacks.items():
            body = bodies[layout]
            for form in ("grouped", "dense"):
                if form == "grouped" and rows < 512 and layout != "rows":
                    continue

                def call(xt, choice, weight, *ws):
                    here = choice[:, :, None] == jnp.arange(HELD)[None, None]
                    if form == "dense":
                        return moe._experts_dense(xt, ws, body, here, weight,
                                                  "moe")
                    return moe._experts_grouped(xt, ws, body, here, weight,
                                                "moe", False)

                took, out = ms(call, (xt, choice, weight) + ws)
                ref = want.setdefault(rows, out)
                print(json.dumps({
                    "layout": layout, "form": form, "rows": rows, "ms": took,
                    "read_ms": round(sum(w.size for w in ws) * 2 / 819e6, 3),
                    "max_abs_diff_from_first": float(jnp.max(jnp.abs(
                        out.astype(jnp.float32) - ref.astype(jnp.float32)))),
                    "out_rms": float(jnp.sqrt(jnp.mean(jnp.square(
                        out.astype(jnp.float32))))),
                    "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
