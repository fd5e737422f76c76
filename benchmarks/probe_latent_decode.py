"""The absorbed decode row of latent attention alone, at
``mistral4_serve_longdoc``'s own shapes on the chip: the walk over the live
blocks (``ops.attention._attend_live_blocks``'s loop: gather, re-layout, two
products; what the cell ran until PR 51) beside the Pallas kernel that copies
each live block's pages as they lie and multiplies them in fast memory
(``ops.pallas_decode.attend_latent_blocks``), and the kernel's step swept.

THE CELL'S TRACE DECIDES, NOT THIS PROBE (``bench_decode_kernel.py`` says
why).  What this probe is for is the price of one call against the bytes it
must read, and the choice of ``pallas_decode.LATENT_STEP_TOKENS``.

The shapes are read from the cell's files: 20 slots over a table of 66,560
positions, pages of 16, 32 heads, rank 256 + rope 64, a bfloat16 plane of the
cell's 83,201 pages drawn on the device.  For every slot at a context of 16k,
32k and 64k positions, one JSON line a form on stderr:

``walk_page_a_row``
    ``latent_attend`` over the plane stored ``(P, 16 * 320)``, shown no
    Pallas: the parent's program;
``walk``
    the same over the plane as it is stored now (``(P, 8, 640)``): what a
    call the kernel's rule refuses takes;
``kernel_<step>``
    the kernel by steps of 512, 1024, 2048 and 4096 positions.

``ms`` is device milliseconds a call by the host's clock, twenty calls
dispatched back to back and fenced once, the best of three; the two ``absorb``
products are inside on both sides.  ``us_a_block`` is that time over the live
blocks of 512 positions, ``at_hbm_peak_ms`` the live rows' bytes at the chip's
HBM peak (``chipbench/peaks.json``).  It refuses to start without a TPU and
names its device on every line.  Nothing of the benchmark calls this.

    chiprun -- sh benchmarks/runs/pr51_probe.sh
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

CELL = "mistral4_serve_longdoc"
CONTEXTS = (16384, 32768, 65536)
STEPS = (512, 1024, 2048, 4096)


def cell_shapes():
    """``(spec, slots, table width, page_tokens)`` of the cell's latent
    node, read from its configuration and traffic files."""
    from chipbench import harness, manifest
    from mxnet_tpu.ops import attention as attn

    loaded = manifest.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    node = next(n for n in harness.build_symbol(cfg)._topo()
                if not n.is_variable and n.op.name == attn.LATENT_OP)
    pt = int(traffic["page_tokens"])
    return (attn.latent_spec(node.parsed_attrs()), int(traffic["slots"]),
            int(traffic["cache_len"]) // pt, pt)


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_latent_decode times kernels: %s is not a TPU"
                         % dev.platform)
    from chipbench import manifest
    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import pallas_decode as pd

    arm_compile_cache()
    hbm = manifest.load_json(manifest.ROOT, manifest.HERE + "/peaks.json")[
        dev.device_kind]["hbm_bytes_per_s"]
    spec, b, m, pt = cell_shapes()
    width = spec.rank + spec.rope
    pages = b * m + 1
    keys = jax.random.split(jax.random.PRNGKey(51), 4)
    plane = jax.random.normal(keys[0], pd.latent_plane_shape(pages, pt, width),
                              jnp.bfloat16)
    a_row = plane.reshape(pages, pt * width)
    q_nope = jax.random.normal(keys[1], (b, 1, spec.heads, spec.nope),
                               jnp.bfloat16)
    q_rope = jax.random.normal(keys[2], (b, 1, spec.heads, spec.rope),
                               jnp.bfloat16)
    w_kvb = 0.06 * jax.random.normal(
        keys[3], (spec.heads * (spec.nope + spec.v), spec.rank), jnp.bfloat16)
    table = jnp.asarray(
        np.random.RandomState(0).permutation(b * m).reshape(b, m) + 1,
        jnp.int32)

    def ms(fn, args, calls=20):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*args))
        best = None
        for _ in range(3):
            tic = time.perf_counter()
            for _ in range(calls):
                last = fn(*args)
            jax.block_until_ready(last)
            took = (time.perf_counter() - tic) / calls * 1e3
            best = took if best is None else min(best, took)
        return best, out

    def attend(cache, total):
        return attn.latent_attend(q_nope, q_rope, cache, table, total, w_kvb,
                                  spec)

    backend, steps = attn._kernel_backend, pd.LATENT_STEP_TOKENS
    for context in CONTEXTS:
        total = jnp.full((b,), context, jnp.int32)
        live = b * -(-context // 512)
        must = b * context * width * 2
        rows = []
        try:
            attn._kernel_backend = lambda: (False, False)
            for form, cache in (("walk_page_a_row", a_row), ("walk", plane)):
                # (a new function a form: jit keeps its traces by function)
                took, ref = ms(lambda c, t: attend(c, t), (cache, total))
                assert attn.DECODE_PATH["last"] == "absorbed"
                rows.append((form, took, ref))
        finally:
            attn._kernel_backend = backend
        try:
            for step in STEPS:
                pd.LATENT_STEP_TOKENS = {0: step}
                took, got = ms(lambda c, t: attend(c, t), (plane, total))
                assert attn.DECODE_PATH["last"] == "absorbed-kernel"
                rows.append(("kernel_%d" % step, took, got))
        finally:
            pd.LATENT_STEP_TOKENS = steps
        ref = np.asarray(rows[0][2], np.float32)
        for form, took, got in rows:
            print(json.dumps({
                "phase": "latent_decode", "cell": CELL, "form": form,
                "slots": b, "context": context, "live_blocks_of_512": live,
                "must_read_mb": round(must / 1e6, 2),
                "at_hbm_peak_ms": round(must / hbm * 1e3, 4),
                "ms": round(took, 4),
                "us_a_block": round(took * 1e3 / live, 3),
                "hbm_util_pct": round(100 * must / hbm / (took * 1e-3), 2),
                "max_abs_diff_to_parent": float(abs(
                    np.asarray(got, np.float32) - ref).max()),
                "max_abs": float(abs(ref).max()),
                "device_kind": dev.device_kind}), file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
