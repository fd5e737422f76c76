"""The expanded latent chunk alone, at ``mistral4_serve_longdoc``'s own shapes
on the chip (ONE slot's 2048 query rows of 32 heads over a bfloat16 plane of
the cell's 83,201 pages, the chunk's last row at 16k / 32k / 64k positions),
in three forms, ms a layer:

``walk``
    ``latent_attend`` shown no Pallas: ``_attend_live_blocks``' running row, a
    block of 512 a step, the float32 scores through HBM (the parent's
    program);
``segments_<segment>_<block>_<tile>``
    the walk by segments with ``pallas_decode.attend_latent_segment`` as its
    step (``ops.attention._attend_latent_segments``: what the tree runs), the
    segment, the kernel's block and its tile of query rows swept;
``in_kernel_<tile>``
    the other form PR 62 weighed and did NOT land, kept here with its numbers:
    ONE kernel, head-major, that copies a block's pages as they lie and
    expands them itself (:func:`attend_in_kernel`): a row of the plane holds
    two positions, so a head's weights are laid block-diagonally over them,
    ``(640, 512)``: 2.5 x the expansion's arithmetic, paid once a (head,
    block); every head reads the slot's pages again.

THE CELL'S TRACE DECIDES, NOT THIS PROBE (``bench_decode_kernel.py`` says
why).  What this probe is for is the choice between the two forms and the
constants ``pallas_decode.LATENT_CHUNK_SEGMENT`` / ``_BLOCK`` / ``_TILE_ROWS``
and the table in their comment.

``ms`` is device milliseconds a call by the host's clock, five calls
dispatched back to back and fenced once, the best of three.  ``tflops`` counts
what the reader of ``attn_latent_chunk_roofline_pct`` counts: the two products
over the positions a row sees (causal) and the expansion of every live
position once.  It refuses to start without a TPU and names its device on
every line.  Nothing of the benchmark calls this.

    chiprun -- sh benchmarks/runs/pr62_probe.sh
"""
import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import numpy as np

from probe_latent_decode import CELL, cell_shapes

CONTEXTS = (16384, 32768, 65536)
ROWS = 2048
# (segment, block, tile of rows) of the segments' form
SWEEP = ((8192, 1024, 256), (4096, 1024, 256), (16384, 1024, 256),
         (8192, 512, 256), (8192, 2048, 256), (8192, 1024, 512),
         (8192, 512, 512), (8192, 1024, 128), (8192, 2048, 128))
IN_KERNEL_TILES = (256, 512)
BLOCK = 512            # positions a step of the in-kernel form


# ---------------------------------------------------------------------------
# the form that did not land: the expansion inside the kernel
# ---------------------------------------------------------------------------
def _in_kernel(pages_ref, total_ref, q_ref, w_ref, hbm, acc_ref, stat_ref,
               buf, m_scr, l_scr, sems, *, spec, ppb, pr, tile, cap, tq):
    """One invocation is ONE head's ``tq`` rows against the slot's blocks up
    to the chunk's causal limit.  ``q_ref`` (1, tq, 128): the head's queries,
    ``[nope | rope]``; ``w_ref`` (1, 640, 512): its weights over a row's two
    positions, a position ``[k_nope | rope | v]`` of 256 columns; ``hbm`` the
    plane as it is stored."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_decode import LANES, _div, _pallas, _rem
    pl, pltpu = _pallas()

    half = ppb * pr                                 # rows of a block
    block = 2 * half
    nb = pages_ref.shape[0] // ppb
    fill = jnp.finfo(jnp.float32).min
    kw = spec.nope + spec.rope                      # 128: a head's key
    per = kw + spec.v                               # 256
    low = total_ref[0] - (tq - 1)
    visit = jnp.clip(_div(jnp.minimum(total_ref[0], cap) + block - 1, block),
                     1, nb)

    def pages_of(b, at, go, unrolled=False):
        def page(i, _):
            go(pltpu.make_async_copy(
                hbm.at[pages_ref[b * ppb + i]],
                buf.at[at, pl.ds(pl.multiple_of(i * pr, pr), pr)],
                sems.at[at]))

        if unrolled:
            for i in range(ppb):
                page(i, None)
        else:
            jax.lax.fori_loop(0, ppb, page, None)

    m_scr[...] = jnp.full(m_scr.shape, fill, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    pages_of(0, 0, lambda c: c.start())

    def attend(b, _):
        at = _rem(b, 2)

        @pl.when(b + 1 < visit)
        def _next():
            pages_of(b + 1, 1 - at, lambda c: c.start(), unrolled=True)

        pages_of(b, at, lambda c: c.wait())
        x = buf[at]                                 # (half, 640)
        y = jax.lax.dot_general(
            x, w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        # the even positions of the rows, then the odd ones
        keys = jnp.concatenate([y[:, :kw], y[:, per:per + kw]], axis=0)
        values = jnp.concatenate([y[:, kw:per], y[:, per + kw:]], axis=0)
        pos0 = b * block

        def update(r0, masked):
            rows = pl.ds(r0, tile)
            s = jax.lax.dot_general(
                q_ref[0, rows], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) \
                * jnp.float32(spec.scale)
            if masked:
                col = jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1)
                pos = pos0 + 2 * _rem(col, half) + _div(col, half)
                limit = jnp.minimum(low + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (tile, 1), 0), cap)
                s = jnp.where(pos < limit, s, fill)
            m0 = m_scr[rows]
            m1 = jnp.maximum(m0, jnp.max(s, axis=1, keepdims=True))
            shrink = jnp.exp(m0 - m1)
            p = jnp.exp(s - jnp.concatenate([m1] * (block // LANES), axis=1))
            l_scr[rows] = shrink * l_scr[rows] \
                + jnp.sum(p, axis=1, keepdims=True)
            m_scr[rows] = m1
            acc_ref[0, rows] = acc_ref[0, rows] * jnp.concatenate(
                [shrink] * (spec.v // LANES), axis=1) \
                + jax.lax.dot_general(
                    p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        def crossed(r, _):
            r0 = pl.multiple_of(r * tile, tile)

            @pl.when(pos0 < jnp.maximum(
                jnp.minimum(low + r0 + tile - 1, cap), 1))
            def _seen():
                update(r0, True)

        # as the landed kernel does: a block under every row's limit is one
        # straight run of the tiles
        every = pos0 + block <= jnp.minimum(low, cap)

        @pl.when(every)
        def _all():
            for r in range(tq // tile):
                update(r * tile, False)

        @pl.when(jnp.logical_not(every))
        def _edge():
            jax.lax.fori_loop(0, tq // tile, crossed, None)

    jax.lax.fori_loop(0, visit, attend, None)
    top = jax.lax.broadcasted_iota(jnp.int32, (8, tq), 0) == 0
    stat_ref[0] = jnp.where(top, m_scr[...].T[:8], l_scr[...].T[:8])


def attend_in_kernel(q_nope, q_rope, plane, table, total, w_kvb, spec, tile,
                     interpret=False):
    """``latent_attend``'s expanded chunk, ONE slot, by the in-kernel form:
    -> (1, tq, H * v)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, tq, h, _ = q_nope.shape
    width = spec.rank + spec.rope
    pr = plane.shape[1]
    pt = 2 * pr
    ppb = BLOCK // pt
    cap = table.shape[1] * pt
    nb = -(-table.shape[1] // ppb)
    pages = jnp.pad(table[0].astype(jnp.int32),
                    (0, nb * ppb - table.shape[1]))
    kw, per = spec.nope + spec.rope, spec.nope + spec.rope + spec.v
    q = jnp.concatenate([q_nope, q_rope], axis=-1)[0]
    q = jnp.swapaxes(q, 0, 1).astype(plane.dtype)           # (H, tq, 128)
    w = w_kvb.reshape(h, spec.nope + spec.v, spec.rank).astype(plane.dtype)
    one = jnp.zeros((h, width, per), plane.dtype)
    one = one.at[:, :spec.rank, :spec.nope].set(
        jnp.swapaxes(w[:, :spec.nope], 1, 2))
    one = one.at[:, :spec.rank, kw:].set(jnp.swapaxes(w[:, spec.nope:], 1, 2))
    one = one.at[:, spec.rank:, spec.nope:spec.nope + spec.rope].set(
        jnp.eye(spec.rope, dtype=plane.dtype))
    wbd = jnp.zeros((h, 2 * width, 2 * per), plane.dtype)
    wbd = wbd.at[:, :width, :per].set(one).at[:, width:, per:].set(one)
    head = lambda i, *_: (i, 0, 0)
    acc, stats = pl.pallas_call(
        functools.partial(_in_kernel, spec=spec, ppb=ppb, pr=pr, tile=tile,
                          cap=cap, tq=tq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h,),
            in_specs=[pl.BlockSpec((1, tq, kw), head),
                      pl.BlockSpec((1, 2 * width, 2 * per), head),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, tq, spec.v), head),
                       pl.BlockSpec((1, 8, tq), head)],
            scratch_shapes=[
                pltpu.VMEM((2, ppb * pr, 2 * width), plane.dtype),
                pltpu.VMEM((tq, 128), jnp.float32),
                pltpu.VMEM((tq, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((h, tq, spec.v), jnp.float32),
                   jax.ShapeDtypeStruct((h, 8, tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=96 << 20),
        name="latent_chunk_in_kernel",
        interpret=interpret,
    )(pages, jnp.reshape(total, (1,)).astype(jnp.int32), q, wbd, plane)
    out = acc / stats[:, 1][..., None]
    return jnp.swapaxes(out, 0, 1).reshape(1, tq, h * spec.v) \
        .astype(plane.dtype)


# ---------------------------------------------------------------------------
def flops(context, spec, rows=ROWS):
    """What the chunk needs: the two products over the positions each row
    sees, and every live position expanded once."""
    seen = sum(min(context - (rows - 1) + i, context) for i in range(rows))
    return 2 * spec.heads * seen * (spec.nope + spec.rope + spec.v) \
        + 2 * spec.heads * context * spec.rank * (spec.nope + spec.v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--contexts", default=",".join(map(str, CONTEXTS)))
    ap.add_argument("--forms", default="walk,segments,in_kernel")
    ap.add_argument("--sweep", default="")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_latent_chunk times kernels: %s is not a TPU"
                         % dev.platform)
    from chipbench import manifest
    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import pallas_decode as pd

    arm_compile_cache()
    peak = manifest.load_json(manifest.ROOT, manifest.HERE + "/peaks.json")[
        dev.device_kind]
    peak = peak.get("bf16_flops_per_s") or peak.get("flops_per_s")
    spec, b, m, pt = cell_shapes()
    width = spec.rank + spec.rope
    pages = b * m + 1
    keys = jax.random.split(jax.random.PRNGKey(62), 4)
    plane = jax.random.normal(keys[0], pd.latent_plane_shape(pages, pt, width),
                              jnp.bfloat16)
    q_nope = jax.random.normal(keys[1], (1, ROWS, spec.heads, spec.nope),
                               jnp.bfloat16)
    q_rope = jax.random.normal(keys[2], (1, ROWS, spec.heads, spec.rope),
                               jnp.bfloat16)
    w_kvb = 0.06 * jax.random.normal(
        keys[3], (spec.heads * (spec.nope + spec.v), spec.rank), jnp.bfloat16)
    table = jnp.asarray(
        np.random.RandomState(0).permutation(b * m)[:m].reshape(1, m) + 1,
        jnp.int32)

    def ms(fn, fn_args, calls=5):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*fn_args))
        best = None
        for _ in range(3):
            tic = time.perf_counter()
            for _ in range(calls):
                last = fn(*fn_args)
            jax.block_until_ready(last)
            took = (time.perf_counter() - tic) / calls * 1e3
            best = took if best is None else min(best, took)
        return best, out

    def attend(cache, total):
        return attn.latent_attend(q_nope, q_rope, cache, table, total, w_kvb,
                                  spec)

    sweep = [tuple(int(x) for x in s.split("x"))
             for s in args.sweep.split(",") if s] or SWEEP
    forms = args.forms.split(",")
    backend = attn._kernel_backend
    kept = (pd.LATENT_CHUNK_SEGMENT, pd.LATENT_CHUNK_BLOCK,
            pd.LATENT_CHUNK_TILE_ROWS)
    for context in (int(c) for c in args.contexts.split(",")):
        total = jnp.full((1,), context, jnp.int32)
        rows = []

        def tried(form, fn, fn_args, path=None):
            try:
                took, got = ms(fn, fn_args)
            except Exception as e:                          # noqa: BLE001
                print(json.dumps({
                    "phase": "latent_chunk", "form": form,
                    "context": context, "error": type(e).__name__ + ": "
                    + str(e)[:300], "device_kind": dev.device_kind}),
                    file=sys.stderr, flush=True)
                return
            assert path is None or attn.DECODE_PATH["last"] == path, \
                attn.DECODE_PATH
            rows.append((form, took, got))

        if "walk" in forms:
            try:
                attn._kernel_backend = lambda: (False, False)
                tried("walk", lambda c, t: attend(c, t), (plane, total),
                      "expanded")
            finally:
                attn._kernel_backend = backend
        if "segments" in forms:
            try:
                for seg, blk, tile in sweep:
                    pd.LATENT_CHUNK_SEGMENT, pd.LATENT_CHUNK_BLOCK = seg, blk
                    pd.LATENT_CHUNK_TILE_ROWS = (tile,)
                    tried("segments_%d_%d_%d" % (seg, blk, tile),
                          lambda c, t: attend(c, t), (plane, total),
                          "expanded-kernel")
            finally:
                (pd.LATENT_CHUNK_SEGMENT, pd.LATENT_CHUNK_BLOCK,
                 pd.LATENT_CHUNK_TILE_ROWS) = kept
        if "in_kernel" in forms:
            for tile in IN_KERNEL_TILES:
                tried("in_kernel_%d" % tile,
                      lambda c, t, tile=tile: attend_in_kernel(
                          q_nope, q_rope, c, table, t[0], w_kvb, spec, tile),
                      (plane, total))
        ref = np.asarray(rows[0][2], np.float32) if rows else None
        need = flops(context, spec)
        for form, took, got in rows:
            print(json.dumps({
                "phase": "latent_chunk", "cell": CELL, "form": form,
                "rows": ROWS, "context": context,
                "ms": round(took, 4),
                "us_a_block_of_512": round(took * 1e3 / (context / 512), 3),
                "gflop": round(need / 1e9, 2),
                "tflops": round(need / (took * 1e-3) / 1e12, 2),
                "roofline_pct": round(100 * need / peak / (took * 1e-3), 2),
                "max_abs_diff_to_first": float(abs(
                    np.asarray(got, np.float32) - ref).max()),
                "max_abs": float(abs(ref).max()),
                "device_kind": dev.device_kind}), file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
