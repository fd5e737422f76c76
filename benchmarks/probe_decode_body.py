"""What a step of the decode row's kernel is bound by, at a serving cell's
own shapes on the chip: ``ops.pallas_decode``'s kernel as it stands beside
copies of it with one part taken out (PR 60).

A variant is the module's SOURCE with one passage replaced (each passage is
asserted to be there) and loaded under another name: the product code has no
switch for any of this.  What a variant computes is wrong on purpose; only its
time is read.

* ``as_it_is``: the module, untouched.
* ``copies_only``: a step waits for its block's copies and sends zeros out:
  the copies' issue, their waits and the shares' copies, no arithmetic.
* ``no_scale_turn``: the block's scale rows are copied and never turned
  (``turned`` is ones): the select, the lane fold and the transposition of
  ``turned_scales`` are gone.
* ``one_wait``: a step waits ONCE a plane for its block's pages (a
  descriptor of the whole buffer's bytes) where the kernel waits a page at a
  time in a loop; the scale rows keep their loop.
* ``no_products``: both products read zeros where they read the block's
  planes: no load of a plane, no cast, no tile of it in the matrix unit; the
  softmax, the scale turn and the copies stay.

Device milliseconds a call, twenty calls back to back and fenced once, as
``bench_decode_kernel.py`` takes them; one JSON line a variant on stderr.

    chiprun -- python3 benchmarks/probe_decode_body.py [cell ...]
"""
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import bench_decode_kernel as probe

ATTEND = '        acc, stats = (whole if t.body == "whole" else grouped)(r, buf)\n'
WAIT = '        pages_of(r, buf, wait)\n        acc, stats'
VARIANTS = {
    "as_it_is": [],
    "copies_only": [(ATTEND, """\
        acc = jnp.zeros((t.rows, t.ow), jnp.float32)
        stats = jnp.zeros((t.rows, LANES), jnp.float32)
""")],
    "no_scale_turn": [
        ("        turned = turned_scales(r, buf) if t.quant else None\n",
         "        turned = jnp.ones((LANES, block), jnp.float32)\n"),
        ("            turned = turned_scales(r, buf)\n",
         "            turned = jnp.ones((LANES, block), jnp.float32)\n")],
    "one_wait": [(WAIT, """\
        for ref, bufs, sem in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
            pltpu.make_async_copy(ref.at[pl.ds(0, t.ppb)], bufs.at[buf],
                                  sems.at[buf, sem]).wait()
        if t.quant:
            def scale_row(i, _):
                pltpu.make_async_copy(s_hbm.at[pl.ds(0, 8)], sbuf.at[buf, i],
                                      sems.at[buf, 2]).wait()

            jax.lax.fori_loop(0, t.ppb, scale_row, None)
        acc, stats""")],
    "no_products": [
        ("lo:lo + width]  # (ppb, pt, E)\n"
         "        if x.dtype != mm:\n"
         "            x = x.astype(jnp.float32)\n"
         "        return x.reshape(block, x.shape[-1]).astype(mm)\n",
         "lo:lo + width]  # (ppb, pt, E)\n"
         "        return jnp.zeros((block, x.shape[-1]), mm)\n")],
}


def variant(name):
    """``ops.pallas_decode`` with the variant's passages replaced, as a
    module of its own."""
    from mxnet_tpu.ops import pallas_decode as pd

    if not VARIANTS[name]:
        return pd
    with open(pd.__file__) as f:
        source = f.read()
    for old, new in VARIANTS[name]:
        assert source.count(old) == 1, (name, old)
        source = source.replace(old, new)
    spec = importlib.util.spec_from_loader(
        "mxnet_tpu.ops.pallas_decode_" + name, loader=None,
        origin=pd.__file__)
    mod = importlib.util.module_from_spec(spec)
    mod.__package__ = "mxnet_tpu.ops"
    exec(compile(source, pd.__file__ + ":" + name, "exec"), mod.__dict__)
    return mod


def main():
    import jax

    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import pallas_decode as pd

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_decode_body times kernels: it needs the chip")
    arm_compile_cache()
    cells = sys.argv[1:] or ["solar2_serve_agent", "falconh1_serve_chat"]
    for cell in cells:
        node = next(n for n in probe.serving_nodes(cell)
                    if probe.decode_path(n) == "decode-kernel")
        args, attend = probe.case(node, 2 / 3)
        block = attn.live_block_plan(args[0].shape, args[3].shape,
                                     node["pt"])[0]
        tiles = pd.tiles(args[0].shape, args[1], args[2], node["heads"],
                         node["kv_heads"], block)
        total = np.asarray(args[4])
        live = int(np.sum(np.clip(-(-total // block), 1, None)))
        for name in VARIANTS:
            mod = variant(name)
            # ``Tiles.attend`` calls the module's own ``attend_blocks``:
            # hand the walk's caller the variant's
            took = mod.tiles(args[0].shape, args[1], args[2], node["heads"],
                             node["kv_heads"], block)
            fn = jax.jit(lambda *a, took=took: attn._attend_live_blocks(
                *a, node["heads"], None, node["kv_heads"], block, 1,
                value_scale=node["value_scale"], kernel=(took, False)))
            jax.block_until_ready(fn(*args))
            best = None
            for _ in range(3):
                tic = time.perf_counter()
                for _ in range(20):
                    last = fn(*args)
                jax.block_until_ready(last)
                took_ms = (time.perf_counter() - tic) / 20 * 1e3
                best = took_ms if best is None else min(best, took_ms)
            print(json.dumps({
                "phase": "decode_body", "cell": cell, "variant": name,
                "body": tiles.body, "live_blocks": live,
                "must_read_mb": round(
                    live * pd.block_bytes(tiles, args[1], args[2]) / 1e6, 2),
                "kernel_ms": round(best, 4),
                "us_a_block": round(best * 1e3 / live, 3),
                "device_kind": dev.device_kind}), file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
