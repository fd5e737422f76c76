"""The decode row's attention alone, at the serving cells' own shapes on the
chip: the walk over the live blocks (``ops.attention._attend_live_blocks``'s
loop, what every cell ran until PR 48) beside the Pallas kernel that reads
each live block's pages from the pools once (``ops.pallas_decode``).

THE CELL'S TRACE DECIDES, NOT THIS PROBE.  A kernel alone runs with nothing
before or after it: no append into the same pools, no matrices between two
nodes' attention, fast memory to itself.  Kernel-alone rows ranked PR 42's
forms wrongly twice (``PERF.md`` section 6, PR 42).  What this probe is for
is the price of one call against the bytes it must read: each row prints the
live blocks' bytes in the pools and their time at the chip's HBM peak
(``chipbench/peaks.json``: 819 GB/s), so
that a form's distance from the memory's speed is read off one line.

For each cell whose decode step takes the kernel
(``ops.attention.decode_kernel_selected``), the shapes of its first such node
are read from the files under ``chipbench/configs`` and ``chipbench/traffic``
(:func:`serving_nodes`), int8 pools of the cell's slots are drawn, and the
slots' lengths are spread evenly over 0.5x to 1.5x of a third, two thirds and
all of the view.  ``walk_ms`` and ``kernel_ms`` are device milliseconds a
call, twenty calls dispatched back to back and fenced once; one JSON line a
row on stderr.  Needs the chip:

    chiprun -- python3 benchmarks/bench_decode_kernel.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SERVING_CELLS = ("opt_serve_backlog", "falconh1_serve_chat",
                 "mimo_serve_longshort", "exaone_serve_reason",
                 "sala_serve_longctx", "solar2_serve_agent",
                 "olmoh_serve_rollouts")
_FREE = ("data", "softmax_label", "mtp_data", "mtp_label")


def serving_nodes(cell):
    """One dict per ``dot_product_attention`` node of a serving cell's graph,
    in graph order, read from the cell's configuration and traffic files:
    what ``ops.attention.paged_attend`` is shown of it in the decode step
    (``rows`` query rows a slot) and in a prefill chunk."""
    from chipbench import harness, manifest
    from mxnet_tpu.ops.attention import sparse_spec

    loaded = manifest.load_cell(cell)
    cfg, traffic = loaded["config"], loaded["traffic"]
    sym = harness.build_symbol(cfg)
    internals = sym.get_internals()
    t = int(cfg["max_position_embeddings"])
    _, outs, _ = internals.infer_shape(
        **{a: (1, t) for a in sym.list_arguments() if a in _FREE})
    shape = dict(zip(internals.list_outputs(), outs))
    pt, chunk = int(traffic["page_tokens"]), int(traffic["prefill_chunk"])
    nodes = []
    for n in sym._topo():
        if n.is_variable or n.op.name != "dot_product_attention":
            continue
        a = n.parsed_attrs()
        q, k, v = (shape[src.name if src.is_variable
                         else src.name + "_output"] for src, _ in n.inputs[:3])
        window = int(a.get("window", 0) or 0)
        # a paged window node keeps a ring of its window and a chunk
        # (DecodePredictor._bind_cache_groups)
        cap = int(traffic["cache_len"])
        if window:
            cap = min(cap, -(-(window + chunk) // pt) * pt)
        nodes.append(dict(
            name=n.name, heads=int(a["num_heads"]),
            kv_heads=int(a.get("num_kv_heads", 0) or a["num_heads"]),
            e=q[2], ek=k[2], ev=v[2], window=window,
            sparse=sparse_spec(a) is not None, spec=sparse_spec(a),
            sink=bool(a.get("sink")),
            value_scale=float(a.get("value_scale", 1.0) or 1.0),
            slots=int(traffic["slots"]), cap=cap, pt=pt, chunk=chunk,
            rows=1 + int(traffic.get("spec_k", 0)),
            kv_dtype=traffic["kv_dtype"]))
    return nodes


def abstract_pools(node, pages=None):
    """The node's pools as the paged ops store them, shapes only."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import QuantKV, scale_group

    p = pages or node["slots"] * (node["cap"] // node["pt"]) + 1
    dt = jnp.dtype(node["kv_dtype"])
    k, v = (jax.ShapeDtypeStruct((p, node["pt"], w), dt)
            for w in (node["ek"], node["ev"]))
    if dt.itemsize > 1:
        return k, v
    return (QuantKV(k, jax.ShapeDtypeStruct(
        (p, node["pt"] * scale_group(node["kv_heads"])), jnp.float32)),
        QuantKV(v, None))


def decode_path(node, tq=None, mesh_active=False):
    """Which of ``paged_attend``'s paths the node takes with ``tq`` query
    rows a slot (the decode step's by default), on a backend that runs
    Pallas: what ``mx_attn_dispatch_total{path}`` counts."""
    from mxnet_tpu.ops import attention as attn

    tq = tq or node["rows"]
    if node["sparse"] and tq == 1:
        return "sparse"         # a decode row attends the list it chose
    b = node["slots"] if tq == node["rows"] else 1
    shape = (b, tq, node["e"])
    table = (b, node["cap"] // node["pt"])
    plan = attn.live_block_plan(shape, table, node["pt"],
                                mesh_active=mesh_active,
                                window=node["window"])
    if plan is None or (node["sparse"] and plan[0] % node["spec"].block):
        return "whole"
    shown = (shape, *abstract_pools(node), table, node["heads"],
             node["kv_heads"])
    take = None
    if not node["sparse"]:
        take, _ = attn.decode_kernel_selected(
            *shown, mesh_active=mesh_active, window=node["window"])
    if take is not None:
        return "decode-kernel"
    # a chunk of a sparse node lays its selection over the same walk
    chosen = None if not node["sparse"] else (
        (1, node["kv_heads"], tq, -(-node["cap"] // node["spec"].block)),
        node["spec"].block)
    take, _ = attn.chunk_kernel_selected(
        *shown, mesh_active=mesh_active, window=node["window"], chosen=chosen)
    return "walk" if take is None else "chunk-kernel"


def case(node, share, seed=0):
    """``(args, attend)``: the arrays of one decode call over the node's
    shapes, lengths around ``share`` of the view, and the attend as a
    function of them."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn

    b, pt = node["slots"], node["pt"]
    m = node["cap"] // pt
    rng = np.random.RandomState(seed)
    data = [jnp.asarray(rng.randint(-127, 128, (b * m + 1, pt, w), np.int8))
            for w in (node["ek"], node["ev"])]
    pools = [attn.QuantKV(data[0], jnp.asarray(rng.uniform(
        0.005, 0.02, (b * m + 1, pt * attn.scale_group(node["kv_heads"]))),
        jnp.float32)), attn.QuantKV(data[1], None)]
    table = jnp.asarray(rng.permutation(b * m).reshape(b, m) + 1, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, node["e"])), jnp.bfloat16)
    mean = share * node["cap"]
    total = np.full(b, mean) if share >= 1 \
        else np.linspace(0.5 * mean, 1.5 * mean, b)
    total = jnp.asarray(np.clip(total, 1, node["cap"]), jnp.int32)
    sink = jnp.asarray(rng.normal(size=(node["heads"],)), jnp.float32) \
        if node["sink"] else None

    def attend(q, kp, vp, table, total):
        return attn.paged_attend(
            q, kp, vp, table, total, num_heads=node["heads"],
            num_kv_heads=node["kv_heads"], sink=sink,
            value_scale=node["value_scale"])

    return (q, pools[0], pools[1], table, total), attend


def main():
    import jax

    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import pallas_decode as pd

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench_decode_kernel times kernels: it needs the "
                         "chip")
    arm_compile_cache()
    from chipbench import manifest

    # a device that is not in the table is an error, not a default
    hbm = manifest.load_json(manifest.ROOT, manifest.HERE + "/peaks.json")[
        dev.device_kind]["hbm_bytes_per_s"]

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

    backend = attn._kernel_backend
    for cell in SERVING_CELLS:
        node = next((n for n in serving_nodes(cell)
                     if decode_path(n) == "decode-kernel"), None)
        if node is None:
            print(json.dumps({"phase": "decode_kernel", "cell": cell,
                              "path": "no node of its decode step takes "
                                      "the kernel"}),
                  file=sys.stderr, flush=True)
            continue
        for share in (1 / 3, 2 / 3, 1):
            args, attend = case(node, share)
            block = attn.live_block_plan(args[0].shape, args[3].shape,
                                         node["pt"])[0]
            tiles = pd.tiles(args[0].shape, args[1], args[2], node["heads"],
                             node["kv_heads"], block)
            total = np.asarray(args[4])
            live = int(np.sum(np.where(
                total >= node["cap"], -(-node["cap"] // block),
                np.clip(-(-total // block), 1, None))))
            must = live * pd.block_bytes(tiles, args[1], args[2])
            t_kernel, got = ms(attend, args)
            assert attn.DECODE_PATH["last"] == "decode-kernel"
            try:
                # the parent's path: the same call on a backend that is
                # shown no Pallas
                attn._kernel_backend = lambda: (False, False)
                # (a new function: jit keeps its traces by function)
                t_walk, ref = ms(lambda *a: attend(*a), args)
                assert attn.DECODE_PATH["last"] == "walk"
            finally:
                attn._kernel_backend = backend
            print(json.dumps({
                "phase": "decode_kernel", "cell": cell, "node": node["name"],
                # (a tree from before PR 60 has the one body)
                "body": getattr(tiles, "body", "whole"),
                "slots": node["slots"], "view": node["cap"], "block": block,
                "live_share": round(share, 3), "live_blocks": live,
                "must_read_mb": round(must / 1e6, 2),
                "at_hbm_peak_ms": round(must / hbm * 1e3, 4),
                "walk_ms": round(t_walk, 4), "kernel_ms": round(t_kernel, 4),
                "kernel_us_a_block": round(t_kernel * 1e3 / live, 3),
                "speedup": round(t_walk / t_kernel, 3),
                "max_abs_diff": float(abs(
                    np.asarray(got, np.float32)
                    - np.asarray(ref, np.float32)).max()),
                "device_kind": dev.device_kind}), file=sys.stderr,
                flush=True)


if __name__ == "__main__":
    main()
