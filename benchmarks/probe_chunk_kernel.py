"""A prefill chunk's attention alone, at the serving cells' own shapes on the
chip: the walk over the live blocks (``ops.attention._attend_live_blocks``'s
loop, what every chunk ran until PR 54) beside the chunk's Pallas kernel
(``ops.pallas_decode.attend_chunk_blocks``), ms a layer.

THE CELL'S TRACE DECIDES, NOT THIS PROBE (``bench_decode_kernel.py`` says
why).  What this probe is for is the rule's constant,
``ops.attention.CHUNK_MIN_ROWS``: the fewest query rows at which the kernel
is ahead of the walk, and the table in its comment.

For each serving cell the shapes of its first full attention node are read
from the files under ``chipbench/configs`` and ``chipbench/traffic``
(``bench_decode_kernel.serving_nodes``); int8 pools of a few slots are
drawn, ONE slot's chunk of the cell's width is laid at each of the cell's
contexts (the slot's length through the chunk's last row), and both paths
are timed through ``_attend_live_blocks`` itself: twenty calls dispatched
back to back and fenced once, the best of three.  A sparse node (MiniCPM-
SALA's) is timed with every row choosing ``topk`` of the blocks it sees
(drawn, the first and the row's own always among them) and with nothing
chosen.  ``--rows`` fixes the kernel's tile of query rows (the rule's own
choice otherwise); ``--chunk`` and ``--contexts`` lay chunks of another width
at other contexts over the same nodes, and ``--min-rows 0`` lifts the rule's
threshold so that a small chunk is timed on the kernel too: the rows of the
table beside ``CHUNK_MIN_ROWS``.  One JSON line a row on stderr and in
``chiprun_out/probe_chunk_kernel.jsonl``.  Needs the chip:

    chiprun -- python3 benchmarks/probe_chunk_kernel.py
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import numpy as np

# cell -> the contexts its chunks are laid at
CONTEXTS = {
    "sala_serve_longctx": (16384, 32768, 65536),
    "solar2_serve_agent": (8192, 16384),
    "mimo_serve_longshort": (4096,),
    "falconh1_serve_chat": (1024,),
    "exaone_serve_reason": (2048, 6144),
    "opt_serve_backlog": (1024,),
}
POOL_SLOTS = 4


def case(node, total, spec=None, seed=0):
    """``(args, chosen)``: one slot's chunk over the node's shapes, its
    last row at position ``total - 1``."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn

    pt, tq = node["pt"], node["chunk"]
    m = node["cap"] // pt
    rng = np.random.RandomState(seed)
    pages = POOL_SLOTS * m + 1
    data = [jnp.asarray(rng.randint(-127, 128, (pages, pt, w), np.int8))
            for w in (node["ek"], node["ev"])]
    pools = [attn.QuantKV(data[0], jnp.asarray(rng.uniform(
        0.005, 0.02, (pages, pt * 2 * node["kv_heads"])), jnp.float32)),
        attn.QuantKV(data[1], None)]
    table = jnp.asarray(rng.permutation(pages - 1)[:m].reshape(1, m) + 1,
                        jnp.int32)
    # a chunk program's residual stream is float32 from its first int8
    # attention on
    q = jnp.asarray(rng.normal(size=(1, tq, node["e"])), jnp.float32)
    chosen = None
    if spec is not None:
        n = -(-node["cap"] // spec.block)
        own = (total - tq + np.arange(tq)) // spec.block      # a row's block
        score = rng.rand(node["kv_heads"], tq, n)
        score[:, :, 0] = 2.0
        score[:, np.arange(tq), own] = 2.0
        score = np.where(np.arange(n)[None, None] <= own[None, :, None],
                         score, -1.0)
        kth = -np.sort(-score, axis=-1)[..., spec.topk - 1:spec.topk]
        mask = (score >= kth) & (score >= 0)
        chosen = (jnp.asarray(mask[None]), spec.block)
    return (q, pools[0], pools[1], table,
            jnp.asarray([total], jnp.int32)), chosen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CONTEXTS))
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--min-rows", type=int, default=-1)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--contexts", default="")
    ap.add_argument("--calls", type=int, default=20)
    opts = ap.parse_args()

    import jax

    from bench_decode_kernel import serving_nodes
    from mxnet_tpu.cache_dirs import arm_compile_cache
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import pallas_decode as pd

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_chunk_kernel times kernels: it needs the "
                         "chip")
    arm_compile_cache()
    if opts.rows:
        pd.CHUNK_TILE_ROWS = (opts.rows,)
        pd._VMEM_BUDGET = 90 << 20
    if opts.min_rows >= 0:
        attn.CHUNK_MIN_ROWS = opts.min_rows
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/probe_chunk_kernel.jsonl", "a")

    def ms(fn, args):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*args))
        best = None
        for _ in range(3):
            tic = time.perf_counter()
            for _ in range(opts.calls):
                last = fn(*args)
            jax.block_until_ready(last)
            took = (time.perf_counter() - tic) / opts.calls * 1e3
            best = took if best is None else min(best, took)
        return best, out

    for cell in opts.cells.split(","):
        node = next(n for n in serving_nodes(cell) if not n["window"])
        if opts.chunk:
            node["chunk"] = opts.chunk
        spec = None
        if node["sparse"]:
            from chipbench import harness, manifest

            sym = harness.build_symbol(manifest.load_cell(cell)["config"])
            spec = next(s for s in (
                attn.sparse_spec(n.parsed_attrs()) for n in sym._topo()
                if not n.is_variable
                and n.op.name == "dot_product_attention") if s is not None)
        h, kvh = node["heads"], node["kv_heads"]
        for total in (map(int, opts.contexts.split(","))
                      if opts.contexts else CONTEXTS[cell]):
            total = max(min(total, node["cap"]), node["chunk"])
            for sparse in ((True, False) if spec is not None else (False,)):
                args, chosen = case(node, total, spec if sparse else None)
                q, kp, vp, table, tot = args
                plan = attn.live_block_plan(q.shape, table.shape, node["pt"])
                tiles, _ = attn.chunk_kernel_selected(
                    q.shape, kp, vp, table.shape, h, kvh,
                    chosen=None if chosen is None
                    else (chosen[0].shape, chosen[1]))

                def attend(chunk, mask, q, kp, vp, table, tot):
                    return attn._attend_live_blocks(
                        q, kp, vp, table, tot, h, None, kvh, *plan,
                        value_scale=node["value_scale"],
                        chosen=None if mask is None else (mask, chosen[1]),
                        chunk=chunk)

                mask = None if chosen is None else chosen[0]
                t_walk, ref = ms(
                    lambda *a: attend(None, *a), (mask,) + args)
                row = {"phase": "chunk_kernel", "cell": cell,
                       "node": node["name"], "heads": h, "kv_heads": kvh,
                       "rows": node["chunk"], "context": total,
                       "block": plan[0],
                       "chosen": None if chosen is None else spec.topk,
                       "walk_ms": round(t_walk, 4)}
                # the products both paths must take: every row against the
                # positions under its causal limit
                seen = total - (node["chunk"] - 1) / 2.0
                flop = 2.0 * h * node["chunk"] * seen \
                    * (node["e"] // h + node["ev"] // kvh)
                row["walk_tflops"] = round(flop / t_walk / 1e9, 2)
                if tiles is None:
                    row["path"] = "walk: the rule refuses the shape"
                else:
                    t_kernel, got = ms(
                        lambda *a: attend((tiles, False), *a),
                        (mask,) + args)
                    row.update(
                        path="chunk-kernel", tile_rows=tiles.rows,
                        kernel_ms=round(t_kernel, 4),
                        kernel_tflops=round(flop / t_kernel / 1e9, 2),
                        speedup=round(t_walk / t_kernel, 3),
                        max_abs_diff=float(abs(
                            np.asarray(got, np.float32)
                            - np.asarray(ref, np.float32)).max()),
                        ref_abs_max=float(abs(
                            np.asarray(ref, np.float32)).max()))
                row["device_kind"] = dev.device_kind
                line = json.dumps(row)
                print(line, file=sys.stderr, flush=True)
                log.write(line + "\n")
                log.flush()


if __name__ == "__main__":
    main()
