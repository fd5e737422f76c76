"""What a prefill chunk's output head costs in a serving cell, and what the
conditional around it costs where it does not run, at the cell's own size on
the chip.

For each cell, on seeded weights of the cell's serving type and pools of the
cell's slots, one chunk of the cell's width at a position in the middle of a
prompt, through four programs over the same operands:

``skipped``
    the chunk program as the loop runs it on a chunk that does not end its
    prompt (the conditional's false branch);
``run``
    the same program on a chunk that ends its prompt (one row through the
    head);
``one_row``
    one row through the head in every chunk, no conditional: what ``run``
    costs without one;
``tiny_head``
    as ``one_row`` with the head's matrix cut to its first 128 rows: the
    stream the head reads is computed and the matrix is not read, so what
    ``skipped`` costs without a conditional;
``no_head``
    the walk alone, nothing of it read but the pools: the compiler drops
    with the head whatever only the head read (the last layer after its
    cache writes);
``every_row``
    every row of the chunk through the head and then one row taken: the
    chunk program before PR 47.

Each is dispatched ``--reps`` times back to back over donated pools and
fenced once, three times over: the median of the three is the program's time
a chunk with the device kept busy (``*_ms``).  Then five calls of each are
traced: ``*_device_ms`` is the median of the program's ``XLA Modules``
events, ``*_cond_ms`` of its ``conditional`` events.  One process a cell, the
chip's; one JSON line a cell; nothing of the benchmark calls this.

    chiprun -- sh -c 'for c in falconh1_serve_chat mimo_serve_longshort; \\
        do python3 benchmarks/probe_chunk_head.py --cell $c; done'
"""
import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from chipbench import harness, manifest, trace
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf
from mxnet_tpu.decode import DecodePredictor
from mxnet_tpu.ops.moe import collecting


def variants(pred, width):
    """``{name: (program over (env, caches, *operands), the flag it is
    given)}``; every program returns the pools first."""
    ones = jnp.ones((1,), jnp.int32)
    real = lambda nvalid: lambda: jnp.arange(width)[None, :] \
        < jnp.asarray(nvalid, jnp.int32).reshape(-1, 1)

    def walk(env, caches, table1, toks, pos0, nvalid, rows):
        with collecting(real=real(nvalid)):
            return pred._run(env, toks, caches, pos0, tables=table1,
                             active=ones, valid=nvalid, head_rows=rows)

    def no_head(env, caches, table1, toks, pos0, nvalid, flag, key):
        return walk(env, caches, table1, toks, pos0, nvalid,
                    jnp.zeros((1,), jnp.int32))[1],

    def one_row(env, caches, table1, toks, pos0, nvalid, flag, key):
        row = jnp.clip(jnp.asarray(nvalid, jnp.int32) - 1, 0, width - 1)
        head, caches = walk(env, caches, table1, toks, pos0, nvalid, row)
        probs = head()[:, 0]
        return caches, probs, pred._sample(key, probs)

    def tiny_head(env, *rest):
        return one_row(dict(env, head_weight=env["head_weight"][:128]),
                       *rest)

    def every_row(env, caches, table1, toks, pos0, nvalid, flag, key):
        probs3, caches = walk(env, caches, table1, toks, pos0, nvalid, None)
        last = jnp.clip(jnp.asarray(nvalid, jnp.int32) - 1, 0, width - 1)
        probs = jnp.take_along_axis(probs3, last[:, None, None], axis=1)[:, 0]
        return caches, probs, pred._sample(key, probs)

    jit = lambda fn: jax.jit(fn, donate_argnums=(1,))
    if pred.self_drafting:
        # the block runs between the two heads: only the loop's own program
        return {"skipped": (pred._chunk_mtp_fn, 7),
                "run": (pred._chunk_mtp_fn, -1)}
    return {"skipped": (pred._chunk_fn, 0), "run": (pred._chunk_fn, 1),
            "one_row": (jit(one_row), 1), "tiny_head": (jit(tiny_head), 0),
            "no_head": (jit(no_head), 0), "every_row": (jit(every_row), 1)}


def probe(cell, seed, reps):
    loaded = manifest.load_cell(cell)
    cfg, traffic = loaded["config"], loaded["traffic"]
    ctx = mx.tpu()
    sym = harness.build_symbol(cfg)
    params = serve_ticks_by_leaf.make_params(
        serve_ticks.weight_shapes(sym, cfg), cfg, seed, cfg["serve_dtype"])
    pred = DecodePredictor(
        sym, {n: mx.nd.NDArray(v, ctx) for n, v in params.items()},
        cache_len=int(traffic["cache_len"]), ctx=ctx, temperature=0.0,
        paged=True, page_tokens=int(traffic["page_tokens"]),
        kv_dtype=traffic["kv_dtype"],
        prefill_chunk=int(traffic["prefill_chunk"]))
    del params
    width = int(traffic["prefill_chunk"])
    state = pred.paged_batch_state(int(traffic["slots"]),
                                   drafting=pred.self_drafting)
    caches = state.caches
    # a prompt of three chunks; the one measured is the second
    prompt = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], size=3 * width)
    mgr = pred._manager
    gate = mgr.gate(prompt, prompt.size, pred.cache_len, 1,
                    budget_wrap_forks=False)
    mgr.map_slot(0, gate[1], gate[2])
    if mgr.ensure(0, 0, prompt.size):
        raise RuntimeError("a fresh slot's pages asked for a fork")
    operands = pred._chunk_operands(0, prompt[width:2 * width], width, width)
    key = jax.random.PRNGKey(0)
    out = {"cell": cell, "width": width, "reps": reps,
           "device": jax.devices()[0].device_kind}
    logdir = tempfile.mkdtemp()
    for name, (program, flag) in variants(pred, width).items():
        flag = np.asarray([flag], np.int32)
        call = lambda c: program(pred._env, c, *operands, flag, key)
        t0 = time.perf_counter()
        caches = jax.block_until_ready(call(caches))[0]
        out[name + "_first_call_s"] = round(time.perf_counter() - t0, 2)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                caches = call(caches)[0]
            jax.block_until_ready(caches)
            times.append((time.perf_counter() - t0) / reps * 1e3)
        out[name + "_ms"] = round(statistics.median(times), 4)
        out[name + "_ms_all"] = [round(t, 4) for t in times]
        with jax.profiler.trace(os.path.join(logdir, name)):
            for _ in range(5):
                caches = call(caches)[0]
            jax.block_until_ready(caches)
        lines = next(iter(trace.load(trace.find_xplane(
            os.path.join(logdir, name)))["devices"].values()), {})
        for what, line, stem in (("device", trace.MODULES_LINE, "jit_"),
                                 ("cond", trace.OPS_LINE, "cond")):
            ms = [d / 1e6 for n, _, d in lines.get(line, ())
                  if n.startswith(stem)]
            if ms:
                out["%s_%s_ms" % (name, what)] = round(
                    statistics.median(ms), 4)
    if "tiny_head_device_ms" in out:
        # what the conditional costs where it skips, and where it runs
        out["conditional_skipped_ms"] = round(
            out["skipped_device_ms"] - out["tiny_head_device_ms"], 4)
        out["conditional_run_ms"] = round(
            out["run_device_ms"] - out["one_row_device_ms"], 4)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="falconh1_serve_chat")
    ap.add_argument("--seed", type=int, default=4700000001)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    os.makedirs("chiprun_out", exist_ok=True)
    line = json.dumps(probe(args.cell, args.seed, args.reps))
    print(line, flush=True)
    with open("chiprun_out/probe_chunk_head.jsonl", "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
