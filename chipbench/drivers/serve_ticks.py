"""``serve_ticks``: a backlog of requests through ``DecodeServer``, driven
one ``serve_tick()`` at a time and timed between two fences.

The whole backlog is queued before the first tick.  Set-up ends when every
slot holds a request that has produced a token; from then each tick's end is
stamped into a preallocated list, and nothing else of the benchmark's own
runs until the window closes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.decode import DecodePredictor, DecodeServer

from .. import correct, harness, timing, traffic as traffic_mod, weights

MAX_TICKS = 400000
GAP_EDGES_MS = [0, 5, 10, 15, 20, 25, 30, 40, 50, 60, 80, 100, 150, 200, 400]


def build_server(sym, traffic, params, ctx):
    pred = DecodePredictor(
        sym, params, cache_len=int(traffic["cache_len"]), ctx=ctx,
        temperature=0.0, paged=True,
        page_tokens=int(traffic["page_tokens"]),
        kv_dtype=traffic["kv_dtype"],
        prefill_chunk=int(traffic["prefill_chunk"]))
    server = DecodeServer(pred, max_prefill=int(traffic["max_prefill"]),
                          slots=int(traffic["slots"]), spec_k=0)
    return pred, server


def weight_shapes(sym, cfg):
    t = int(cfg["max_position_embeddings"])
    arg_shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    return {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}


def reference_rows(cfg, traffic):
    """``fwd(params, seq)``: the plain reference's logits, from one full
    forward pass over ``check_prompt + check_decode`` tokens, at the
    positions the comparison reads: the prompt's last and each decoded
    one."""
    ref = correct.reference_of(cfg)
    plen = int(traffic["check_prompt"])
    return jax.jit(lambda p, x: ref.forward(p, cfg, x)[0, plen - 1:])


def control_case(cfg, traffic, seed):
    """For ``chipbench.control``: the seeded weights, ``forward(params)``
    as this driver's comparison calls the reference (over seeded tokens:
    the run's own decoded ones are the system's), and the type the cell
    computes in."""
    params = weights.make_params(
        weight_shapes(harness.build_symbol(cfg), cfg), cfg, seed,
        cfg["serve_dtype"])
    n = int(traffic["check_prompt"]) + int(traffic["check_decode"])
    seq = traffic_mod.rng_of(seed, 4).integers(0, cfg["vocab_size"],
                                               size=(1, n))
    fwd = reference_rows(cfg, traffic)
    return {"params": params, "forward": lambda p: fwd(p, seq),
            "dtype": cfg["serve_dtype"]}


def check_against_reference(pred, cfg, traffic, params, seed, atol):
    """Chunked prefill of one prompt, then ``check_decode`` decoded
    positions, through the paged pool and the very programs that serve —
    against the reference's one full forward pass over the same tokens."""
    slots, steps = int(traffic["slots"]), int(traffic["check_decode"])
    plen = int(traffic["check_prompt"])
    rng = traffic_mod.rng_of(seed, 4)
    prompt = rng.integers(0, cfg["vocab_size"], size=plen)
    # one real row; the other rows of the serving batch get one token each
    toks = np.zeros((slots, plen), np.float32)
    toks[0] = prompt
    toks[1:, 0] = rng.integers(0, cfg["vocab_size"], size=slots - 1)
    lens = np.ones(slots, np.int64)
    lens[0] = plen
    state, probs = pred.prefill(toks, lens)
    got = [probs[0]]
    fed = [int(np.asarray(state.tok)[0, 0])]
    for _ in range(steps):
        state, probs = pred.step(state)
        got.append(probs[0])
        fed.append(int(np.asarray(state.tok)[0, 0]))
    del state
    seq = np.concatenate([prompt, np.asarray(fed[:-1])])[None, :]
    return [correct.compare_logp(
        jnp.stack(got), reference_rows(cfg, traffic)(params, seq), atol)]


def delivered(server):
    """Output tokens handed to requests so far: those of retired requests
    and those of the requests in the slots."""
    return server.tokens_out + sum(len(r["toks"])
                                   for r in server._ps["active"].values())


def run(job):
    cfg, traffic, phases = job["config"], job["traffic"], job["phases"]
    seed, tracer, counters = job["seed"], job["tracer"], job["counters"]
    seconds = job["seconds"]
    ctx = job["contexts"][0]
    # read before anything is built: a configuration that states no limit
    # for this pool's type fails here and not after the window
    atol = correct.limit(cfg, "serve_ticks",
                         "logp_atol." + traffic["kv_dtype"])
    sym = harness.build_symbol(cfg)
    shapes = weight_shapes(sym, cfg)
    phases.mark("import_and_bind")
    params = weights.make_params(shapes, cfg, seed, cfg["serve_dtype"])
    jax.block_until_ready(params)
    pred, server = build_server(
        sym, traffic, {n: mx.nd.NDArray(v, ctx) for n, v in params.items()},
        ctx)
    phases.mark("weights_and_data")

    queue = traffic_mod.backlog(traffic, cfg["vocab_size"], seed)
    rids = [server.submit(p, max_new_tokens=o) for p, o in queue]
    caps = {r: o for r, (_, o) in zip(rids, queue)}
    slots = int(traffic["slots"])
    server.serve_reset()
    ps = server.serve_open()
    first = True
    while len(ps["active"]) < slots:
        server.serve_tick()
        if first:
            phases.mark("compile_or_cache")
            first = False
    for _ in range(int(traffic.get("warmup_ticks", 8))):
        server.serve_tick()
    jax.block_until_ready(ps["state"])
    phases.mark("fill")

    stamps = [0.0] * MAX_TICKS
    active_before = [0] * MAX_TICKS
    live_tokens = [0] * MAX_TICKS
    active, lens = ps["active"], ps["slot_lens"]
    tick, tracing = server.serve_tick, tracer.on
    job["memory"].sample()
    harness.quiesce()
    gc0 = harness.gc_counts()
    # a traced window closes after `trace_ticks` ticks or `trace_seconds`,
    # whichever comes first: the trace's size, and so the time it takes to
    # stop, load and reduce, then does not grow as the tick gets shorter
    max_ticks = min(MAX_TICKS, int(traffic.get("trace_ticks", MAX_TICKS))) \
        if tracing else MAX_TICKS
    tracer.start()
    phases.mark("trace_start")
    tokens0 = delivered(server)
    counters.window_open = True
    n = 0
    t0 = now = time.perf_counter()
    while now - t0 < seconds and n < max_ticks:
        active_before[n] = len(active)
        live_tokens[n] = int(lens.sum())
        if tracing:
            with tracer.span("serve_tick"):
                tick()
        else:
            tick()
        now = time.perf_counter()
        stamps[n] = now
        n += 1
    jax.block_until_ready(ps["state"])
    t1 = time.perf_counter()
    phases.mark("window")
    counters.window_open = False
    tokens = delivered(server) - tokens0
    tracer.stop(phases)
    gc1 = harness.gc_counts()
    job["memory"].sample()
    queue_left = len(server._queue)
    n_active = len(active)
    results = server.serve_results(clear=False)
    # after the window: the serving pools go, and the same programs prefill
    # and decode one prompt against the reference (no set-up time spent)
    del active, lens, tick
    server.serve_reset()
    ps = None
    phases.mark("after_window")
    checks = check_against_reference(pred, cfg, traffic, params, seed, atol)
    phases.mark("check")

    stamps, active_before = stamps[:n], active_before[:n]
    live_tokens = live_tokens[:n]
    wrong_len = [r for r, toks in results.items() if len(toks) != caps[r]]
    complete = {"ok": not wrong_len and queue_left > 0,
                "requests_completed": len(results),
                "wrong_length": len(wrong_len), "queue_left": queue_left}
    rate = timing.window_rate(1, tokens, t0, t1)
    values, wts = timing.gaps(stamps, t0, active_before)
    p95 = 1e3 * timing.weighted_percentile(values, wts, 0.95)
    p50 = 1e3 * timing.weighted_percentile(values, wts, 0.50)
    hist = timing.histogram([1e3 * v for v in values], wts, GAP_EDGES_MS)
    print("gaps: %d samples over %d ticks; p50 %.3f ms p95 %.3f ms; "
          "histogram (ms edges %s): %s"
          % (sum(wts), n, p50, p95, GAP_EDGES_MS, [int(h) for h in hist]),
          flush=True)
    worst, at = timing.longest_step(stamps, t0)
    # tokens delivered per tick = slots active when its decode step ran;
    # for the per-segment rates the tick's own count is close enough
    per_tick = [float(a) for a in active_before]
    return {
        "end_to_end": {"serve_out_tokens_per_s": rate,
                       "serve_gap_p95_ms": p95},
        "setup_s": phases.since_start(t0),
        "attempted": len(results) + n_active, "failed": len(wrong_len),
        "checks": checks + [complete],
        "trace": tracer.parsed, "trace_bytes": tracer.trace_bytes,
        "facts": {"rate": rate, "ticks": n, "window_s": t1 - t0,
                  "slots": slots, "gap_p95_ms": p95, "gap_p50_ms": p50,
                  "mean_active": float(np.mean(active_before)),
                  "mean_live_tokens": float(np.mean(live_tokens))},
        "side": {
            "window_s": t1 - t0, "ticks": n, "tokens": tokens,
            "segment_rates": timing.segment_rates(stamps, t0, per_tick),
            "prefix_rates": timing.prefix_rates(stamps, t0, per_tick,
                                                timing.PREFIX_MARKS_S),
            "ticks_with_prefill": int(sum(
                1 for v in values if v > 1.04 * float(np.median(values)))),
            "longest_tick_s": worst, "longest_tick_index": at,
            "median_tick_s": float(np.median(values)),
            "gap_samples": int(sum(wts)), "gap_p50_ms": p50,
            "gap_p95_ms": p95, "gap_histogram_ms": [GAP_EDGES_MS, hist],
            "requests_completed": len(results), "queue_left": queue_left,
            "gc_collections_in_window": [b - a for a, b in zip(gc0, gc1)],
        },
    }
