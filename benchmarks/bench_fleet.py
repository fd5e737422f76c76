#!/usr/bin/env python
"""Benchmark: the disaggregated serving fleet vs round-robin monolithic.

A bursty multi-tenant shared-prefix trace (T tenants, each with its own
system prompt; requests arrive in interleaved waves) drains through an
in-process fleet of N paged ``DecodeServer`` hosts twice, on the SAME
trace and wave schedule:

* **round_robin** — the monolithic baseline: requests cycle over the
  hosts, every host prefills every tenant's prefix the first time it
  sees it (N cold prefills per tenant fleet-wide), no prefill workers;
* **cache_aware** (+ disaggregation + swap) — the ``serve.fleet``
  Router: hosts are scored by the longest ``PrefixCache`` chain match
  against each prompt (the ``/metrics.json`` chain summary), tie-broken
  by load, with deterministic first-page hash affinity for cold bursts,
  so each tenant's prefix prefills ONCE fleet-wide and every later
  request computes only its tail.  Prompts too cold to ride a match go
  to a dedicated prefill worker whose committed pages MIGRATE into the
  target host's pool (DistServe-style split; one traced extract + one
  traced install, page ids as data).

A **preemption drill** (untimed, same fleet, both configs) wedges each
fleet deterministically by page arithmetic — a low-priority long decode
plus near-capacity cold prompts cannot coexist two-to-a-host, and
nothing in a cold fleet's prefix cache is evictable — so the
higher-priority waiter preempts the long decode
(priority preemption / ``MXNET_FLEET_DECODE_BOUND``), its pages swap to
host RAM, and the router rehomes it to ANOTHER host where it restores
bit-exactly.

Deterministic halves (asserted at EVERY dims, smoke included):

* token identity — both fleet configs AND a per-host reference
  ``generate`` of every prompt (drill included — swap-out plus
  cross-host restore is invisible in the output) produce identical
  tokens;
* routing decisions — cache-aware keeps each tenant on exactly ONE
  host; round-robin scatters tenants with no affinity;
* zero retraces — every host and worker predictor traced each paged
  program at most once across warmup + drill + all drains (admission,
  migration, swap-out and readmit are all DATA);
* the preemption drill really swapped (``swap_outs >= 1``, both
  configs).

Headline (the benches' contract, one JSON line on stdout):
``fleet_tokens_per_sec_h<N>`` with ``vs_round_robin`` (= vs_baseline),
``p95_ttft_ms``, ``router_cache_hit_rate``, migrated/swapped page
counts and the ``programs`` whose dispatches left a span.  Non-smoke
asserts
``vs_round_robin >= 1.5`` — the wall-clock win of not prefilling every
tenant's prefix on every host.  Wall-clock ratios at smoke dims are
REPORTED only (shared-machine noise); the deterministic halves above
carry the tier-1 contract (tests/test_bench_contract.py).

Env knobs: BENCH_FLEET_HOSTS, BENCH_FLEET_TENANTS, BENCH_FLEET_REQS
(per tenant), BENCH_PREFIX_LEN, BENCH_FLEET_MAX_NEW, BENCH_PAGE_TOKENS,
BENCH_PREFILL_CHUNK, BENCH_EMBED, BENCH_VOCAB, BENCH_LAYERS.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE = "--smoke" in sys.argv
COLD = "--cold-start" in sys.argv

if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

# arm a tight fair-admission bound so the tight-pool bursts exercise the
# preemption path deterministically in BOTH configs (the default of 8 is
# tuned for production pools, where retirements usually win the race)
os.environ.setdefault("MXNET_FLEET_DECODE_BOUND", "3")


def emit(row):
    print(json.dumps(row), file=sys.stderr, flush=True)


def model_setup():
    """Dims, symbol, params and the predictor factory — shared by the
    fleet drive and the ``--cold-start`` program-readiness phase (same
    env knobs, same model, so the two headlines describe one fleet)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.models import attention_lm

    # outside --smoke the fleet's predictors live on the TPU or the run
    # stops here (DecodePredictor's own default context is the host CPU,
    # which a TPU VM also has)
    ctx = mx.cpu() if SMOKE else mx.tpu()
    emit({"phase": "placement", "params_on": str(ctx.jax_device),
          "platform": ctx.jax_device.platform,
          "device_kind": ctx.jax_device.device_kind,
          "device_count": len(jax.devices())})

    n_hosts = int(os.environ.get("BENCH_FLEET_HOSTS",
                                 "2" if SMOKE else "3"))
    tenants = int(os.environ.get("BENCH_FLEET_TENANTS",
                                 "4" if SMOKE else "6"))
    per_tenant = int(os.environ.get("BENCH_FLEET_REQS",
                                    "3" if SMOKE else "6"))
    prefix_len = int(os.environ.get("BENCH_PREFIX_LEN",
                                    "24" if SMOKE else "384"))
    max_new = int(os.environ.get("BENCH_FLEET_MAX_NEW",
                                 "8" if SMOKE else "4"))
    page_tokens = int(os.environ.get("BENCH_PAGE_TOKENS",
                                     "8" if SMOKE else "16"))
    chunk = int(os.environ.get("BENCH_PREFILL_CHUNK",
                               "8" if SMOKE else "16"))
    e = int(os.environ.get("BENCH_EMBED", "32" if SMOKE else "128"))
    vocab = int(os.environ.get("BENCH_VOCAB", "64"))
    layers = int(os.environ.get("BENCH_LAYERS", "2"))
    heads = 4
    slots = 2
    tail_lo, tail_hi = 1, max(2, page_tokens)
    # cache covers prompt + generation + a page of slack
    cache_len = -(-(prefix_len + tail_hi + max_new + 1)
                  // page_tokens) * page_tokens + page_tokens
    # pool: holds a host's steady working set — its share of tenant
    # prefixes plus the resident long request plus matched (tail-only)
    # admissions — but NOT a simultaneous cold full-prompt migration:
    # the burst blocks the gate there and the fair-admission bound
    # preempts the lowest-priority slot, which readmits bit-exactly
    # once the wave passes.  Round-robin hosts need ALL tenants'
    # prefixes (3x this) resident, so they additionally churn the
    # prefix cache — the capacity half of what cache-aware routing buys
    per_req_pages = cache_len // page_tokens
    prefix_pages = prefix_len // page_tokens
    pool_pages = 2 * prefix_pages + per_req_pages + 6

    sym = attention_lm.get_symbol(vocab_size=vocab, seq_len=cache_len,
                                  num_layers=layers, embed=e,
                                  heads=heads, ffn_hidden=4 * e)
    rng = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(1, cache_len), softmax_label=(1, cache_len))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = rng.normal(0, 0.02, shape).astype(np.float32)
    for name, shape in zip(sym.list_auxiliary_states(), aux_shapes):
        params["aux:" + name] = np.zeros(shape, np.float32)

    def mk_pred(pool=pool_pages):
        return DecodePredictor(sym, params, cache_len=cache_len, ctx=ctx,
                               temperature=0.0, kv_dtype="",
                               paged=True, page_tokens=page_tokens,
                               pool_pages=pool, prefill_chunk=chunk)

    return dict(n_hosts=n_hosts, tenants=tenants, per_tenant=per_tenant,
                prefix_len=prefix_len, max_new=max_new,
                page_tokens=page_tokens, chunk=chunk, vocab=vocab,
                slots=slots, tail_lo=tail_lo, tail_hi=tail_hi,
                cache_len=cache_len, pool_pages=pool_pages,
                mk_pred=mk_pred)


def main():
    import jax

    from mxnet_tpu import obs
    from mxnet_tpu.decode import DecodeServer
    from mxnet_tpu.serve.fleet import FleetHost, PrefillWorker, Router

    cfg = model_setup()
    n_hosts, tenants = cfg["n_hosts"], cfg["tenants"]
    per_tenant, prefix_len = cfg["per_tenant"], cfg["prefix_len"]
    max_new, page_tokens = cfg["max_new"], cfg["page_tokens"]
    vocab, slots = cfg["vocab"], cfg["slots"]
    tail_lo, tail_hi = cfg["tail_lo"], cfg["tail_hi"]
    cache_len, mk_pred = cfg["cache_len"], cfg["mk_pred"]
    # the preemption drill's low-priority residents: long enough to stay
    # decoding when the high-priority probe arrives, short enough not to
    # leave a serial batch-of-one tail.  (Wrapped swap/restore
    # bit-parity is pinned by tests/test_fleet.py.)
    long_cap = 9 * max_new

    # ---- the bursty multi-tenant shared-prefix trace -------------------
    trace_rng = np.random.RandomState(7)
    prefixes = [trace_rng.randint(0, vocab, size=(prefix_len,))
                for _ in range(tenants)]
    waves = []
    for w in range(per_tenant):
        wave = []
        for tnt in range(tenants):
            tail = trace_rng.randint(
                0, vocab, size=(trace_rng.randint(tail_lo, tail_hi + 1),))
            wave.append((tnt, np.concatenate([prefixes[tnt], tail]),
                         max_new, 0))
        # bursts arrive interleaved, not tenant-ordered — a fixed order
        # whose length divides the host count would hand round-robin
        # accidental tenant affinity
        wave = [wave[i] for i in trace_rng.permutation(len(wave))]
        waves.append(wave)
    flat = [req for wave in waves for req in wave]
    total_tokens = sum(cap for _, _, cap, _ in flat)
    ticks_between = 3       # the burst spacing, identical per config

    # ---- the preemption drill (untimed, same fleet) --------------------
    # Deterministic by priority logic, not pool-tuning luck: fill every
    # host's slots with LOW-priority long decodes (one drill tenant per
    # host, `slots` requests each — round-robin and sticky affinity both
    # land them one-tenant-per-host), then submit a HIGH-priority probe
    # of drill tenant 0.  Its host is slot-full with lower-priority
    # residents, so priority preemption swaps the longest one to host
    # RAM, the probe admits, and the router rehomes the victim to
    # another host where it restores bit-exactly.  Exercises swap-out,
    # cross-host readmit and the priority rule in BOTH configs.
    drill_rng = np.random.RandomState(13)
    drill_heads = [drill_rng.randint(0, vocab, size=(prefix_len,))
                   for _ in range(n_hosts)]
    drill_reqs = []
    for s in range(slots):
        for h in range(n_hosts):
            drill_reqs.append((np.concatenate(
                [drill_heads[h],
                 drill_rng.randint(0, vocab, size=(tail_hi,))]),
                long_cap, -1))
    drill_reqs.append((np.concatenate(
        [drill_heads[0],
         drill_rng.randint(0, vocab, size=(tail_hi,))]), max_new, 1))

    # ---- one fleet configuration, driven over the trace ----------------
    def build(policy):
        hosts = [FleetHost("%s%d" % (policy[:2], i),
                           DecodeServer(mk_pred(), max_prefill=cache_len,
                                        slots=slots))
                 for i in range(n_hosts)]
        workers = [PrefillWorker(mk_pred(), "%sw0" % policy[:2])] \
            if policy == "cache_aware" else []
        return Router(hosts, workers, policy=policy), hosts, workers

    def drive(router):
        rids = []
        for wave in waves:
            for tnt, prompt, cap, prio in wave:
                rids.append(router.submit(prompt, cap, priority=prio))
            for _ in range(ticks_between):
                router.tick()
        res = router.drain()
        return [res[r] for r in rids]

    def run_config(policy):
        router, hosts, workers = build(policy)
        drive(router)           # warmup: compile every program
        # --- preemption drill on the cold fleet (untimed) ---
        router.reset()
        drill_rids = [router.submit(p, cap, priority=prio)
                      for p, cap, prio in drill_reqs]
        drill_res = router.drain()
        drill_out = [drill_res[r] for r in drill_rids]
        drill_swaps = sum(h.server.swap_outs for h in hosts)
        assert drill_swaps >= 1, \
            "preemption drill produced no swap (%s)" % policy
        best, out, stats, decisions = 0.0, None, None, None
        for _ in range(2):      # best-of-2 drains, cold each time
            router.reset()
            for h in hosts:
                h.server.steps = h.server.spec_steps = 0
                h.server.tokens_out = 0
            tic = time.time()
            res = drive(router)
            dt = time.time() - tic
            assert len(res) == len(flat)
            rate = total_tokens / dt
            if rate > best:
                best, out = rate, res
            stats = router.stats()
            decisions = list(router.decisions)
        preds = [h.server._pred for h in hosts] + \
            [w._pred for w in workers]
        return {"rate": best, "out": out, "stats": stats,
                "decisions": decisions, "preds": preds,
                "drill_out": drill_out, "drill_swaps": drill_swaps,
                "steps": sum(h.server.steps for h in hosts)}

    rr = run_config("round_robin")
    ca = run_config("cache_aware")

    # ---- deterministic halves ------------------------------------------
    # token identity: cache-aware + disaggregated + preempted == plain
    # round-robin == the per-host reference generate, request by request
    for i, (a, b) in enumerate(zip(rr["out"], ca["out"])):
        assert np.array_equal(a, b), \
            "fleet configs diverged on request %d" % i
    ref = mk_pred()
    for i, (tnt, prompt, cap, prio) in enumerate(flat):
        expect = ref.generate(prompt[None].astype(np.float32),
                              prompt.size, max_new_tokens=cap, seed=0)[0]
        assert np.array_equal(ca["out"][i], expect), \
            "fleet diverged from per-host generate on request %d" % i
    # the drill's preempted/rehomed requests are token-identical too —
    # swap-out + cross-host restore is invisible in the output
    for i, (prompt, cap, prio) in enumerate(drill_reqs):
        expect = ref.generate(prompt[None].astype(np.float32),
                              prompt.size, max_new_tokens=cap, seed=0)[0]
        assert np.array_equal(ca["drill_out"][i], expect), \
            "drill diverged from per-host generate on request %d" % i
        assert np.array_equal(rr["drill_out"][i], expect), i
    # routing decisions: cache-aware pins each tenant to ONE host;
    # round-robin scatters every tenant over all hosts
    tenant_of = {}
    for (rid, host, matched, path), (tnt, _, _, _) in zip(
            ca["decisions"], flat):
        tenant_of.setdefault(tnt, set()).add(host)
    affinity = all(len(hs) == 1 for hs in tenant_of.values())
    assert affinity, tenant_of
    rr_spread = {}
    for (rid, host, matched, path), (tnt, _, _, _) in zip(
            rr["decisions"], flat):
        rr_spread.setdefault(tnt, set()).add(host)
    # (exact coverage depends on wave phase; the contract is merely that
    # round-robin has NO tenant affinity while cache-aware is perfect)
    assert any(len(hs) > 1 for hs in rr_spread.values()), rr_spread
    # zero retraces across admission, migration, swap-out and readmit
    for pred in ca["preds"] + rr["preds"]:
        tc = pred.trace_counts
        assert tc["prefill"] == 0 and tc["verify"] == 0, tc
        assert all(tc[prog] <= 1 for prog in
                   ("chunk", "decode", "fork", "commit", "extract",
                    "install")), tc
    # the preemption drill really swapped and every victim readmitted
    assert ca["stats"]["swap_outs"] >= 1, ca["stats"]
    assert rr["stats"]["swap_outs"] >= 1, rr["stats"]
    assert ca["stats"]["swap_ins"] == ca["stats"]["swap_outs"]
    # disaggregation really migrated pages
    migrated = sum(ca["stats"]["migrated_pages_by_host"].values())
    assert ca["stats"]["worker_prefills"] >= 1, ca["stats"]
    assert migrated >= 1, ca["stats"]
    hit = ca["stats"]["router_cache_hit_rate"]
    assert hit > 0, ca["stats"]

    vs_rr = ca["rate"] / max(rr["rate"], 1e-9)
    for policy, cfg in (("round_robin", rr), ("cache_aware", ca)):
        emit({"phase": policy, "tokens_per_sec": round(cfg["rate"], 1),
              "requests": len(flat), "hosts": n_hosts,
              "decode_steps": cfg["steps"],
              "stats": {k: v for k, v in cfg["stats"].items()
                        if k not in ("hosts",)}})
    if not SMOKE:
        # the acceptance line at full dims: cache-aware + disaggregated
        # routing must beat round-robin monolithic by >= 1.5x on the
        # same bursty shared-prefix trace
        assert vs_rr >= 1.5, \
            "cache-aware fleet is %.2fx round-robin (acceptance: " \
            ">= 1.5x)" % vs_rr

    p95 = ca["stats"].get("ttft_p95_s")
    print(json.dumps({
        "metric": "fleet_tokens_per_sec_h%d" % n_hosts,
        "value": round(ca["rate"], 1),
        "unit": "tok/s",
        "vs_baseline": round(vs_rr, 3),
        "vs_round_robin": round(vs_rr, 3),
        "round_robin_tokens_per_sec": round(rr["rate"], 1),
        "fleet_tokens_per_sec": round(ca["rate"], 1),
        "p95_ttft_ms": round(p95 * 1e3, 2) if p95 is not None else None,
        "p95_ttft_ms_round_robin": round(
            rr["stats"].get("ttft_p95_s", 0) * 1e3, 2),
        "router_cache_hit_rate": round(hit, 3),
        "migrated_pages": int(migrated),
        "swapped_pages": int(sum(
            ca["stats"]["swapped_pages_by_host"].values())),
        "swap_outs": ca["stats"]["swap_outs"],
        "worker_prefills": ca["stats"]["worker_prefills"],
        "hosts": n_hosts, "tenants": tenants,
        "requests": len(flat),
        "prefix_len": prefix_len,
        "tenant_affinity": bool(affinity),
        "token_identical": True,
        "zero_retraces": True,
        "programs": sorted({e["name"] for e in obs.timeline.events()
                            if e["cat"] == "program"}),
    }))


def cold_start_main():
    """``--cold-start``: program-readiness wall clock per fleet host —
    the warm AOT-cache path (deserialize every serving program,
    ``mxnet_tpu.programs.aot``) vs the trace+lower+compile path every
    host used to pay.  One build host populates the content-addressed
    cache (the once-per-fleet cost, reported untimed); each of the
    N hosts then cold-starts by loading.  Deterministic halves asserted
    at every dims: all-hit/zero-miss warm loads, token identity of an
    AOT-served drain vs the plain JIT reference, ZERO traces on the
    AOT host's predictor, and fingerprint equality between a prefill
    worker's programs and the decode hosts' (byte-identical programs,
    provably).  Non-smoke acceptance: ``cold_start_vs_jit >= 3.0``.
    """
    import shutil
    import tempfile

    from mxnet_tpu import config as _config
    from mxnet_tpu.decode import DecodeServer
    from mxnet_tpu.programs import aot as _aot

    cfg = model_setup()
    n_hosts, slots = cfg["n_hosts"], cfg["slots"]
    vocab, cache_len = cfg["vocab"], cfg["cache_len"]
    mk_pred, max_new = cfg["mk_pred"], cfg["max_new"]
    spec_k = 3
    # the server clamps its chunk width to the admission window; mirror
    # it so prepared signatures match what serve_tick drives
    chunk_w = min(cfg["chunk"] or cache_len, cache_len)

    def mk_server(pred):
        return DecodeServer(pred, max_prefill=cache_len, slots=slots,
                            max_new_tokens=max_new, spec_k=spec_k)

    trace_rng = np.random.RandomState(11)
    prefix = trace_rng.randint(0, vocab, size=(cfg["page_tokens"] * 2,))
    prompts = [np.concatenate([prefix, trace_rng.randint(
        0, vocab, size=(n,))]) for n in (3, 7, 2, 5)]

    with _config.overrides(MXNET_AOT="0"):
        # reference tokens + the per-host JIT readiness baseline (every
        # program traced+lowered+compiled, no cache anywhere)
        ref_pred = mk_pred()
        ref_srv = mk_server(ref_pred)
        for p in prompts:
            ref_srv.submit(p)
        ref = ref_srv.run()
        jit_wall = []
        for _ in range(n_hosts):
            pred = mk_pred()
            tic = time.time()
            pred.prepare_programs(slots, chunk_w=chunk_w, spec_k=spec_k,
                                  mode="compile")
            jit_wall.append(time.time() - tic)

    cache = os.environ.get("BENCH_AOT_CACHE")
    keep = bool(cache)
    cache = cache or tempfile.mkdtemp(prefix="mxnet_aot_bench_")
    try:
        with _config.overrides(MXNET_AOT="1", MXNET_PROGRAM_CACHE=cache):
            _aot.reset_stats()
            # one build host populates the cache — once per fleet
            pred0 = mk_pred()
            srv0 = mk_server(pred0)
            tic = time.time()
            srv0.serve_open()
            populate_s = time.time() - tic
            populate = srv0.aot_report
            programs_loaded = len(populate["programs"])
            # warm cold start, per host: readiness is a deserialize
            aot_wall, reports, hosts = [], [], []
            for _ in range(n_hosts):
                pred = mk_pred()
                srv = mk_server(pred)
                tic = time.time()
                srv.serve_open()
                aot_wall.append(time.time() - tic)
                reports.append(srv.aot_report)
                hosts.append((pred, srv))
            hits = sum(r["hits"] for r in reports)
            misses = sum(r["misses"] for r in reports)
            assert misses == 0 and hits == programs_loaded * n_hosts, \
                (hits, misses, programs_loaded)
            # prefill workers provably run byte-identical programs to
            # their target hosts: every fingerprint matches
            wfp = mk_pred().program_fingerprints(slots, chunk_w=chunk_w,
                                                 spec_k=spec_k)
            hfp = hosts[0][0].program_fingerprints(slots, chunk_w=chunk_w,
                                                   spec_k=spec_k)
            worker_identical = wfp == hfp
            assert worker_identical, (wfp, hfp)
            # AOT-served drain: token-identical to the JIT reference,
            # zero traces on the serving predictor, all-cache sources
            pred1, srv1 = hosts[0]
            for p in prompts:
                srv1.submit(p)
            out = srv1.run()
            assert set(out) == set(ref)
            token_identical = all(np.array_equal(ref[k], out[k])
                                  for k in ref)
            assert token_identical
            zero_retraces = all(v == 0
                                for v in pred1.trace_counts.values())
            assert zero_retraces, pred1.trace_counts
            sources = {k: v["source"]
                       for k, v in srv1.aot_report["programs"].items()}
            assert all(s == "cache" for s in sources.values()), sources
    finally:
        if not keep:
            shutil.rmtree(cache, ignore_errors=True)

    cold_start_s = sum(aot_wall) / n_hosts
    jit_s = sum(jit_wall) / n_hosts
    vs_jit = jit_s / max(cold_start_s, 1e-9)
    emit({"phase": "cold_start", "hosts": n_hosts,
          "programs": programs_loaded, "populate_s": round(populate_s, 3),
          "jit_wall_s": [round(t, 3) for t in jit_wall],
          "aot_wall_s": [round(t, 3) for t in aot_wall],
          "sources": sources})
    if not SMOKE:
        # the acceptance line at full dims: a warm-cache host must be
        # ready >= 3x faster than the trace+compile path
        assert vs_jit >= 3.0, \
            "AOT cold start is %.2fx JIT (acceptance: >= 3.0x)" % vs_jit
    print(json.dumps({
        "metric": "fleet_cold_start_s_h%d" % n_hosts,
        "value": round(cold_start_s, 4),
        "unit": "s",
        "vs_baseline": round(vs_jit, 3),
        "cold_start_s": round(cold_start_s, 4),
        "cold_start_jit_s": round(jit_s, 4),
        "cold_start_vs_jit": round(vs_jit, 3),
        "populate_s": round(populate_s, 4),
        "programs_loaded": programs_loaded,
        "aot_hits": hits, "aot_misses": misses,
        "aot_fallbacks": _aot.AOT_STATS["fallbacks"],
        "worker_programs_identical": bool(worker_identical),
        "token_identical": bool(token_identical),
        "zero_retraces": bool(zero_retraces),
        "hosts": n_hosts,
    }))


if __name__ == "__main__":
    if not SMOKE:
        from mxnet_tpu.cache_dirs import arm_compile_cache

        arm_compile_cache()
    cold_start_main() if COLD else main()
