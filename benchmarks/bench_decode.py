#!/usr/bin/env python
"""Benchmark: KV-cached autoregressive decoding vs recompute-the-prefix.

The serving-side headline the training benches never covered: an
attention-LM generating tokens through ``mxnet_tpu.decode`` —

* **prefill** — the (B, T) prompt pass that fills the ring-buffer KV
  caches, reported as ``prefill_tokens_per_sec``;
* **decode**  — the donated one-token-per-call step program, reported as
  ``decode_tokens_per_sec``;
* **naive**   — the recompute-the-prefix baseline: one full forward at the
  bound (B, T) shape per generated token (what ``Predictor.forward``
  generation costs), the O(T^2) plan the KV cache exists to beat;
* **serve**   — the continuous-batching loop (``DecodeServer``) on a
  MIXED-LENGTH request trace (prompt lengths spread over [T/8, T/4],
  per-request caps varied): end-to-end served tokens/s including
  prefills.  Run twice on the SAME trace:

  - ``serve`` — the PR-4 dense-cache configuration (f32 ring buffers, one
    token per step): the baseline;
  - ``serve_spec_quant`` — speculative decoding (``MXNET_SPEC_K`` drafts
    through the model-free n-gram proposer, one batched verify pass)
    over quantized KV caches (``MXNET_KV_DTYPE``): both factors of the
    bandwidth-bound decode cost attacked at once.  The acceptance line:
    >= 2x the dense serve rate at T=2048, accept-rate reported.

* **serve_paged** — the SHARED-SYSTEM-PROMPT mixed-length trace (N
  requests x one common 256-token prefix + random tails), drained twice:
  the PR-6 dense-ring spec x quant config (rings reserve the full T per
  slot), and the paged config (``MXNET_KV_PAGED`` machinery: shared page
  pools sized to the live-token working set, copy-on-write prefix
  sharing so the common prefix prefills once, chunked prefill
  interleaved with decode).  Paged serving is asserted token-identical
  to the dense-ring drain (greedy), prefix_cache_hit_rate > 0,
  trace_counts prove zero retraces across admissions/forks/retirements,
  and the capacity headline ``serve_paged_tokens_per_sec_per_gb`` must
  reach >= 2x the dense-ring tokens/s/GB at full dims (T=2048) — memory
  is the serving bottleneck PagedAttention removes.

* **pallas_decode** — static attention-traffic pricing of the paged
  decode step, the walk over live blocks vs the decode row's Pallas kernel
  (ops/pallas_decode.py, taken where
  ``ops.attention.decode_kernel_selected`` admits the shapes): attention
  bytes = one pool pass + materialized gather intermediates
  (``analysis.cost.program_cost``'s gather_bytes term).  Published as
  ``decode_attn_bytes_per_token`` (+ per-path variants and the ratio) and
  ``pallas_decode_enabled``; non-smoke asserts the kernel's path prices
  <= 0.5x the walk's bytes at T=2048 — the priced traffic win.  (At
  the --smoke dims the rule keeps the whole view: both prices are one.)

* **gqa** — grouped-query attention (``num_kv_heads = heads/G``,
  docs/inference.md): for each group factor G in the grid the bench
  builds a grouped LM, re-drains the SAME shared-prefix paged trace and
  statically prices the decode step's attention traffic.  Every K/V
  plane — page pools, int8 scale planes, ring caches — is physically
  G x narrower, so the pool shrink is asserted as EXACT arithmetic
  (``gqa_pool_bytes * G == mha_pool_bytes``), the G=1 row IS the MHA
  paged serve (same symbol object, same predictor config — the grouped
  path is bit-exact when there is nothing to group, pinned across
  dense/ring/flash/decode in tests/test_gqa.py), and retrace counts
  stay at the paged phase's zero-retrace bar.  Published: ``gqa_cache_bytes_per_slot``,
  ``gqa_decode_attn_bytes_per_token``, ``vs_mha_tokens_per_sec_per_gb``
  and the int8 x G compounding ratio against the f32 MHA pool;
  non-smoke asserts at the top grid G (>= 4 at T=2048): pool
  <= 0.3x MHA, priced attention bytes <= 0.35x MHA, int8-grouped pool
  <= 0.1x the f32 MHA pool.

The bench also ASSERTS the O(1)-in-prefix property statically: dot FLOPs
(``parallel.hlo_stats.dot_flops``) of the lowered decode-step program must
not grow with the prefix, while the full-forward program's roughly double
from T/2 to T — a failed assertion exits nonzero, so CI catches a decode
path that silently regressed to re-running the prefix.  Cache bytes come
from the same static analyzer the mxlint cache-bytes pass uses
(``DecodePredictor.cache_bytes``), feeding the capacity headline
``tokens_per_sec_per_gb`` — quantization's win shows up in the JSON
contract even where compute, not bandwidth, bounds the harness.

The benches' contract: ONE json line on stdout —
``{"metric": "decode_tokens_per_sec_t<T>", "value", "unit",
"vs_baseline", ...}`` — where ``vs_baseline`` is the decode rate over the
naive recompute rate on the same chip (the acceptance headline: >= 5x at
T=512).  Per-phase detail goes to stderr, one json per line.

Env knobs: BENCH_T, BENCH_BATCH, BENCH_EMBED, BENCH_HEADS, BENCH_VOCAB,
BENCH_LAYERS, BENCH_DECODE_STEPS, BENCH_NAIVE_STEPS, BENCH_DTYPE,
BENCH_SPEC_K (draft width, default 8), BENCH_KV_DTYPE (default int8),
BENCH_SERVE_REQS, BENCH_MAX_NEW, BENCH_SHARED_REQS, BENCH_PAGE_TOKENS,
BENCH_PREFILL_CHUNK, BENCH_GQA_GROUPS (comma list of group factors G;
default "1,4,8" filtered to divisors of BENCH_HEADS).
``--smoke``: the tier-1 CI entry — tiny dims on the forced-CPU platform
(tests/test_bench_contract.py invokes it).

``--live-blocks``: kernel-alone rows of the paged einsum path on the chip
(no model: one attention node's pools, table and query) — the whole view
(``paged_gather`` + ``_sdpa_cache``) against the walk over live blocks
(``_attend_live_blocks``) at the two serving cells' shapes, live shares of
1/3, 1/2 and 1, a decode row and a prefill chunk; one json row a case on
stderr.  ``--sweep`` adds the block widths and step sizes the constants in
``ops/attention.py`` were chosen from.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE = "--smoke" in sys.argv

if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def main():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.decode import DecodePredictor, DecodeServer
    from mxnet_tpu.models import attention_lm
    from mxnet_tpu.parallel.hlo_stats import dot_flops

    # outside --smoke every number below is a chip number: the predictors
    # live on the TPU or the run stops here (DecodePredictor's own default
    # context is the host CPU, which a TPU VM also has)
    ctx = mx.cpu() if SMOKE else mx.tpu()
    print(json.dumps({"phase": "placement",
                      "params_on": str(ctx.jax_device),
                      "platform": ctx.jax_device.platform,
                      "device_kind": ctx.jax_device.device_kind,
                      "device_count": len(jax.devices())}),
          file=sys.stderr, flush=True)

    t = int(os.environ.get("BENCH_T", "256" if SMOKE else "2048"))
    b = int(os.environ.get("BENCH_BATCH", "2" if SMOKE else "4"))
    e = int(os.environ.get("BENCH_EMBED", "32" if SMOKE else "1024"))
    heads = int(os.environ.get("BENCH_HEADS", "4"))
    # the smoke vocab stays small: a small-vocab random-weight proxy's
    # greedy output is repetitive, like real LM decoding (which is what
    # makes prompt-lookup speculation pay in production serving)
    vocab = int(os.environ.get("BENCH_VOCAB", "64" if SMOKE else "8192"))
    layers = int(os.environ.get("BENCH_LAYERS", "2"))
    n_decode = int(os.environ.get("BENCH_DECODE_STEPS",
                                  "16" if SMOKE else "64"))
    n_naive = int(os.environ.get("BENCH_NAIVE_STEPS", "4"))
    spec_k = int(os.environ.get("BENCH_SPEC_K", "8"))
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "int8")

    sym = attention_lm.get_symbol(vocab_size=vocab, seq_len=t,
                                  num_layers=layers, embed=e, heads=heads,
                                  ffn_hidden=4 * e)

    # random weights: generation quality is irrelevant to throughput
    rng = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(b, t), softmax_label=(b, t))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = (rng.normal(0, 0.02, shape)).astype(np.float32)
    for name, shape in zip(sym.list_auxiliary_states(), aux_shapes):
        params["aux:" + name] = np.zeros(shape, np.float32)

    # kv_dtype pinned OFF: the dense predictor is the PR-4 baseline and
    # must not silently inherit an ambient MXNET_KV_DTYPE
    pred = DecodePredictor(sym, params, cache_len=t, ctx=ctx,
                           temperature=0.0, kv_dtype="")

    prompt_len = t // 2
    prompts = rng.randint(0, vocab, size=(b, t)).astype(np.float32)
    prompts[:, prompt_len:] = 0.0

    key = jax.random.PRNGKey(0)

    def emit(row):
        print(json.dumps(row), file=sys.stderr, flush=True)

    # ---- static FLOP accounting: the O(1)-in-prefix assertion ----------
    state, _ = pred.prefill(prompts, prompt_len, key)
    f_decode = dot_flops(pred.decode_step_text(state))
    f_full = dot_flops(pred.prefill_text(b, t))
    f_half = dot_flops(pred.prefill_text(b, t // 2))
    # the decode-step program has no T-shaped input at all: its cost per
    # token is a constant, while the recompute program's grows with the
    # prefix (~2x from T/2 to T).  Both facts asserted from lowered HLO.
    grow = f_full / max(f_half, 1)
    per_tok_ratio = f_full / max(f_decode, 1)
    emit({"phase": "flops", "decode_step_dot_flops": f_decode,
          "full_forward_dot_flops_t%d" % t: f_full,
          "full_forward_dot_flops_t%d" % (t // 2): f_half,
          "full_growth": round(grow, 3),
          "full_over_decode": round(per_tok_ratio, 1)})
    assert grow >= 1.5, \
        "full-forward FLOPs did not grow with prefix length (%.2f)" % grow
    assert per_tok_ratio >= 4, \
        "decode step FLOPs are not O(1) in the prefix (full/decode=%.1f)" \
        % per_tok_ratio

    # ---- prefill throughput --------------------------------------------
    pred.prefill(prompts, prompt_len, key)  # compile
    n_prefill = 2 if SMOKE else 5
    tic = time.time()
    for _ in range(n_prefill):
        state, _ = pred.prefill(prompts, prompt_len, key)
    jax.block_until_ready(state.caches)
    prefill_tok_s = b * prompt_len * n_prefill / (time.time() - tic)
    emit({"phase": "prefill", "tokens_per_sec": round(prefill_tok_s, 1),
          "batch": b, "prompt_len": prompt_len})

    # ---- decode throughput ---------------------------------------------
    state, _ = pred.step(state, key)  # compile
    tic = time.time()
    for _ in range(n_decode):
        state, _ = pred.step(state, key)
        np.asarray(state.tok)  # the serving loop's per-step EOS read
    decode_tok_s = b * n_decode / (time.time() - tic)
    emit({"phase": "decode", "tokens_per_sec": round(decode_tok_s, 1),
          "steps": n_decode, "cache_len": t})

    # ---- naive recompute baseline --------------------------------------
    # one full (B, T) forward per generated token, fixed shape (jitted
    # once): exactly what generation through Predictor.forward costs
    naive = prompts.copy()
    cur = prompt_len
    pred.prefill(naive, cur, key)  # compiled above; warm anyway
    tic = time.time()
    for _ in range(n_naive):
        st, _ = pred.prefill(naive, cur, key)
        tok = np.asarray(st.tok)
        naive[:, cur] = tok[:, 0]
        cur += 1
    naive_tok_s = b * n_naive / (time.time() - tic)
    emit({"phase": "naive", "tokens_per_sec": round(naive_tok_s, 1),
          "steps": n_naive, "T": t})

    # ---- mixed-length serving trace: dense baseline vs spec x quant ----
    # prompt lengths spread over [T/8, T/4] and per-request caps varied,
    # so the schedule exercises padded prefills, staggered retirement and
    # slot reuse — the traffic shape the PR-4 fixed-length serve never saw
    slots = 2 if SMOKE else 4
    max_new = int(os.environ.get("BENCH_MAX_NEW", "96" if SMOKE else "256"))
    n_reqs = int(os.environ.get("BENCH_SERVE_REQS", str(3 * slots)))
    trace_rng = np.random.RandomState(7)
    lo, hi = max(1, t // 8), max(2, t // 4)
    trace = [(trace_rng.randint(0, vocab,
                                size=(trace_rng.randint(lo, hi + 1),)),
              max_new if i % 2 == 0 else max(2, max_new // 2))
             for i in range(n_reqs)]
    total_cap = sum(cap for _, cap in trace)

    def run_serve(p, workload=None, window=None, **kw):
        # admissions prefill at the trace's prompt ceiling, not the full
        # cache width: padding every admission to T would charge a whole
        # T-wide forward per request (both configs alike) and drown the
        # decode-side comparison the serve exists to measure
        wtrace = trace if workload is None else workload
        wcap = sum(cap for _, cap in wtrace)
        server = DecodeServer(p, max_prefill=window or hi, slots=slots,
                              **kw)
        # warmup drain: compile the (1, T) prefill (or the paged chunk /
        # fork / commit programs), step/verify and the slot-splice
        # programs OUTSIDE the timed region
        for _ in range(2):
            server.submit(wtrace[0][0], max_new_tokens=2)
        server.run()
        # best-of-N drains of the SAME trace: the serving loop's wall
        # clock rides the host scheduler, so the fastest drain is the
        # machine-noise-free estimate (both configs measured alike)
        best, results = 0.0, None
        for _ in range(3 if SMOKE else 2):
            server.steps = server.spec_steps = 0
            server.tokens_out = server.proposed = server.accepted = 0
            ids = [server.submit(prompt, max_new_tokens=cap)
                   for prompt, cap in wtrace]
            tic = time.time()
            drained = server.run()
            dt = time.time() - tic
            assert len(drained) == len(wtrace) \
                and server.tokens_out == wcap
            best = max(best, server.tokens_out / dt)
            results = [drained[rid] for rid in ids]
        return server, best, results

    # PR-4 configuration: dense f32 caches, one token per step
    # (spec_k pinned 0 so an ambient MXNET_SPEC_K cannot turn the
    # baseline speculative and measure spec-vs-spec)
    server_d, serve_tok_s, _ = run_serve(pred, spec_k=0)
    emit({"phase": "serve", "tokens_per_sec": round(serve_tok_s, 1),
          "requests": n_reqs, "slots": slots,
          "decode_steps": server_d.steps})

    # speculation x quantization on the SAME trace
    qpred = DecodePredictor(sym, params, cache_len=t, ctx=ctx,
                            temperature=0.0, kv_dtype=kv_dtype)
    server_q, serve_sq_tok_s, _ = run_serve(qpred, spec_k=spec_k)
    # static cache accounting (the mxlint cache-bytes pass's numbers),
    # per serving slot: the quantization win as capacity, not just speed
    one = np.zeros((1, hi), np.float32)
    bytes_f32 = pred.cache_bytes(pred.prefill(one, 1)[0])
    bytes_q = qpred.cache_bytes(qpred.prefill(one, 1)[0])
    serve_gb = bytes_q * slots / 1e9
    tok_s_per_gb = serve_sq_tok_s / serve_gb
    emit({"phase": "serve_spec_quant",
          "tokens_per_sec": round(serve_sq_tok_s, 1),
          "requests": n_reqs, "slots": slots, "spec_k": spec_k,
          "kv_dtype": kv_dtype,
          "decode_steps": server_q.steps,
          "spec_steps": server_q.spec_steps,
          "accept_rate": round(server_q.accept_rate, 3),
          "cache_bytes_per_slot": bytes_q,
          "tokens_per_sec_per_gb": round(tok_s_per_gb, 1)})
    vs_pr4 = serve_sq_tok_s / serve_tok_s
    # the speculation win that machine noise cannot touch: device steps
    # per served token (the count ratio IS tokens-per-verify-pass)
    steps_ratio = server_d.steps / max(server_q.steps, 1)
    if not SMOKE:
        # the acceptance line at full dims (T=2048): speculation x
        # quantization combined must at least double the PR-4 serve rate
        assert vs_pr4 >= 2.0, \
            "spec x quant serve is %.2fx the PR-4 dense baseline " \
            "(acceptance: >= 2x at T=%d)" % (vs_pr4, t)

    # ---- shared-system-prompt trace: PR-6 dense rings vs paged+prefix --
    # N requests share one common prefix (the million-user system-prompt
    # shape) with random mixed-length tails; drained by the PR-6 config
    # (dense rings reserving the full T per slot) and by the paged config
    # (pool sized to the live-token working set, prefix shared, chunked
    # prefill) — same spec x quant settings, so the delta IS the memory
    # manager
    prefix_len = int(os.environ.get("BENCH_PREFIX_LEN",
                                    "32" if SMOKE else "256"))
    page_tokens = int(os.environ.get("BENCH_PAGE_TOKENS", "16"))
    n_shared = int(os.environ.get("BENCH_SHARED_REQS", str(3 * slots)))
    prefix = trace_rng.randint(0, vocab, size=(prefix_len,))
    tail_lo, tail_hi = max(1, t // 16), max(2, t // 8)
    strace = [(np.concatenate(
        [prefix, trace_rng.randint(0, vocab, size=(
            trace_rng.randint(tail_lo, tail_hi + 1),))]),
        max_new if i % 2 == 0 else max(2, max_new // 2))
        for i in range(n_shared)]
    hi2 = max(p.size for p, _ in strace)

    server_sd, shared_dense_tok_s, dense_out = run_serve(
        qpred, workload=strace, window=hi2, spec_k=spec_k)

    # paged capacity covers the worst-case live tokens of one request
    # (prompt + cap + speculation window), NOT the full T — pages
    # decouple the reservation from max-context, which is the whole win
    paged_cap = -(-(hi2 + max_new + spec_k + 2) // page_tokens) \
        * page_tokens
    pool_pages = slots * (paged_cap // page_tokens) \
        + -(-prefix_len // page_tokens) + 4
    ppred = DecodePredictor(
        sym, params, cache_len=paged_cap, ctx=ctx, temperature=0.0,
        kv_dtype=kv_dtype, paged=True, page_tokens=page_tokens,
        pool_pages=pool_pages,
        prefill_chunk=int(os.environ.get("BENCH_PREFILL_CHUNK", "64")))
    server_p, paged_tok_s, paged_out = run_serve(
        ppred, workload=strace, window=hi2, spec_k=spec_k)

    # correctness first: greedy paged+prefix serving is token-identical
    # to the dense-ring drain of the same trace
    for i, (a, b) in enumerate(zip(dense_out, paged_out)):
        assert np.array_equal(a, b), \
            "paged serve diverged from dense-ring serve on request %d" % i
    # zero retraces across admissions, COW forks and retirements: every
    # paged program traced AT MOST once across warmup + all drains (a
    # near-perfect accept rate can retire everything through verify
    # passes alone, leaving the plain decode program legitimately at 0)
    tc = ppred.trace_counts
    assert tc["chunk"] == 1 and all(
        tc[prog] <= 1 for prog in ("decode", "verify", "fork", "commit")), tc
    pstats = server_p.stats()
    assert pstats["prefix_cache_hit_rate"] > 0, pstats

    pool_gb = ppred.pool_bytes() / 1e9
    dense_gb = bytes_q * slots / 1e9
    paged_tok_s_per_gb = paged_tok_s / pool_gb
    shared_dense_tok_s_per_gb = shared_dense_tok_s / dense_gb
    vs_pr6_per_gb = paged_tok_s_per_gb / shared_dense_tok_s_per_gb
    emit({"phase": "serve_paged",
          "tokens_per_sec": round(paged_tok_s, 1),
          "dense_ring_tokens_per_sec": round(shared_dense_tok_s, 1),
          "requests": n_shared, "slots": slots,
          "prefix_len": prefix_len, "page_tokens": page_tokens,
          "pool_pages": pool_pages, "paged_cache_len": paged_cap,
          "pool_bytes": ppred.pool_bytes(),
          "dense_ring_bytes": bytes_q * slots,
          "decode_steps": server_p.steps,
          "spec_steps": server_p.spec_steps,
          "prefix_cache_hit_rate":
              round(pstats["prefix_cache_hit_rate"], 3),
          "kv_hbm_utilization":
              round(pstats["kv_hbm_utilization"], 3),
          "cow_forks": pstats["cow_forks"],
          "tokens_per_sec_per_gb": round(paged_tok_s_per_gb, 1),
          "vs_pr6_per_gb": round(vs_pr6_per_gb, 3)})
    if not SMOKE:
        # the paging acceptance line at full dims: >= 2x the PR-6
        # dense-ring capacity headline on the shared-prefix trace
        assert vs_pr6_per_gb >= 2.0, \
            "paged serve is %.2fx the dense-ring tokens/s/GB " \
            "(acceptance: >= 2x at T=%d)" % (vs_pr6_per_gb, t)

    # ---- the decode row's kernel: priced attention traffic -------------
    # Static pricing only (trace+lower, no execution, so it is exact and
    # machine-noise-free even in --smoke): the paged decode step's
    # attention traffic = one pass over the shared KV pool PLUS any
    # materialized gather intermediates.  The walk's paged_gather writes
    # (and its attention re-reads) the blocks the slots have reached of
    # the (B, M*pt, E) dense-ring view per K and V per layer (all of it
    # where the view is one block) — program_cost's gather_bytes term; the
    # decode row's Pallas kernel (ops/pallas_decode.py, taken where
    # ops.attention.decode_kernel_selected admits the shapes) copies the
    # live blocks' pages inside the kernel and has no such gather, so its
    # priced bytes must drop >= 2x where it is taken.
    from mxnet_tpu.analysis.cost import program_cost
    from mxnet_tpu.ops import attention as _attn_ops
    from mxnet_tpu.ops.attention import live_block_plan

    def _price_decode_attn(kernel, psym=sym, pparams=params):
        # kernel: a backend that runs Pallas (the interpreter in --smoke),
        # so the rule decides from the shapes; else one that is shown
        # none, so the walk (or the whole view) serves every shape
        backend = _attn_ops._kernel_backend
        _attn_ops._kernel_backend = (lambda: (True, SMOKE)) if kernel \
            else (lambda: (False, False))
        try:
            pp2 = DecodePredictor(
                psym, pparams, cache_len=paged_cap, ctx=ctx,
                temperature=0.0, kv_dtype=kv_dtype, paged=True,
                page_tokens=page_tokens, pool_pages=pool_pages)
            st = pp2.paged_batch_state(slots)
            tables, active = pp2._paged_probe_args(st)
            pp2._probing = True
            try:
                cost = program_cost(
                    pp2._decode_fn, (pp2._env, st, tables, active, key))
            finally:
                pp2._probing = False
        finally:
            _attn_ops._kernel_backend = backend
        took = "decode-kernel" in pp2._decode_paths.get(1, ())
        # the static count sees the walk over live blocks as ONE step
        # of a loop whose trip count is data: price the walk with every
        # block live, the case the kernel is compared at
        plan = None if took else live_block_plan(
            (slots, 1), (slots, paged_cap // page_tokens), page_tokens)
        if plan is not None:
            cost["gather_bytes"] *= -(
                -slots * -(-paged_cap // plan[0]) // plan[1])
        cost["decode_kernel"] = took
        return pp2.pool_bytes() + cost["gather_bytes"], cost

    attn_einsum, cost_e = _price_decode_attn(False)
    attn_fused, cost_f = _price_decode_attn(True)
    # what the TIMED serve phases above actually dispatched: the kernel
    # where this backend runs Pallas and the rule took the shapes (the
    # CPU-harness smoke keeps the walk: interpret-mode kernels would
    # measure the Pallas interpreter, not the serving loop)
    pallas_enabled = bool(_attn_ops._kernel_backend()[0]
                          and cost_f["decode_kernel"])
    attn_active = attn_fused if pallas_enabled else attn_einsum
    attn_ratio = attn_einsum / max(attn_fused, 1)
    emit({"phase": "pallas_decode",
          "pallas_decode_enabled": pallas_enabled,
          "decode_attn_bytes_einsum": attn_einsum,
          "decode_attn_bytes_fused": attn_fused,
          "gather_bytes_einsum": cost_e["gather_bytes"],
          "gather_bytes_fused": cost_f["gather_bytes"],
          "program_bytes_einsum": cost_e["bytes"],
          "program_bytes_fused": cost_f["bytes"],
          "attn_bytes_ratio": round(attn_ratio, 3)})
    if not SMOKE:
        # the kernel acceptance line at full dims (T=2048): reading the
        # live pages inside the kernel must at least halve the decode
        # step's priced attention bytes
        assert cost_f["decode_kernel"], "the rule refused the full dims"
        assert attn_fused * 2 <= attn_einsum, \
            "fused decode attention prices %d bytes vs einsum %d " \
            "(acceptance: <= 0.5x at T=%d)" % (attn_fused, attn_einsum, t)

    # ---- GQA/MQA head groups: the KV bill divided by G -----------------
    # grouped-query attention keeps every q head but shares each K/V head
    # across a group of G queries (num_kv_heads = heads/G), so every K/V
    # plane — page pools, int8 scale planes, swap wires — is physically
    # G x narrower.  Same shared-prefix trace, same spec x quant settings
    # as serve_paged: the delta IS the head grouping.
    gqa_env = os.environ.get("BENCH_GQA_GROUPS")
    wanted = tuple(int(x) for x in gqa_env.split(",")) if gqa_env \
        else (1, heads) if SMOKE else (1, 4, 8)
    gqa_grid = sorted({g for g in wanted if g >= 1 and heads % g == 0})
    dropped = sorted(set(wanted) - set(gqa_grid))
    if dropped:
        # no silent caps: name the grid points divisibility dropped
        emit({"phase": "gqa", "note": "groups %s dropped: BENCH_HEADS=%d "
              "not divisible" % (dropped, heads)})
    assert gqa_grid and gqa_grid[-1] > 1, \
        "GQA grid %r has no grouped member for heads=%d" % (gqa_grid, heads)

    # the f32 MHA pool: the ungrouped, unquantized baseline the
    # int8 x G compounding ratio divides by
    fpred = DecodePredictor(sym, params, cache_len=paged_cap, ctx=ctx,
                            temperature=0.0, kv_dtype="", paged=True,
                            page_tokens=page_tokens, pool_pages=pool_pages)
    fpred.paged_batch_state(slots)
    mha_pool_f32 = fpred.pool_bytes()
    mha_pool = ppred.pool_bytes()  # the int8 pool the serve above drained

    gqa_rows = {}
    for g in gqa_grid:
        kvh = heads // g
        if g == 1:
            # G=1 builds the SAME symbol object with ppred's exact
            # predictor config (paged/quant/spec settings verbatim), so
            # the row reuses the measured paged serve and its pricing —
            # re-serving an identical fresh predictor would only re-pay
            # its program traces.  The nontrivial G=1 bit-parity claims
            # (grouped graph json == ungrouped, dense/ring/flash/decode
            # identity) live in tests/test_gqa.py.
            gpred, server_g = ppred, server_p
            gqa_tok_s, attn_g = paged_tok_s, attn_active
        else:
            gsym = attention_lm.get_symbol(
                vocab_size=vocab, seq_len=t, num_layers=layers, embed=e,
                heads=heads, ffn_hidden=4 * e, num_kv_heads=kvh)
            grng = np.random.RandomState(0)
            # NB: the token-identity loops above rebound ``b`` — size
            # the probe from the prompt batch, not the loop leftover
            gbatch = int(prompts.shape[0])
            gshapes, _, gaux = gsym.infer_shape(
                data=(gbatch, t), softmax_label=(gbatch, t))
            gparams = {}
            for name, shape in zip(gsym.list_arguments(), gshapes):
                if name in ("data", "softmax_label"):
                    continue
                gparams[name] = grng.normal(
                    0, 0.02, shape).astype(np.float32)
            for name, shape in zip(gsym.list_auxiliary_states(), gaux):
                gparams["aux:" + name] = np.zeros(shape, np.float32)

            gpred = DecodePredictor(
                gsym, gparams, cache_len=paged_cap, ctx=ctx,
                temperature=0.0, kv_dtype=kv_dtype, paged=True,
                page_tokens=page_tokens, pool_pages=pool_pages,
                prefill_chunk=int(os.environ.get("BENCH_PREFILL_CHUNK",
                                                 "64")))
            server_g, gqa_tok_s, _gqa_out = run_serve(
                gpred, workload=strace, window=hi2, spec_k=spec_k)
            attn_g, _ = _price_decode_attn(pallas_enabled, psym=gsym,
                                           pparams=gparams)
        # grouping must not perturb trace stability: zero retraces
        # across admission, COW forks and retirement, same bar as paged
        gtc = gpred.trace_counts
        assert gtc["chunk"] == 1 and all(
            gtc[prog] <= 1
            for prog in ("decode", "verify", "fork", "commit")), gtc

        gqa_pool = gpred.pool_bytes()
        # the pool shrink is exact arithmetic, not a measurement: data
        # AND scale planes are each G x narrower
        assert gqa_pool * g == mha_pool, (g, gqa_pool, mha_pool)
        gqa_gb = gqa_pool / 1e9
        row = {"groups": g, "num_kv_heads": kvh,
               "cache_bytes_per_slot": gqa_pool // slots,
               "pool_bytes": gqa_pool,
               "pool_ratio_vs_mha": round(gqa_pool / mha_pool, 4),
               "decode_attn_bytes_per_token": round(attn_g / slots, 1),
               "attn_bytes_ratio_vs_mha": round(attn_g / attn_active, 4),
               "tokens_per_sec": round(gqa_tok_s, 1),
               "tokens_per_sec_per_gb": round(gqa_tok_s / gqa_gb, 1),
               "vs_mha_tokens_per_sec_per_gb": round(
                   (gqa_tok_s / gqa_gb) / paged_tok_s_per_gb, 3),
               "decode_steps": server_g.steps,
               "spec_steps": server_g.spec_steps}
        gqa_rows[g] = row
        emit(dict(row, phase="gqa"))

    gstar = gqa_grid[-1]
    star = gqa_rows[gstar]
    # int8 quantization compounds with grouping — both shrink the same
    # planes, so the product lands against the f32 MHA pool
    int8_vs_f32_mha = star["pool_bytes"] / mha_pool_f32
    if not SMOKE and gstar >= 4:
        # the GQA acceptance lines at full dims (T=2048, G >= 4)
        assert star["pool_bytes"] <= 0.3 * mha_pool, star
        assert star["decode_attn_bytes_per_token"] <= \
            0.35 * (attn_active / slots), (star, attn_active)
        assert int8_vs_f32_mha <= 0.1, (star, mha_pool_f32)

    print(json.dumps({
        "metric": "decode_tokens_per_sec_t%d" % t,
        "value": round(decode_tok_s, 1),
        "unit": "tok/s",
        "vs_baseline": round(decode_tok_s / naive_tok_s, 3),
        "prefill_tokens_per_sec": round(prefill_tok_s, 1),
        "decode_tokens_per_sec": round(decode_tok_s, 1),
        "serve_tokens_per_sec": round(serve_tok_s, 1),
        "serve_spec_quant_tokens_per_sec": round(serve_sq_tok_s, 1),
        "vs_pr4_serve": round(vs_pr4, 3),
        "serve_steps_ratio": round(steps_ratio, 3),
        "accept_rate": round(server_q.accept_rate, 3),
        "spec_k": spec_k,
        "kv_dtype": kv_dtype,
        "cache_bytes_per_slot_f32": bytes_f32,
        "cache_bytes_per_slot_quant": bytes_q,
        "tokens_per_sec_per_gb": round(tok_s_per_gb, 1),
        "serve_paged_tokens_per_sec": round(paged_tok_s, 1),
        "serve_paged_tokens_per_sec_per_gb": round(paged_tok_s_per_gb, 1),
        "vs_pr6_per_gb": round(vs_pr6_per_gb, 3),
        "prefix_cache_hit_rate": round(pstats["prefix_cache_hit_rate"], 3),
        "kv_hbm_utilization": round(pstats["kv_hbm_utilization"], 3),
        "pool_bytes": ppred.pool_bytes(),
        "decode_step_dot_flops": f_decode,
        "full_forward_dot_flops": f_full,
        "pallas_decode_enabled": pallas_enabled,
        "decode_attn_bytes_per_token": round(attn_active / slots, 1),
        "decode_attn_bytes_per_token_einsum": round(attn_einsum / slots, 1),
        "decode_attn_bytes_per_token_fused": round(attn_fused / slots, 1),
        "decode_attn_bytes_ratio": round(attn_ratio, 3),
        "gqa_groups": gqa_grid,
        "gqa_group": gstar,
        "gqa_num_kv_heads": heads // gstar,
        "gqa_cache_bytes_per_slot": star["cache_bytes_per_slot"],
        "gqa_pool_bytes": star["pool_bytes"],
        "gqa_pool_ratio_vs_mha": star["pool_ratio_vs_mha"],
        "gqa_decode_attn_bytes_per_token":
            star["decode_attn_bytes_per_token"],
        "gqa_attn_bytes_ratio_vs_mha": star["attn_bytes_ratio_vs_mha"],
        "gqa_tokens_per_sec": star["tokens_per_sec"],
        "gqa_tokens_per_sec_per_gb": star["tokens_per_sec_per_gb"],
        "vs_mha_tokens_per_sec_per_gb":
            star["vs_mha_tokens_per_sec_per_gb"],
        "gqa_int8_vs_f32_mha_pool_ratio": round(int8_vs_f32_mha, 4),
        "mha_pool_bytes_f32": mha_pool_f32,
    }))

# the two serving cells' attention nodes over the whole context
# (chipbench/configs, chipbench/traffic): slots, capacity, q heads, kv heads,
# key and value head widths, the prefill chunk, whether a sink and a value
# scale join
LIVE_SHAPES = {
    "opt-1.3b": dict(slots=32, cap=2048, heads=32, kv_heads=32, hd=64,
                     hdv=64, chunk=256, sink=False, value_scale=1.0),
    "mimo-v2.5": dict(slots=64, cap=9216, heads=64, kv_heads=4, hd=192,
                      hdv=128, chunk=512, sink=True, value_scale=0.707),
}


def live_block_case(shape, tq, share, seed=0, page_tokens=16):
    """``(args, whole, walk)`` of one kernel-alone case: the arrays, and the
    two attends as functions of them (``walk(block, group)`` builds one).
    Slots' lengths are spread evenly over 0.5x to 1.5x of ``share`` of the
    capacity (all full at share 1); a chunk is one slot at ``share``."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn

    d = LIVE_SHAPES[shape]
    b = d["slots"] if tq == 1 else 1
    m = d["cap"] // page_tokens
    rng = np.random.RandomState(seed)
    # a node's pools as ops.attention stores them: one scale plane, a
    # page a row, beside the K data
    data = [jnp.asarray(rng.randint(-127, 128, (b * m + 1, page_tokens,
                                                d["kv_heads"] * w), np.int8))
            for w in (d["hd"], d["hdv"])]
    pools = [attn.QuantKV(data[0], jnp.asarray(rng.uniform(
        0.005, 0.02, (b * m + 1, page_tokens * 2 * d["kv_heads"])),
        jnp.float32)), attn.QuantKV(data[1], None)]
    table = jnp.asarray(rng.permutation(b * m).reshape(b, m) + 1, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, tq, d["heads"] * d["hd"])),
                    jnp.bfloat16)
    mean = share * d["cap"]
    total = np.full(b, mean) if share >= 1 or b == 1 \
        else np.linspace(0.5 * mean, 1.5 * mean, b)
    total = jnp.asarray(np.clip(total, tq, d["cap"]), jnp.int32)
    sink = jnp.asarray(rng.normal(size=(d["heads"],)), jnp.float32) \
        if d["sink"] else None
    kw = dict(sink=sink, value_scale=d["value_scale"], layer="attn")

    def whole(q, kp, vp, table, total):
        return attn._sdpa_cache(
            q, *attn.paged_gather_kv(kp, vp, table),
            total, d["heads"], None, num_kv_heads=d["kv_heads"], **kw)

    def walk(block, group):
        return lambda q, kp, vp, table, total: attn._attend_live_blocks(
            q, kp, vp, table, total, d["heads"], None, d["kv_heads"],
            block, group, **kw)

    return (q, pools[0], pools[1], table, total), whole, walk


def live_blocks_rows(sweep):
    import jax

    from mxnet_tpu.ops import attention as attn

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("--live-blocks times kernels: it needs the chip")

    def ms(fn, args, calls=20):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*args))
        tic = time.perf_counter()
        for _ in range(calls):
            last = fn(*args)
        jax.block_until_ready(last)
        return (time.perf_counter() - tic) / calls * 1e3, out

    for shape, d in LIVE_SHAPES.items():
        for tq in (1, d["chunk"]):
            for share in (1 / 3, 1 / 2, 1):
                args, whole, walk = live_block_case(shape, tq, share)
                plan = attn.live_block_plan(args[0].shape, args[3].shape, 16)
                plans = [plan]
                if sweep and share != 1 / 2:
                    plans += [(w, max(1, r // (w * tq)))
                              for w in (128, 256, 512, 1024)
                              for r in ((4096, 8192, 16384, 32768)
                                        if tq == 1 else (w * tq, 2 * w * tq))
                              if (w, max(1, r // (w * tq))) != plan]
                t_whole, ref = ms(whole, args)
                for block, group in plans:
                    t_walk, got = ms(walk(block, group), args)
                    err = float(abs(np.asarray(got, np.float32)
                                    - np.asarray(ref, np.float32)).max())
                    print(json.dumps({
                        "phase": "live_blocks", "shape": shape, "tq": tq,
                        "live_share": round(share, 3), "block": block,
                        "group": group, "chosen": (block, group) == plan,
                        "whole_view_ms": round(t_whole, 4),
                        "live_blocks_ms": round(t_walk, 4),
                        "speedup": round(t_whole / t_walk, 3),
                        "max_abs_diff": err,
                        "device_kind": dev.device_kind}),
                        file=sys.stderr, flush=True)


if __name__ == "__main__":
    if not SMOKE:
        from mxnet_tpu.cache_dirs import arm_compile_cache

        arm_compile_cache()
    if "--live-blocks" in sys.argv:
        live_blocks_rows("--sweep" in sys.argv)
    else:
        main()
