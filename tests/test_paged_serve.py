"""Paged KV caches with copy-on-write prefix sharing (mxnet_tpu.serve +
decode paged mode + ops.attention paged kernels).

Covers the PR-7 acceptance surface: paged serving is bit-parity with the
dense ring (teacher-forced logits, per-row padded lens, generation past
capacity — ring wrap vs page recycle), chunked prefill equals one-shot
prefill, COW forks isolate slots that shared a prefix, refcounts drain to
zero on retirement, allocator exhaustion backpressures admission instead
of crashing, the (2, 2, 2) TP page pools carry the model-axis sharding
spec, and the whole schedule runs on single traces of each program.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.models import attention_lm
from mxnet_tpu.serve import PageAllocator, PrefixCache
from mxnet_tpu.test_utils import serve_reading_first, serve_tick_counts

VOCAB, T, EMBED, HEADS = 17, 16, 8, 2
B = 2


def _lm_and_params(seed=0, seq_len=T):
    sym = attention_lm.get_symbol(VOCAB, seq_len, num_layers=2, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(data=(B, seq_len),
                                       softmax_label=(B, seq_len))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = rng.normal(0, 0.5, shape).astype(np.float32)
    return sym, params


def test_paged_matches_dense_teacher_forced():
    """Prefill + teacher-forced decode over paged pools reproduces the
    dense-ring logits (1e-5) and greedy tokens, including per-row padded
    prompt lengths."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(1)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    lens = np.array([5, 9], np.int32)
    padded = x.copy()
    for b in range(B):
        padded[b, lens[b]:] = 0.0

    dense = DecodePredictor(sym, params, cache_len=T)
    paged = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4, prefill_chunk=4)
    ds, dp = dense.prefill(padded, lens)
    ps, pp = paged.prefill(padded, lens)
    np.testing.assert_allclose(np.asarray(pp), np.asarray(dp),
                               rtol=1e-5, atol=1e-6)
    for i in range(3):
        ds, dp = dense.step(ds)
        ps, pp = paged.step(ps)
        np.testing.assert_allclose(np.asarray(pp), np.asarray(dp),
                                   rtol=1e-5, atol=1e-6, err_msg="i=%d" % i)
        np.testing.assert_array_equal(np.asarray(ps.tok),
                                      np.asarray(ds.tok))
    # one chunk trace, one decode trace across the whole drive
    assert paged.trace_counts["chunk"] == 1
    assert paged.trace_counts["decode"] == 1


def test_chunked_prefill_matches_one_shot():
    """A chunk width that does not divide the prompt produces the same
    first-token distribution as one-shot (dense) prefill AND as
    single-chunk paged prefill."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(2)
    x = rng.randint(0, VOCAB, (B, 8)).astype(np.float32)
    dense = DecodePredictor(sym, params, cache_len=T)
    _, dp = dense.prefill(x, 8)
    for chunk in (3, 8):
        paged = DecodePredictor(sym, params, cache_len=T, paged=True,
                                page_tokens=4, prefill_chunk=chunk)
        _, pp = paged.prefill(x, 8)
        np.testing.assert_allclose(np.asarray(pp), np.asarray(dp),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg="chunk=%d" % chunk)


def test_page_recycle_matches_ring_wrap():
    """Generation past capacity: the dense ring wraps, the paged table
    recycles its oldest page in place — identical distributions and
    greedy tokens throughout (the gathered view IS a ring)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(3)
    x = rng.randint(0, VOCAB, (B, 6)).astype(np.float32)
    dense = DecodePredictor(sym, params, cache_len=8)
    paged = DecodePredictor(sym, params, cache_len=8, paged=True,
                            page_tokens=4)
    ds, _ = dense.prefill(x, 6)
    ps, _ = paged.prefill(x, 6)
    for i in range(8):      # wraps at total=8
        ds, dp = dense.step(ds)
        ps, pp = paged.step(ps)
        np.testing.assert_allclose(np.asarray(pp), np.asarray(dp),
                                   rtol=1e-5, atol=1e-6, err_msg="i=%d" % i)
        np.testing.assert_array_equal(np.asarray(ps.tok),
                                      np.asarray(ds.tok))


@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["float", "int8"])
def test_cow_fork_no_crosstalk(kv_dtype):
    """Two slots sharing a prefix diverge without cross-talk: identical
    prompts map the same pages (prefix cache), teacher-forcing different
    next tokens forks the shared partial page, and both rows' outputs
    match independent dense rows.  With int8 pools the fork copies the
    page's row of the node's one scale plane, and the append that follows
    reads that row, replaces one token's scales and writes it back."""
    sym, params = _lm_and_params()
    # a dense int8 prefill attends float K/V and the paged one the chunks it
    # has quantized: 2e-5 apart, before and after PR 42 to the last digit
    tol = dict(rtol=1e-3, atol=1e-4) if kv_dtype else \
        dict(rtol=1e-5, atol=1e-6)
    rng = np.random.RandomState(4)
    same = rng.randint(0, VOCAB, (6,))
    xb = np.stack([same, same]).astype(np.float32)

    paged = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4, kv_dtype=kv_dtype)
    ps, _ = paged.prefill(xb, 6)
    # row 1 matched row 0's published pages (shared, refcounted)
    mgr = paged._manager
    assert mgr.prefix_cache.hits > 0
    assert (mgr.tables[0][:1] == mgr.tables[1][:1]).all()
    ps = ps._replace(tok=jnp.asarray([[1], [2]], jnp.int32))  # diverge
    ps, pp = paged.step(ps)
    assert mgr.allocator.forks > 0        # the divergent write forked

    dense = DecodePredictor(sym, params, cache_len=T, kv_dtype=kv_dtype)
    ds, _ = dense.prefill(xb, 6)
    ds = ds._replace(tok=jnp.asarray([[1], [2]], jnp.int32))
    ds, dp = dense.step(ds)
    np.testing.assert_allclose(np.asarray(pp), np.asarray(dp), **tol)
    # a few more steps: the forked slots keep decoding independently
    for _ in range(2):
        ds, dp = dense.step(ds)
        ps, pp = paged.step(ps)
        np.testing.assert_allclose(np.asarray(pp), np.asarray(dp), **tol)

    # retirement: dropping every slot leaves only prefix-cache-held pages
    for s in range(mgr.slots):
        mgr.free_slot(s)
    assert mgr.allocator.used_pages == mgr.prefix_cache.pages_held
    mgr.prefix_cache.clear()
    assert mgr.allocator.used_pages == 0  # refcounts drained to zero


def test_paged_server_shared_prefix_matches_dense():
    """The paged server on a shared-prefix trace is token-identical to
    the dense-ring server, with prefix-cache hits, chunked admissions and
    zero retraces; per-request SLO stats are populated."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(5)
    prefix = rng.randint(0, VOCAB, (8,))
    prompts = [np.concatenate([prefix, rng.randint(0, VOCAB, (n,))])
               for n in (3, 5, 2, 4)]
    max_new = 4

    dense_srv = DecodeServer(DecodePredictor(sym, params, cache_len=T),
                             max_prefill=14, slots=2,
                             max_new_tokens=max_new)
    dids = [dense_srv.submit(p) for p in prompts]
    dres = dense_srv.run()

    paged_pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                                 page_tokens=4, prefill_chunk=5)
    paged_srv = DecodeServer(paged_pred, max_prefill=14, slots=2,
                             max_new_tokens=max_new)
    pids = [paged_srv.submit(p) for p in prompts]
    pres = paged_srv.run()
    for a, b in zip(dids, pids):
        np.testing.assert_array_equal(dres[a], pres[b])

    stats = paged_srv.stats()
    assert stats["prefix_cache_hit_rate"] > 0
    assert 0 < stats["kv_hbm_utilization"] <= 1
    assert stats["requests_completed"] == len(prompts)
    assert stats["ttft_p95_s"] >= stats["queue_wait_p50_s"] >= 0
    tc = paged_pred.trace_counts
    assert tc["chunk"] == 1 and tc["decode"] <= 1 and tc["commit"] == 1

    # profiler surfaced the per-request records too
    from mxnet_tpu import profiler

    pstats = profiler.step_stats()
    assert pstats["requests"]["count"] >= len(prompts)
    assert pstats["requests"]["ttft_p95_s"] >= 0

    # the telemetry acceptance half for serving: the always-on timeline
    # exported right after this drive is valid chrome-trace JSON whose
    # events cover the serving schedule — admissions, chunked-prefill
    # windows, retirements and the per-dispatch program spans
    from mxnet_tpu import obs
    from mxnet_tpu.test_utils import assert_chrome_trace

    assert_chrome_trace(
        obs.timeline.export(),
        required_names=("admit", "retire", "serve.prefill", "prefill",
                        "paged_decode_step"))


def test_the_fork_program_is_ready_when_a_session_opens():
    """The copy-on-write fork is the one program of the loop that a fill
    does not run; a session that shares prefixes runs it once as it opens
    (page 0 onto itself), so that the first shared page written, however
    late, compiles nothing: every page as it was, one trace before the
    first request and the same one after forks have run."""
    import jax

    sym, params = _lm_and_params()
    pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                           page_tokens=4, prefill_chunk=5)
    srv = DecodeServer(pred, max_prefill=14, slots=2, max_new_tokens=4)
    fresh = pred.paged_batch_state(2).caches
    ps = srv.serve_open()
    assert pred.trace_counts["fork"] == 1
    for a, b in zip(jax.tree_util.tree_leaves(fresh),
                    jax.tree_util.tree_leaves(ps["state"].caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rng = np.random.RandomState(5)
    prefix = rng.randint(0, VOCAB, (6,))    # ends inside a page: forks
    for n in (3, 2, 4, 3):
        srv.submit(np.concatenate([prefix, rng.randint(0, VOCAB, (n,))]))
    assert len(srv.run()) == 4
    assert pred._manager.stats()["cow_forks"] > 0
    assert pred.trace_counts["fork"] == 1


def test_paged_server_speculative_matches_generate():
    """Speculative verify over page tables (quantized pools): the paged
    spec server returns exactly what per-prompt dense generation returns,
    with one verify trace."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in (5, 7, 4)]
    max_new = 4
    qd = DecodePredictor(sym, params, cache_len=2 * T, kv_dtype="int8")
    # pad the reference prompts to ONE width: a single (1, 8) prefill
    # program serves all three references (tier-1 compile budget)
    from mxnet_tpu.decode import _pad_window

    refs = [qd.generate(_pad_window(p, 8), p.size,
                        max_new_tokens=max_new, seed=0)[0]
            for p in prompts]
    qp = DecodePredictor(sym, params, cache_len=2 * T, paged=True,
                         page_tokens=4, kv_dtype="int8")
    srv = DecodeServer(qp, max_prefill=2 * T, slots=2,
                       max_new_tokens=max_new, spec_k=3)
    ids = [srv.submit(p) for p in prompts]
    res = srv.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(res[rid], ref)
    assert srv.spec_steps > 0
    assert qp.trace_counts["verify"] == 1
    # the pools really store narrow data
    from mxnet_tpu.ops.attention import QuantKV

    mgr = qp._manager
    assert mgr is not None


def test_allocator_exhaustion_backpressure():
    """A pool too small for concurrent requests queues them (no crash)
    and drains as retirements free pages — EOS-free caps, immediate page
    frees and all; results match the unconstrained reference."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(7)
    # 4 pages total (3 usable): exactly one 5-token request's worth at
    # page_tokens=4 with its decode growth — slot 2 must WAIT
    small = DecodePredictor(sym, params, cache_len=8, paged=True,
                            page_tokens=4, pool_pages=4,
                            prefix_cache=False)
    ref_pred = DecodePredictor(sym, params, cache_len=8)
    prompts = [rng.randint(0, VOCAB, (5,)) for _ in range(3)]
    refs = [ref_pred.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=3, seed=0)[0]
            for p in prompts]
    srv = DecodeServer(small, max_prefill=8, slots=2, max_new_tokens=3)
    ids = [srv.submit(p) for p in prompts]
    res = srv.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(res[rid], ref)
    # later requests really waited on the allocator, then drained
    stats = srv.stats()
    assert stats["requests_completed"] == 3
    # everything freed at the end (no prefix cache holding pages)
    assert small._manager.allocator.used_pages == 0


def test_paged_pool_tp_sharding_spec():
    """(2, 2, 2) mesh: the page pools carry the kv_pool_pspec — E (head)
    dim sharded on 'model', page dim replicated — and paged decode
    reproduces the unsharded logits."""
    from mxnet_tpu.parallel import MeshConfig, build_mesh
    from mxnet_tpu.parallel.tp_rules import kv_pool_pspec

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device harness")
    mesh = build_mesh(MeshConfig(data=2, seq=2, model=2))
    spec = kv_pool_pspec(mesh.shape)
    assert tuple(spec) == (None, None, "model")

    sym, params = _lm_and_params()
    rng = np.random.RandomState(8)
    x = rng.randint(0, VOCAB, (B, 8)).astype(np.float32)
    plain = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4)
    shard = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4, mesh=mesh)
    s_state, s_probs = shard.prefill(x, 8)
    p_state, p_probs = plain.prefill(x, 8)
    # the pools really are model-sharded (not silently replicated)
    kc = s_state.caches[0][0]
    assert "model" in tuple(kc.sharding.spec), kc.sharding
    np.testing.assert_allclose(np.asarray(s_probs), np.asarray(p_probs),
                               rtol=1e-4, atol=1e-5)
    s_state, s_probs = shard.step(s_state)
    p_state, p_probs = plain.step(p_state)
    np.testing.assert_allclose(np.asarray(s_probs), np.asarray(p_probs),
                               rtol=1e-4, atol=1e-5)


def test_eos_mid_window_frees_pages_immediately():
    """EOS inside a speculation window retires the request AND frees its
    pages before the next admission: with a pool sized for one request
    and slots=1, the follow-up requests can only admit if retirement
    freed pages immediately."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(9)
    pred = DecodePredictor(sym, params, cache_len=16, paged=True,
                           page_tokens=4, pool_pages=5,
                           prefix_cache=False)
    ref_pred = DecodePredictor(sym, params, cache_len=16)
    prompt = rng.randint(0, VOCAB, (6,))
    ref = ref_pred.generate(prompt[None].astype(np.float32), 6,
                            max_new_tokens=8)[0]
    eos = next(int(ref[i]) for i in range(1, len(ref)) if ref[i] != ref[0])
    ref_len = int(np.flatnonzero(ref == eos)[0]) + 1
    srv = DecodeServer(pred, max_prefill=8, slots=1, eos_id=eos,
                       max_new_tokens=64, spec_k=4)
    ids = [srv.submit(prompt) for _ in range(3)]
    res = srv.run()
    for rid in ids:
        np.testing.assert_array_equal(res[rid], ref[:ref_len])
    assert srv.spec_steps > 0
    assert pred._manager.allocator.used_pages == 0


@pytest.mark.parametrize("ends", ["eos", "cap"])
def test_request_that_ends_at_its_first_token(ends):
    """Without a proposer the host reads a committed slot's first token
    after the decode step is queued behind the chunk: a request that ends
    at that token (EOS, or a cap of one) still gets it alone, its pages go
    back at once, and its neighbours get what ``generate`` gives them."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(11)
    pred = DecodePredictor(sym, params, cache_len=16, paged=True,
                           page_tokens=4, prefix_cache=False)
    ref_pred = DecodePredictor(sym, params, cache_len=16)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in (6, 5, 7)]
    refs = [ref_pred.generate(q[None].astype(np.float32), len(q),
                              max_new_tokens=5)[0] for q in prompts]
    short = 1
    eos = int(refs[short][0]) if ends == "eos" else None
    srv = DecodeServer(pred, max_prefill=8, slots=2, eos_id=eos,
                       max_new_tokens=5, spec_k=0)
    ids = [srv.submit(q, max_new_tokens=1 if ends == "cap" and i == short
                      else 5) for i, q in enumerate(prompts)]
    res = srv.run()
    for i, rid in enumerate(ids):
        want = refs[i]
        if eos is not None and eos in want:
            want = want[:int(np.flatnonzero(want == eos)[0]) + 1]
        if ends == "cap" and i == short:
            want = want[:1]
        np.testing.assert_array_equal(res[rid], want)
    assert len(res[ids[short]]) == 1
    assert pred._manager.allocator.used_pages == 0
    stats = srv.stats()
    assert stats["requests_completed"] == 3 and stats["ttft_p95_s"] > 0


# ---------------------------------------------------------------------------
# the loop reads one tick behind (PR 40): tick n + 1's programs are queued
# before tick n's tokens are read.  The oracle is the same loop made to read
# first every tick (``serve_results`` after each ``serve_tick``).
# ---------------------------------------------------------------------------
MIXED_PROMPTS = (5, 9, 3, 7, 4, 8, 6)
MIXED_CAPS = (6, 2, 7, 1, 5, 3, 4)


def _mixed_server(kv_dtype="", eos_id=None, slots=2, **kw):
    sym, params = _lm_and_params()
    pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                           page_tokens=4, prefill_chunk=4, kv_dtype=kv_dtype,
                           **kw)
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in MIXED_PROMPTS]
    return pred, DecodeServer(pred, max_prefill=12, slots=slots,
                              eos_id=eos_id, spec_k=0), prompts


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_reading_behind_gives_the_tokens_of_reading_first(kv_dtype):
    """Seven requests of mixed prompt and output lengths through two slots
    (every slot reused, a cap of one among them): the loop that reads a tick
    behind gives each request the tokens of the loop that reads first, as
    many as its cap; a tick that follows a step queues its programs behind
    it, unread."""
    pred, srv, prompts = _mixed_server(kv_dtype)
    before = serve_tick_counts()
    ids = [srv.submit(p, max_new_tokens=c)
           for p, c in zip(prompts, MIXED_CAPS)]
    behind = srv.run()
    moved = serve_tick_counts(before)
    # first: the session's first tick, and one that follows a tick which
    # queued no step (a prompt's earlier chunk into an empty batch)
    assert moved["behind"] > 10 and moved["first"] <= 4, moved
    assert moved["dropped"] == 0
    assert srv.tokens_out == sum(MIXED_CAPS)
    before = serve_tick_counts()
    ids2 = [srv.submit(p, max_new_tokens=c)
            for p, c in zip(prompts, MIXED_CAPS)]
    first = serve_reading_first(srv)
    moved = serve_tick_counts(before)
    assert moved["behind"] == 0 and moved["first"] > 10, moved
    for a, b, cap in zip(ids, ids2, MIXED_CAPS):
        np.testing.assert_array_equal(behind[a], first[b])
        assert len(behind[a]) == cap
    assert pred._manager.allocator.used_pages == \
        pred._manager.prefix_cache.pages_held
    tc = pred.trace_counts
    assert tc["chunk"] == 1 and tc["decode"] == 1 and tc["commit"] == 1


@pytest.mark.parametrize("ends", ["mid", "first", "cap1"])
def test_an_eos_is_seen_one_step_late_and_moves_no_token(ends):
    """With ``eos_id`` a slot rides one step past its EOS, the row is
    dropped and counted, and every request has the tokens of the loop that
    reads first and of its own ``generate``: an EOS in the middle of an
    answer, an EOS as the first token, and a cap of one beside an EOS."""
    sym, params = _lm_and_params()
    ref_pred = DecodePredictor(sym, params, cache_len=T)
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in MIXED_PROMPTS]
    refs = [ref_pred.generate(_pad(p, 12), p.size, max_new_tokens=6)[0]
            for p in prompts]
    if ends == "first":
        eos = int(refs[1][0])
    else:       # a token some answer reaches after two others at least
        eos = next(int(r[j]) for r in refs for j in range(2, 6)
                   if r[j] not in r[:j])
    caps = [1 if ends == "cap1" and i == 2 else 6
            for i in range(len(prompts))]
    want = []
    for r, cap in zip(refs, caps):
        r = r[:cap]
        hit = np.flatnonzero(r == eos)
        want.append(r[:int(hit[0]) + 1] if hit.size else r)
    assert any(1 < len(w) < 6 for w in want) or ends == "first"
    pred, srv, _ = _mixed_server(eos_id=eos)
    before = serve_tick_counts()
    ids = [srv.submit(p, max_new_tokens=c) for p, c in zip(prompts, caps)]
    behind = srv.run()
    moved = serve_tick_counts(before)
    assert moved["dropped"] >= 1 and moved["behind"] > 0, moved
    ids2 = [srv.submit(p, max_new_tokens=c) for p, c in zip(prompts, caps)]
    first = serve_reading_first(srv)
    for a, b, w in zip(ids, ids2, want):
        np.testing.assert_array_equal(behind[a], w)
        np.testing.assert_array_equal(first[b], w)
    assert srv.tokens_out == 2 * sum(len(w) for w in want)
    assert pred._manager.allocator.used_pages == \
        pred._manager.prefix_cache.pages_held


def _pad(prompt, width):
    from mxnet_tpu.decode import _pad_window

    return _pad_window(prompt, width)


def _tick_until_unread_alone(srv):
    """Tick until nothing is queued, mid-prefill or in a slot, and the last
    step's tokens are still on the device."""
    srv.serve_reset()
    ps = srv.serve_open()
    while srv._queue or ps["active"] or ps["pending"]:
        srv.serve_tick()
    assert ps["unread"] is not None
    return ps


@pytest.mark.parametrize("reader", ["has_work", "serve_results",
                                    "serve_reset", "stats", "inject"])
def test_a_step_unread_is_read_by_whoever_needs_its_tokens(reader):
    """A request that ended by its cap has left its slot while its last
    token is unread: ``has_work`` stays true for the tick that reads it, and
    ``serve_results`` / ``stats`` / ``serve_reset`` / ``inject`` read it
    themselves; its record closes with all its tokens either way."""
    pred, srv, prompts = _mixed_server()
    rid = srv.submit(prompts[0], max_new_tokens=3)
    ps = _tick_until_unread_alone(srv)
    assert srv.has_work and not ps["results"] and srv.tokens_out == 2
    assert "retire" not in srv._req[rid]
    if reader == "has_work":
        srv.serve_tick()
        assert not srv.has_work
        assert len(srv.serve_results()[rid]) == 3
    elif reader == "serve_results":
        assert len(srv.serve_results(clear=False)[rid]) == 3
        assert not srv.has_work
    elif reader == "stats":
        assert srv.stats()["requests_completed"] == 1
        assert not srv.has_work
    elif reader == "inject":
        from mxnet_tpu.serve.swap import SwappedRequest

        record = SwappedRequest(
            prompts[1], [1], list(prompts[1]) + [1], 2, 0, prompts[1].size,
            1, np.zeros(pred._manager.pages_per_slot, bool), None)
        srv.inject(record)
        assert ps["unread"] is None and len(ps["results"][rid]) == 3
    else:
        srv.serve_reset()
        assert srv._ps is None and not srv.has_work
    assert srv._req[rid]["tokens"] == 3 and srv.tokens_out == 3


@pytest.mark.parametrize("why", ["proposer", "swap", "no_reserve"])
def test_what_needs_the_tokens_reads_first(why):
    """A proposer drafts from the histories, a swap-out takes the victim's
    tokens with it, and a row that may be dropped may not take a page
    beyond its slot's reservation: each reads before it queues, counted
    ``mx_serve_ticks_total{read="first"}``, and the tokens are ``generate``'s."""
    from mxnet_tpu import config as _cfg

    sym, params = _lm_and_params(seed=3)
    rng = np.random.RandomState(3)
    long_p, short_p = rng.randint(0, VOCAB, (6,)), rng.randint(0, VOCAB, (5,))
    ref_pred = DecodePredictor(sym, params, cache_len=T)
    ref_long = ref_pred.generate(_pad(long_p, 8), 6, max_new_tokens=10)[0]
    ref_short = ref_pred.generate(_pad(short_p, 8), 5, max_new_tokens=4)[0]
    before = serve_tick_counts()
    if why == "swap":
        with _cfg.overrides(MXNET_FLEET_DECODE_BOUND="4",
                            MXNET_FLEET_SWAP="1"):
            pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                                   page_tokens=4, prefill_chunk=4,
                                   pool_pages=6, prefix_cache=False)
            srv = DecodeServer(pred, max_prefill=8, slots=2, spec_k=0)
            r1 = srv.submit(long_p, 10, priority=-1)
            r2 = srv.submit(short_p, 4, priority=1)
            res = srv.run()
        moved = serve_tick_counts(before)
        assert srv.swap_outs >= 1
        # the session's first tick, the tick that swapped out, the tick
        # that restored (and one after a tick that queued a chunk alone)
        assert 1 + srv.swap_outs + srv.swap_ins <= moved["first"] \
            < moved["behind"], moved
    else:
        eos = None
        if why == "no_reserve":
            eos = next(t for t in range(VOCAB)
                       if t not in ref_long and t not in ref_short)
        pred = DecodePredictor(sym, params, cache_len=T, paged=True,
                               page_tokens=4, prefill_chunk=4)
        srv = DecodeServer(pred, max_prefill=8, slots=2, eos_id=eos,
                           spec_k=3 if why == "proposer" else 0)
        r1, r2 = srv.submit(long_p, 10), srv.submit(short_p, 4)
        if why == "no_reserve":
            mgr = srv.serve_open() and pred._manager
            asked = []
            mgr.within_reserve = lambda *a: asked.append(a) or False
            while srv.has_work:
                srv.serve_tick()
            res = srv.serve_results()
            assert asked
        else:
            res = srv.run()
            assert srv.spec_steps > 0
        moved = serve_tick_counts(before)
        # no decode step is queued behind an unread one (a tick that steps
        # nothing, the last one, has only the read left)
        assert moved["first"] > 4 and moved["behind"] <= \
            (0 if why == "proposer" else 2), moved
    np.testing.assert_array_equal(res[r1], ref_long)
    np.testing.assert_array_equal(res[r2], ref_short)


def test_within_reserve_counts_the_pages_an_append_would_take():
    from mxnet_tpu.serve import PagedKVManager

    mgr = PagedKVManager(2, 16, 4, prefix_cache=False)
    got = mgr.gate(np.arange(6), 6, 4)
    mgr.map_slot(0, got[1], got[2])
    mgr.ensure(0, 0, 6)                     # two pages, from the reservation
    assert mgr.within_reserve(0, 6, 7)      # inside the second page
    assert mgr.within_reserve(0, 8, 9)      # a third, still reserved
    mgr.allocator.unreserve(int(mgr._reserve[0]))
    mgr._reserve[0] = 0
    assert mgr.within_reserve(0, 7, 8) and not mgr.within_reserve(0, 8, 9)
    assert mgr.within_reserve(0, 8, 8)      # nothing to write


def test_the_read_comes_after_the_dispatch_and_reads_the_tick_before(
        monkeypatch):
    """Structure of the new order, from the timeline and the transfers: in
    a tick no ``serve.readback`` begins before that tick's
    ``serve.decode_dispatch`` has ended, and what it fetches is the token
    copy the PREVIOUS tick left in ``ps["unread"]``, not this tick's."""
    from mxnet_tpu import config, obs

    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    config.refresh("MXNET_TELEMETRY")
    try:
        pred, srv, prompts = _mixed_server()
        for p, c in zip(prompts[:4], (5, 6, 4, 5)):
            srv.submit(p, max_new_tokens=c)
        fetched = []
        real = jax.device_get
        monkeypatch.setattr(jax, "device_get",
                            lambda x: fetched.append(list(x)) or real(x))
        obs.timeline.clear()
        srv.serve_reset()
        ps = srv.serve_open()
        left, reads = [], []        # after tick i; during tick i
        while srv.has_work:
            n = len(fetched)
            srv.serve_tick()
            reads.append(fetched[n:])
            left.append(ps["unread"])
        for i in range(1, len(left)):
            if left[i - 1] is None:
                continue
            assert len(reads[i]) == 1       # one transfer a tick
            want = left[i - 1]["toks"]
            assert want is None or any(a is want for a in reads[i][0])
            if left[i] is not None and left[i]["toks"] is not None:
                assert not any(a is left[i]["toks"] for a in reads[i][0])
        ev = obs.timeline.events()
    finally:
        monkeypatch.undo()
        config.refresh("MXNET_TELEMETRY")
    ticks = [e for e in ev if e["name"] == "serve.tick"]
    # behind, wherever the tick before left something to read
    assert [t["args"]["read"] for t in ticks] == ["first"] + [
        "first" if was is None else "behind" for was in left[:-1]]
    assert [t["args"]["read"] for t in ticks].count("behind") > 10
    inside = lambda e, t: t["ts"] <= e["ts"] and \
        e["ts"] + e["dur"] <= t["ts"] + t["dur"]
    seen = 0
    for t in ticks:
        disp = [e for e in ev if e["name"] == "serve.decode_dispatch"
                and inside(e, t)]
        back = [e for e in ev if e["name"] == "serve.readback"
                and inside(e, t)]
        assert len(back) <= 1 and len(disp) <= 1
        if disp and back:
            seen += 1
            assert disp[0]["ts"] + disp[0]["dur"] <= back[0]["ts"]
    assert seen > 5


def test_allocator_and_prefix_cache_units():
    """Unit coverage of the host-side bookkeeping: refcounts, reservation
    accounting, LRU eviction, partial-page matching, release_page."""
    alloc = PageAllocator(6)
    a, b = alloc.alloc(), alloc.alloc()
    assert alloc.used_pages == 2 and a != b and a != 0 and b != 0
    assert alloc.reserve(3) and not alloc.reserve(1)
    assert alloc.available() == 0
    alloc.unreserve(1)
    c = alloc.alloc()                      # 2 free remain, 2 reserved
    assert alloc.available() == 0
    alloc.incref(c)
    assert alloc.shared(c)
    assert not alloc.decref(c) and alloc.decref(c)
    assert alloc.free_pages == 3

    alloc2 = PageAllocator(8)
    cache = PrefixCache(4, alloc2)
    toks = np.arange(10)                   # 2 full pages + 2-token tail
    pages = [alloc2.alloc(), alloc2.alloc(), alloc2.alloc()]
    cache.insert(toks, 10, pages)
    # identical prompt: matches both full pages + the partial, capped L-1
    matched, got = cache.match(toks)
    assert matched == 9 and got == pages
    # same 2-page prefix, divergent tail: full pages only
    other = np.concatenate([toks[:8], [99, 98]])
    matched2, got2 = cache.match(other)
    assert matched2 == 8 and got2 == pages[:2]
    assert cache.hit_rate > 0
    # release_page invalidates entries without touching other holders
    dropped = cache.release_page(pages[2])
    assert dropped == 1 and alloc2.refcount(pages[2]) == 1
    # eviction frees cache-only pages
    for p in pages:
        alloc2.decref(p)                   # drop the "slot" refs
    freed = cache.evict(2)
    assert freed == 2 and alloc2.used_pages == 0


def test_prefix_cache_drops_what_continued_an_evicted_page():
    """A chain is walked from its first page, so what continued a dropped
    page is dropped with it: its pages come back and its digests leave
    ``summary()`` at once, and the same prompt published again is a chain
    of its own pages, matched whole."""
    from mxnet_tpu.serve import chain_hash

    alloc = PageAllocator(16)
    cache = PrefixCache(4, alloc)
    toks = np.arange(100, 114)             # 3 full pages + a 2-token tail
    first = [alloc.alloc() for _ in range(4)]
    cache.insert(toks, 14, first)
    other = np.arange(200, 208)            # a second chain, published later
    second = [alloc.alloc() for _ in range(2)]
    cache.insert(other, 8, second)
    for p in first + second:
        alloc.decref(p)                    # the slots retire
    assert cache.pages_held == 6 and alloc.used_pages == 6
    # the oldest entry is the first chain's first page: one page asked for,
    # the whole chain goes (nothing could reach the rest), the other stays
    assert cache.evict(1) == 4
    assert cache.pages_held == 2 and alloc.used_pages == 2
    assert cache.match(toks) == (0, [])
    assert cache.match(other) == (7, second)
    summ = cache.summary()
    assert summ["full"] == [chain_hash(other[:4]), chain_hash(other)]
    assert summ["partial"] == []
    # published again from other pages: matched whole, page for page
    again = [alloc.alloc() for _ in range(4)]
    cache.insert(toks, 14, again)
    assert cache.match(toks) == (13, again)
    assert cache.pages_held == 6
    summ = cache.summary()
    assert [chain_hash(toks[:4 * k]) in summ["full"] for k in (1, 2, 3)] \
        == [True] * 3
    assert summ["partial"] == [{"prefix": chain_hash(toks[:12]), "len": 2,
                                "hash": chain_hash(toks[12:])}]
    # a page recycled in the middle takes what continued it, not what led
    # to it; the slot's own references keep the pages
    assert cache.release_page(again[1]) == 3
    assert cache.match(toks) == (4, again[:1])
    assert cache.pages_held == 3 and alloc.used_pages == 6
    assert len(cache.summary()["full"]) == 3
    for p in again:
        alloc.decref(p)
    assert alloc.used_pages == 3
    cache.clear()
    assert alloc.used_pages == 0 and not cache._children and not cache._meta


def test_cache_bytes_pass_understands_paged_layouts():
    """mxlint satellite: the cache-bytes pass budgets pool bytes and
    errors on a dense-ring allocation under MXNET_KV_PAGED=1."""
    from mxnet_tpu.analysis import load_budgets, run_passes
    from mxnet_tpu.analysis.artifact import ProgramArtifact
    from mxnet_tpu.analysis.passes import CacheBytesPass

    paged_ok = ProgramArtifact(
        name="paged_decode_step", jaxpr_text="", stablehlo_text="",
        compiled_text="", meta={"cache_bytes": 1024, "kv_dtype": None,
                                "cache_data_dtypes": ["float32"],
                                "cache_layout": "paged", "kv_paged": True,
                                "page_tokens": 4, "pool_pages": 8})
    dense_bad = ProgramArtifact(
        name="decode_step", jaxpr_text="", stablehlo_text="",
        compiled_text="", meta={"cache_bytes": 1024, "kv_dtype": None,
                                "cache_data_dtypes": ["float32"],
                                "cache_layout": "dense",
                                "kv_paged": True})
    budgets = {"programs": {"paged_decode_step": {"cache_bytes": 2048},
                            "decode_step": {"cache_bytes": 2048}}}
    report = run_passes([paged_ok, dense_bad], passes=[CacheBytesPass()],
                        budgets=budgets)
    codes = {(f.program, f.code) for f in report.findings}
    assert ("paged_decode_step", "within-budget") in codes
    assert ("decode_step", "dense-under-paged") in codes
    assert any(f.severity == "error" for f in report.findings
               if f.code == "dense-under-paged")


@pytest.mark.parametrize("t", [1, 8], ids=["step", "chunk"])
@pytest.mark.parametrize("kvh", [4, 8, 32])
def test_quantized_pools_keep_what_a_plane_a_pool_kept(kvh, t):
    """``paged_append_kv`` then ``paged_gather_kv`` over a node's int8 pools,
    whose scales share one plane (P, page_tokens * 2 * H), return element
    for element what a (P, page_tokens, H) plane a pool returned: the
    expectation is built by plain numpy indexing.  A step of one token a
    slot with an inactive row, and a chunk whose last rows lie past
    ``valid`` and that starts inside a page: the scratch page alone takes
    the masked writes."""
    from mxnet_tpu.ops import attention as attn

    pt, m, hd = 4, 6, 8
    b = 3 if t == 1 else 1
    pages = 1 + b * m
    rng = np.random.RandomState(kvh + t)
    old = [rng.normal(size=(pages, pt, kvh * hd)).astype(np.float32)
           for _ in range(2)]
    kp, vp = attn.quantize_pools(*map(jnp.asarray, old), "int8", kvh)
    assert kp.scale.shape == (pages, pt * 2 * kvh) and vp.scale is None
    table = 1 + rng.permutation(b * m).reshape(b, m).astype(np.int32)
    new = [rng.normal(size=(b, t, kvh * hd)).astype(np.float32)
           for _ in range(2)]
    start = np.asarray([5, 0, 22][:b], np.int32)     # 22: wraps the ring
    active = np.asarray([1, 0, 1][:b], np.int32)
    valid = None if t == 1 else np.asarray([t - 3], np.int32)

    # a plane a pool, as the pools were stored: (P, pt, E) and (P, pt, H)
    want = [attn.quantize_kv(jnp.asarray(x), "int8", kvh) for x in old]
    want = [[np.asarray(w.data).copy(), np.asarray(w.scale).copy()]
            for w in want]
    fresh = [attn.quantize_kv(jnp.asarray(x), "int8", kvh) for x in new]
    written = set()
    for r in range(b):
        for j in range(t):
            if not active[r] or (valid is not None and j >= valid[r]):
                continue
            pos = int(start[r]) + j
            page, slot = table[r, (pos // pt) % m], pos % pt
            written.add(int(page))
            for w, f in zip(want, fresh):
                w[0][page, slot] = np.asarray(f.data)[r, j]
                w[1][page, slot] = np.asarray(f.scale)[r, j]

    # op by op, as the expectation's scales were computed (under jit XLA
    # folds the division by 127 another way, an ulp apart)
    got_k, got_v = attn.paged_append_kv(
        kp, vp, jnp.asarray(table), *map(jnp.asarray, new),
        jnp.asarray(start), num_heads=kvh, active=jnp.asarray(active),
        valid=None if valid is None else jnp.asarray(valid))
    assert got_k.scale.shape == kp.scale.shape and got_v.scale is None
    # every page but the scratch page, as stored
    plane = np.asarray(got_k.scale).reshape(pages, pt, 2, kvh)
    for i, (got, w) in enumerate(zip((got_k, got_v), want)):
        np.testing.assert_array_equal(np.asarray(got.data)[1:], w[0][1:])
        np.testing.assert_array_equal(plane[1:, :, i], w[1][1:])
    assert written and 0 not in written
    # and as the slots' views show them: a dense ring a slot
    view_k, view_v = attn.paged_gather_kv(got_k, got_v, jnp.asarray(table))
    for view, w in zip((view_k, view_v), want):
        np.testing.assert_array_equal(
            np.asarray(view.data), w[0][table].reshape(b, m * pt, -1))
        np.testing.assert_array_equal(
            np.asarray(view.scale), w[1][table].reshape(b, m * pt, kvh))


def test_a_chunk_as_long_as_its_ring_lands_where_it_wraps():
    """The pages a chunk can touch are then the whole ring, and the tokens
    that run past its end land at its start, in the page the chunk began
    in."""
    from mxnet_tpu.ops import attention as attn

    pt, m, kvh, hd = 4, 2, 2, 4
    rng = np.random.RandomState(5)
    zeros = jnp.zeros((3, pt, kvh * hd), jnp.float32)
    kp, vp = attn.quantize_pools(zeros, zeros, "int8", kvh)
    table = jnp.asarray([[2, 1]], jnp.int32)
    new = [jnp.asarray(rng.normal(size=(1, m * pt, kvh * hd)), jnp.float32)
           for _ in range(2)]
    kp, vp = attn.paged_append_kv(kp, vp, table, *new, 3, num_heads=kvh)
    view_k, view_v = attn.paged_gather_kv(kp, vp, table)
    for view, x in zip((view_k, view_v), new):
        want = attn.quantize_kv(x, "int8", kvh)
        # token j sits at ring position (3 + j) % 8
        order = (np.arange(m * pt) - 3) % (m * pt)
        np.testing.assert_array_equal(np.asarray(view.scale)[0],
                                      np.asarray(want.scale)[0][order])
        np.testing.assert_array_equal(np.asarray(view.data)[0],
                                      np.asarray(want.data)[0][order])


def test_paged_int8_pools_under_a_mesh_replicate_the_scale_plane():
    """A row of the scale plane is tokens x heads: no split of it is a
    head-group split, so under a mesh the plane replicates (and says so)
    while the data planes keep the model-axis split; logits match the
    unsharded predictor."""
    from mxnet_tpu.parallel import MeshConfig, build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device harness")
    mesh = build_mesh(MeshConfig(data=2, seq=2, model=2))
    sym, params = _lm_and_params()
    rng = np.random.RandomState(9)
    x = rng.randint(0, VOCAB, (B, 8)).astype(np.float32)
    plain = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4, kv_dtype="int8")
    shard = DecodePredictor(sym, params, cache_len=T, paged=True,
                            page_tokens=4, kv_dtype="int8", mesh=mesh)
    # as placed (what a program hands back is GSPMD's to choose)
    kc, vc = shard.paged_batch_state(B).caches[0]
    assert "model" in tuple(kc.data.sharding.spec), kc.data.sharding
    assert "model" in tuple(vc.data.sharding.spec), vc.data.sharding
    assert kc.scale.shape == (kc.data.shape[0], 4 * 2 * HEADS)
    assert vc.scale is None
    assert not any(tuple(kc.scale.sharding.spec)), kc.scale.sharding
    assert any(d["site"] == "pool-scale" for d in shard._replicated_degrades)
    s_state, s_probs = shard.prefill(x, 8)
    p_state, p_probs = plain.prefill(x, 8)
    np.testing.assert_allclose(np.asarray(s_probs), np.asarray(p_probs),
                               rtol=1e-4, atol=1e-5)
    for _ in range(2):
        s_state, s_probs = shard.step(s_state)
        p_state, p_probs = plain.step(p_state)
        np.testing.assert_allclose(np.asarray(s_probs), np.asarray(p_probs),
                                   rtol=1e-4, atol=1e-5)


def _each_is_the_argmax_of_one_pass(system_probs, sym, params, prompts,
                                    answers):
    """Every answer is the arg max of ONE whole forward pass over its own
    sequence, at every decoded position: the sequences as rows of one
    padded batch."""
    from test_decoder_lm import padded_rows

    seqs = [np.concatenate([p, a[:-1]]) for p, a in zip(prompts, answers)]
    batch = padded_rows(seqs)
    probs = np.asarray(system_probs(sym, params, batch))
    probs = probs.reshape(batch.shape + probs.shape[-1:])   # (B * T, V)
    for row, p, seq, answer in zip(probs, prompts, seqs, answers):
        assert np.array_equal(row[p.size - 1:seq.size].argmax(-1), answer)


# ---------------------------------------------------------------------------
# a state group of two-leaf rows, a full group of int8 pages and held experts
# in one graph (``solar_open2``'s keys at a toy size)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def delta_graph():
    from test_decoder_lm import build, delta_config, system_probs

    cfg = delta_config(serve_num_hidden_layers=4)
    sym, params = build(cfg)
    return cfg, sym, params, system_probs


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_delta_rows_beside_pages_and_held_experts_serve_as_one_pass(
        delta_graph, kv_dtype):
    """Five requests through two slots (chunks of 8, then decode steps, each
    slot reused: a chunk at position 0 voids the state and the tail the last
    request left): every request's tokens are its own ``generate``'s, and
    each is the arg max of ONE whole forward pass over the sequence that was
    served, at every decoded position."""
    from mxnet_tpu import obs
    from mxnet_tpu.base import MXNetError

    cfg, sym, params, system_probs = delta_graph
    nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
    make = lambda: DecodePredictor(
        sym, nd, cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=4,
        kv_dtype=kv_dtype, prefill_chunk=8)
    pred = make()
    assert [g.kind for g in pred._groups] == ["full", "state"]
    assert [l.kind for l in pred.cache_layouts()] == ["full"] + ["state"] * 3
    # a row: 3 delta layers x (3 positions of 3 x 64 channels, 4 x 16 x 16)
    row = 3 * (3 * 192 * 4 + 4 * 16 * 16 * 4)
    assert pred.state_row_bytes() == pred.state_row_bytes("kda_rows") == row
    assert pred.state_nodes("kda_rows") == 3
    assert pred.state_nodes("linattn_rows") == pred.state_nodes("ssm_rows") \
        == 0
    server = DecodeServer(pred, max_prefill=32, slots=2, spec_k=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 26, 9, 30)]
    noted = lambda: [e["args"] for e in obs.timeline.events()
                     if e["name"] == "serve.readback" and e.get("args")]
    seen = len(noted())
    rids = [server.submit(p, max_new_tokens=10) for p in prompts]
    results = server.run()
    alone = make()
    for rid, p in zip(rids, prompts):
        want = alone.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=10)[0]
        assert np.array_equal(results[rid], want), rid
    if not kv_dtype:    # int8 keys move a near-tie; float pools are exact
        _each_is_the_argmax_of_one_pass(system_probs, sym, params, prompts,
                                        [results[rid] for rid in rids])
    rows = [a["kda_rows"] for a in noted()[seen:] if "kda_rows" in a]
    assert rows and set(rows) <= {3, 6}
    assert sum(rows) == 3 * len(prompts) * (10 - 1)
    snap = obs.registry.snapshot()
    assert snap["mx_kda_state_bytes"]["series"][0]["value"] == 2 * row
    assert snap["mx_kda_rows_total"]["series"][0]["value"] >= sum(rows)
    # what a state group refuses today stays refused by name
    assert not server._swap_armed
    with pytest.raises(MXNetError, match="'state' cache group.*not in pages"):
        server.inject(object())
    with pytest.raises(MXNetError, match="'state' cache group.*rejected "
                                         "draft has already advanced"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=2)
    state, _ = pred.prefill(prompts[0][None].astype(np.float32),
                            np.array([5]))
    with pytest.raises(MXNetError, match="KimiDeltaAttention.*rejected "
                                         "draft"):
        pred.verify_step(state, np.zeros((1, 3), np.int32))
    from mxnet_tpu.parallel.mesh import MeshConfig, build_mesh
    with pytest.raises(MXNetError, match="KimiDeltaAttention.*one device"):
        DecodePredictor(sym, nd, cache_len=64, ctx=mx.cpu(), paged=True,
                        page_tokens=4, prefill_chunk=8,
                        mesh=build_mesh(MeshConfig(model=2)))


# ---------------------------------------------------------------------------
# a state group of two-leaf rows whose widths differ (a 2 x 24 + 48-channel
# tail, 8 x 16 matrices) beside int8 pages of THREE KV heads, whose scale row
# is padded (``olmo_hybrid``'s keys at a toy size)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def gdn_graph():
    from test_decoder_lm import build, gdn_config, system_probs

    cfg = gdn_config()
    sym, params = build(cfg)
    return cfg, sym, params, system_probs


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_gated_deltanet_rows_beside_padded_int8_pages_serve_as_one_pass(
        gdn_graph, kv_dtype):
    """Five requests through two slots (chunks of 8, then decode steps, each
    slot reused): every request's tokens are its own ``generate``'s, and over
    float pools the arg max of ONE whole forward pass over the sequence that
    was served; the rows are counted under the op's own name, and what a
    state group refuses stays refused by name."""
    from mxnet_tpu import obs
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops.attention import QuantKV

    cfg, sym, params, system_probs = gdn_graph
    nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
    make = lambda: DecodePredictor(
        sym, nd, cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=4,
        kv_dtype=kv_dtype, prefill_chunk=8)
    pred = make()
    assert [l.kind for l in pred.cache_layouts()] == ["state"] * 3 + ["full"]
    # a row: 3 delta layers x (3 positions of 96 channels, 3 x 8 x 16)
    row = 3 * (3 * 96 * 4 + 3 * 8 * 16 * 4)
    assert pred.state_row_bytes() == pred.state_row_bytes("gdn_rows") == row
    assert pred.state_nodes("gdn_rows") == 3
    assert pred.state_nodes("kda_rows") == 0
    if kv_dtype:
        kc, vc = pred.paged_batch_state(2).caches[3]
        assert isinstance(kc, QuantKV) and vc.scale is None
        assert kc.scale.shape == (kc.data.shape[0], 4 * 8)     # 6 -> 8
    server = DecodeServer(pred, max_prefill=32, slots=2, spec_k=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 26, 9, 30)]
    noted = lambda: [e["args"] for e in obs.timeline.events()
                     if e["name"] == "serve.readback" and e.get("args")]
    seen = len(noted())
    rids = [server.submit(p, max_new_tokens=10) for p in prompts]
    results = server.run()
    alone = make()
    for rid, p in zip(rids, prompts):
        want = alone.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=10)[0]
        assert np.array_equal(results[rid], want), rid
    if not kv_dtype:    # int8 keys move a near-tie; float pools are exact
        _each_is_the_argmax_of_one_pass(system_probs, sym, params, prompts,
                                        [results[rid] for rid in rids])
    rows = [a["gdn_rows"] for a in noted()[seen:] if "gdn_rows" in a]
    assert rows and set(rows) <= {3, 6}
    assert sum(rows) == 3 * len(prompts) * (10 - 1)
    snap = obs.registry.snapshot()
    assert snap["mx_gdn_state_bytes"]["series"][0]["value"] == 2 * row
    assert snap["mx_gdn_rows_total"]["series"][0]["value"] >= sum(rows)
    # the names and help texts of the three older ops' metrics as they were
    for name, text in (
            ("mx_ssm_rows_total", "(slot, SelectiveSSM node) rows whose "
             "recurrent state a decode step advanced (idle and mid-prefill "
             "slots left out)"),
            ("mx_linattn_state_bytes", "bytes of the state cache group's "
             "LightningAttention rows: every slot's (H, D, D) float32 "
             "states"),
            ("mx_kda_rows_total", "(slot, KimiDeltaAttention node) rows "
             "whose matrix state a decode step advanced (idle and "
             "mid-prefill slots left out)")):
        assert snap[name]["help"] == text
    assert not server._swap_armed
    with pytest.raises(MXNetError, match="'state' cache group.*rejected "
                                         "draft has already advanced"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=2)
    state, _ = pred.prefill(prompts[0][None].astype(np.float32),
                            np.array([5]))
    with pytest.raises(MXNetError, match="GatedDeltaNet.*rejected draft"):
        pred.verify_step(state, np.zeros((1, 3), np.int32))
    from mxnet_tpu.parallel.mesh import MeshConfig, build_mesh
    with pytest.raises(MXNetError, match="GatedDeltaNet.*one device"):
        DecodePredictor(sym, nd, cache_len=64, ctx=mx.cpu(), paged=True,
                        page_tokens=4, prefill_chunk=8,
                        mesh=build_mesh(MeshConfig(model=2)))


# ---------------------------------------------------------------------------
# a graph whose every layer is ONE sublayer (``nemotron_h``'s letters at a toy
# size): stateless layers (experts, a dense MLP) lie BETWEEN the stateful
# ones, whose rows (a conv tail and a state) and int8 pages of two KV heads a
# slot carries
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def letter_graph():
    from test_decoder_lm import build, letter_config, system_probs

    cfg = letter_config()
    sym, params = build(cfg)
    return cfg, sym, params, system_probs


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_stateless_layers_between_stateful_ones_serve_as_one_pass(
        letter_graph, kv_dtype):
    """Five requests through two slots (admitted, prefilled in chunks of 8,
    decoded, retired, each slot handed to the next request with no clearing
    program): every request's tokens are its own ``generate``'s, and over
    float pools the arg max of ONE whole forward pass over the sequence that
    was served; the tick's counters carry the mixers' rows and the held
    experts' pairs; a state row cannot be swapped out and in, so preemption
    stays disarmed, and what a state group refuses stays refused by name."""
    from mxnet_tpu import obs
    from mxnet_tpu.base import MXNetError

    cfg, sym, params, system_probs = letter_graph
    nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
    make = lambda: DecodePredictor(
        sym, nd, cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=4,
        kv_dtype=kv_dtype, prefill_chunk=8)
    pred = make()
    # ME*-EM: two state rows and one node of pages; E and - keep nothing
    assert [l.kind for l in pred.cache_layouts()] == ["state", "full",
                                                      "state"]
    # a row: 2 mixer layers x (3 positions of 96 channels, 4 x 8 x 16)
    row = 2 * (3 * 96 * 4 + 4 * 8 * 16 * 4)
    assert pred.state_row_bytes() == pred.state_row_bytes("ssm_rows") == row
    assert pred.state_nodes("ssm_rows") == 2
    server = DecodeServer(pred, max_prefill=32, slots=2, spec_k=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 26, 9, 30)]
    noted = lambda: [e["args"] for e in obs.timeline.events()
                     if e["name"] == "serve.readback" and e.get("args")]
    seen = len(noted())
    rids = [server.submit(p, max_new_tokens=10) for p in prompts]
    results = server.run()
    alone = make()
    for rid, p in zip(rids, prompts):
        want = alone.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=10)[0]
        assert np.array_equal(results[rid], want), rid
    if not kv_dtype:    # int8 keys move a near-tie; float pools are exact
        _each_is_the_argmax_of_one_pass(system_probs, sym, params, prompts,
                                        [results[rid] for rid in rids])
    notes = [a for a in noted()[seen:] if "ssm_rows" in a]
    assert notes and {a["ssm_rows"] for a in notes} <= {2, 4}
    assert sum(a["ssm_rows"] for a in notes) == 2 * len(prompts) * (10 - 1)
    # two E layers, three of eight experts a token, four held: a tick's held
    # pairs and the held experts they touched, beside the rows
    assert all({"moe_rows_held", "moe_rows_elsewhere", "moe_expert_visits"}
               <= set(a) for a in notes)
    assert all(a["moe_rows_held"] + a["moe_rows_elsewhere"]
               == 3 * a["ssm_rows"] for a in notes)
    assert max(a["moe_expert_visits"] for a in notes) <= 8
    assert not server._swap_armed
    with pytest.raises(MXNetError, match="'state' cache group.*rejected "
                                         "draft has already advanced"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=2)
    from mxnet_tpu.parallel.mesh import MeshConfig, build_mesh
    with pytest.raises(MXNetError, match="SelectiveSSM.*one device"):
        DecodePredictor(sym, nd, cache_len=64, ctx=mx.cpu(), paged=True,
                        page_tokens=4, prefill_chunk=8,
                        mesh=build_mesh(MeshConfig(model=2)))
