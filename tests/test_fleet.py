"""Disaggregated serving fleet (mxnet_tpu.serve.fleet / serve.swap +
the DecodeServer tick/preemption machinery).

Covers the ISSUE-13 acceptance surface: token-level radix matching
inside final partial pages (and the chain-summary digest the router
scores), router affinity units (longest chain wins, load tie-break,
dead-host skip, sticky cold affinity), swap-out/readmit bit parity
(pages restored exactly, params untouched, token identity with a
never-preempted run), fleet-vs-single-host token identity across page
migration, migration/retirement refcounts draining to zero, and the
``/metrics.json`` chain-summary provider.
"""
import json
import urllib.request

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import config as _cfg
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.models import attention_lm
from mxnet_tpu.serve import PageAllocator, PrefixCache, chain_hash
from mxnet_tpu.serve.fleet import (FleetHost, PrefillWorker, Router,
                                   match_chains)

VOCAB, T, EMBED, HEADS = 17, 32, 8, 2


def _lm_and_params(seed=0, seq_len=T):
    sym = attention_lm.get_symbol(VOCAB, seq_len, num_layers=2,
                                  embed=EMBED, heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(data=(2, seq_len),
                                       softmax_label=(2, seq_len))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = rng.normal(0, 0.5, shape).astype(np.float32)
    return sym, params


def _mk_pred(sym, params, cache_len=T, **kw):
    kw.setdefault("page_tokens", 4)
    kw.setdefault("prefill_chunk", 4)
    return DecodePredictor(sym, params, cache_len=cache_len, paged=True,
                           **kw)


# ---------------------------------------------------------------------------
# satellite: token-level radix matching inside final partial pages
# ---------------------------------------------------------------------------
def test_radix_matching_inside_pages():
    """A prompt diverging MID-page still shares the page up to the
    divergence point — against both a stored partial entry and the
    final page of a deeper full chain — where the old exact-content
    rule matched nothing.  The router's hash-summary estimate is a
    lower bound of the host-side match."""
    alloc = PageAllocator(32)
    cache = PrefixCache(4, alloc)
    pages = [alloc.alloc() for _ in range(3)]
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]     # 2 full pages + [9, 10]
    cache.insert(prompt, 10, pages)

    # divergence inside the SECOND full page: match its first 2 tokens
    m, pg = cache.match([1, 2, 3, 4, 5, 6, 99, 98, 97])
    assert m == 6 and pg == pages[:2]
    assert cache.radix_hits == 1
    # divergence inside the stored partial: match 1 of its 2 tokens
    m, pg = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 77, 66])
    assert m == 9 and pg == pages[:3]
    assert cache.radix_hits == 2
    # exact partial-content prefix still matches in full
    m, pg = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11])
    assert m == 10 and pg == pages[:3]
    # full-hit rule: never match the entire prompt
    m, pg = cache.match([1, 2, 3, 4])
    assert m == 3 and pg == pages[:1]

    # the wire digest: full chains by hash, partials by (prefix, len,
    # hash) — and the router estimate never exceeds the real match
    summ = cache.summary()
    assert summ["page_tokens"] == 4
    assert chain_hash([1, 2, 3, 4]) in summ["full"]
    assert chain_hash([1, 2, 3, 4, 5, 6, 7, 8]) in summ["full"]
    assert {"prefix": chain_hash([1, 2, 3, 4, 5, 6, 7, 8]), "len": 2,
            "hash": chain_hash([9, 10])} in summ["partial"]
    for probe in ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11],
                  [1, 2, 3, 4, 5, 6, 99], [1, 2, 3, 4], [7, 7, 7]):
        est = match_chains(probe, summ)
        real, _ = cache.match(probe)
        assert est <= real, (probe, est, real)
    # aligned probes estimate exactly
    assert match_chains([1, 2, 3, 4, 5, 6, 7, 8, 42], summ) == 8
    assert match_chains([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], summ) == 10

    cache.clear()
    for p in pages:
        alloc.decref(p)
    assert alloc.used_pages == 0


# ---------------------------------------------------------------------------
# satellite: router affinity units (no jax — stub hosts)
# ---------------------------------------------------------------------------
class _StubServer:
    def __init__(self):
        self.submitted = []
        self._max_new = 8
        self._preempt_cb = None
        self._req = {}
        self._pred = type("P", (), {"_page_tokens": 4})()
        self.swap_outs = 0

    def submit(self, prompt, cap, priority=0):
        rid = len(self.submitted)
        self.submitted.append(np.asarray(prompt))
        self._req[rid] = {"submit": 0.0}
        return rid

    def _bind_host_metrics(self, name):
        pass


class _StubHost(FleetHost):
    def __init__(self, name, chains, load):
        super().__init__(name, _StubServer())
        self._chains = chains
        self._load = load

    def summary(self):
        return {"host": self.name, "slots": 4, "active": self._load,
                "queue_depth": 0, "free_pages": 64, "swap_outs": 0,
                "chains": self._chains}


def _chains_for(tokens, pt=4):
    """A summary holding every full-page chain of ``tokens``."""
    toks = np.asarray(tokens, np.int64)
    return {"page_tokens": pt,
            "full": [chain_hash(toks[:(i + 1) * pt])
                     for i in range(toks.size // pt)],
            "partial": []}


def test_router_affinity_units():
    """Longest cached chain wins; equal chains tie-break to the lower
    load; dead hosts are skipped; cold prompts bind sticky to the
    least-loaded host and stay bound."""
    tenant = np.arange(12) % VOCAB
    short_c = _chains_for(tenant[:4])      # 1 page cached
    long_c = _chains_for(tenant)           # 3 pages cached
    h_short = _StubHost("short", short_c, load=0)
    h_long = _StubHost("long", long_c, load=3)
    router = Router([h_short, h_long], policy="cache_aware")
    prompt = np.concatenate([tenant, [7, 7]])
    # longest chain wins even though that host is busier
    assert router.route({"rid": 0, "prompt": prompt, "cap": 4,
                         "prio": 0, "submit": 0.0}).name == "long"

    # equal chains: the LESS loaded host wins the tie
    h_a = _StubHost("a", long_c, load=5)
    h_b = _StubHost("b", long_c, load=1)
    router2 = Router([h_a, h_b], policy="cache_aware")
    assert router2.route({"rid": 0, "prompt": prompt, "cap": 4,
                          "prio": 0, "submit": 0.0}).name == "b"

    # dead hosts are skipped even when they hold the longest chain
    h_b.alive = False
    assert router2.route({"rid": 1, "prompt": prompt, "cap": 4,
                          "prio": 0, "submit": 0.0}).name == "a"

    # cold prompts: sticky least-loaded affinity — the first sighting
    # binds the chain, repeats follow it even after loads change
    h_c = _StubHost("c", {"page_tokens": 4, "full": [], "partial": []}, 2)
    h_d = _StubHost("d", {"page_tokens": 4, "full": [], "partial": []}, 0)
    router3 = Router([h_c, h_d], policy="cache_aware")
    cold = np.asarray([9, 8, 7, 6, 5])
    first = router3.route({"rid": 0, "prompt": cold, "cap": 4,
                           "prio": 0, "submit": 0.0}).name
    assert first == "d"                      # least loaded
    h_d._load = 9
    again = router3.route({"rid": 1, "prompt": cold, "cap": 4,
                           "prio": 0, "submit": 0.0}).name
    assert again == "d"                      # sticky

    # round-robin ignores chains entirely
    router4 = Router([_StubHost("x", long_c, 0),
                      _StubHost("y", long_c, 0)], policy="round_robin")
    names = [router4.route({"rid": i, "prompt": prompt, "cap": 4,
                            "prio": 0, "submit": 0.0}).name
             for i in range(4)]
    assert names == ["x", "y", "x", "y"]


# ---------------------------------------------------------------------------
# satellite: router HA — health-driven alive flips + in-flight requeue
# ---------------------------------------------------------------------------
class _TickStubServer(_StubServer):
    """A stub host whose serve loop the router can actually tick: every
    ``serve_tick`` finishes ONE queued request with deterministic tokens
    (the prompt length, repeated), so HA requeue semantics are checkable
    without jax."""

    def __init__(self):
        super().__init__()
        self._pending = []      # (hrid, prompt)
        self._done = {}
        self.completed = 0

    def submit(self, prompt, cap, priority=0):
        hrid = super().submit(prompt, cap, priority)
        self._pending.append((hrid, np.asarray(prompt)))
        return hrid

    @property
    def has_work(self):
        return bool(self._pending)

    def serve_tick(self):
        if self._pending:
            hrid, prompt = self._pending.pop(0)
            self._done[hrid] = np.full((2,), prompt.size, np.int32)
            self.completed += 1

    def serve_results(self, clear=True):
        out = dict(self._done)
        if clear:
            self._done.clear()
        return out


def test_router_health_flip_and_requeue():
    """The HA rung: a host whose health probe goes dark is flipped
    dead automatically, its in-flight requests (routed but unfinished)
    requeue at the router and complete on the survivor; a recovering
    probe flips the host back alive and it rejoins routing.  Every
    result is delivered exactly once."""
    health = {"a": True, "b": True}
    sa, sb = _TickStubServer(), _TickStubServer()
    ha = FleetHost("a", sa, health=lambda: health["a"])
    hb = FleetHost("b", sb, health=lambda: health["b"])
    router = Router([ha, hb], policy="round_robin")

    prompts = [np.arange(n) % VOCAB for n in (3, 4, 5, 6)]
    rids = [router.submit(p, 2) for p in prompts]
    # route everything but let no host finish yet: route() directly
    while router._queue:
        router.route(router._queue.popleft())
    assert len(sa.submitted) == 2 and len(sb.submitted) == 2

    # host a goes dark BEFORE finishing anything: the next tick's
    # health poll flips it and requeues its two in-flight requests
    health["a"] = False
    router.tick()
    assert ha.alive is False
    assert ("a", False) in router.host_flips
    # the requeued entries re-routed to b (the only live host) and the
    # drain completes every request on b alone
    res = router.drain()
    assert set(res) == set(rids)
    assert sa.completed == 0 and sb.completed == len(prompts)
    for rid, p in zip(rids, prompts):
        assert np.array_equal(res[rid], np.full((2,), p.size, np.int32))

    # recovery: the probe returns, the host flips back alive and
    # round-robin routing includes it again
    health["a"] = True
    assert router.poll_health() == [("a", True, 0)]
    assert ha.alive is True
    r2 = router.submit(np.arange(4), 2)
    router.drain()
    assert r2 in router.results
    # exactly one delivery per request — the dark host's stale copies
    # (requeued before it finished them) have no result mapping left,
    # so even if it completes them after revival nothing double-lands
    assert len(router.results) == len(prompts) + 1

    # a dark host's stale completion never double-delivers: route one
    # request, kill its owner before it finishes, let the dark host
    # "finish" it anyway — only the survivor's (requeued) copy delivers
    r3 = router.submit(np.arange(5), 2)
    while router._queue:
        router.route(router._queue.popleft())
    owner_name = next(k[0] for k, v in router._map.items() if v == r3)
    owner = sa if owner_name == "a" else sb
    pre = dict(router.results)
    health[owner_name] = False
    router.tick()           # flips the owner + requeues r3
    owner.serve_tick()      # the dark host finishes its stale copy
    res = router.drain()
    assert res[r3].tolist() == [5, 5]
    # the dark host's mapping was dropped with the requeue, so its
    # stale result has no consumer — result count grew by exactly one
    assert len(router.results) == len(pre) + 1
    health[owner_name] = True
    router.poll_health()
    assert all(h.alive for h in (ha, hb))

    # every host dark: tick fails LOUDLY with the queue intact (nothing
    # popped and lost); recovery then drains the held entry
    health["a"] = health["b"] = False
    r4 = router.submit(np.arange(3), 2)
    router.poll_health()
    with pytest.raises(Exception, match="no live decode hosts"):
        router.tick()
    assert len(router._queue) == 1          # the entry is HELD, not lost
    health["a"] = health["b"] = True
    res = router.drain()
    assert res[r4].tolist() == [3, 3]


def test_health_grace_hysteresis():
    """`health_grace` tolerates N consecutive probe failures beyond the
    first before flipping dark — one timed-out scrape of a loaded host
    must not requeue its whole batch; a success resets the count."""
    up = {"ok": False}
    host = FleetHost("g", _TickStubServer(), health=lambda: up["ok"],
                     health_grace=1)
    router = Router([host], policy="round_robin")
    assert router.poll_health() == [] and host.alive   # 1st miss: grace
    up["ok"] = True
    router.poll_health()                               # success resets
    up["ok"] = False
    assert router.poll_health() == [] and host.alive   # graced again
    flips = router.poll_health()                       # 2nd consecutive
    assert flips == [("g", False, 0)] and not host.alive


# ---------------------------------------------------------------------------
# swap-out / readmit bit parity (single host)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["float", "int8"])
def test_swap_out_readmit_bit_parity(kv_dtype):
    """A tight pool plus the fair-admission bound preempts the
    low-priority long decode; its readmission restores the pages
    bit-exactly (asserted inside the restore under _verify_restore),
    the final tokens equal the never-preempted reference, the model
    parameters are untouched, and every page drains at the end.  With
    int8 pools the pages carry the node's one scale plane beside the K
    data (the V pool's ``scale`` is None: the trees skip it)."""
    sym, params = _lm_and_params(seed=3)
    rng = np.random.RandomState(3)
    T2 = 16
    long_p = rng.randint(0, VOCAB, (6,))
    short_p = rng.randint(0, VOCAB, (5,))
    ref_pred = DecodePredictor(sym, params, cache_len=T2, kv_dtype=kv_dtype)
    ref_long = ref_pred.generate(long_p[None].astype(np.float32), 6,
                                 max_new_tokens=24, seed=0)[0]
    ref_short = ref_pred.generate(short_p[None].astype(np.float32), 5,
                                  max_new_tokens=4, seed=0)[0]

    with _cfg.overrides(MXNET_FLEET_DECODE_BOUND="4",
                        MXNET_FLEET_SWAP="1"):
        pred = _mk_pred(sym, params, cache_len=T2, pool_pages=6,
                        prefix_cache=False, kv_dtype=kv_dtype)
        srv = DecodeServer(pred, max_prefill=8, slots=2,
                           max_new_tokens=24)
        srv._verify_restore = True
        param_name = next(iter(pred._env))
        before = np.asarray(pred._env[param_name]).copy()
        r1 = srv.submit(long_p, 24, priority=-1)
        r2 = srv.submit(short_p, 4, priority=1)
        res = srv.run()
    assert srv.swap_outs >= 1 and srv.swap_ins == srv.swap_outs
    np.testing.assert_array_equal(res[r1], ref_long)
    np.testing.assert_array_equal(res[r2], ref_short)
    # params of the ring untouched by extract/install
    np.testing.assert_array_equal(np.asarray(pred._env[param_name]),
                                  before)
    # zero retraces across swap-out and readmit
    tc = pred.trace_counts
    assert tc["extract"] == 1 and tc["install"] == 1, tc
    assert tc["chunk"] == 1 and tc["decode"] <= 1 and tc["commit"] <= 1
    assert pred._manager.allocator.used_pages == 0


def test_swap_disabled_keeps_backpressure():
    """MXNET_FLEET_SWAP=0 restores the classic behavior: the waiter
    queues until retirements free pages — no preemption, same
    tokens."""
    sym, params = _lm_and_params(seed=3)
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, VOCAB, (6,))
    short_p = rng.randint(0, VOCAB, (5,))
    ref_pred = DecodePredictor(sym, params, cache_len=16)
    ref_long = ref_pred.generate(long_p[None].astype(np.float32), 6,
                                 max_new_tokens=12, seed=0)[0]
    ref_short = ref_pred.generate(short_p[None].astype(np.float32), 5,
                                  max_new_tokens=4, seed=0)[0]
    with _cfg.overrides(MXNET_FLEET_DECODE_BOUND="4",
                        MXNET_FLEET_SWAP="0"):
        pred = _mk_pred(sym, params, cache_len=16, pool_pages=6,
                        prefix_cache=False)
        srv = DecodeServer(pred, max_prefill=8, slots=2,
                           max_new_tokens=12)
        r1 = srv.submit(long_p, 12, priority=-1)
        r2 = srv.submit(short_p, 4, priority=1)
        res = srv.run()
    assert srv.swap_outs == 0
    np.testing.assert_array_equal(res[r1], ref_long)
    np.testing.assert_array_equal(res[r2], ref_short)


# ---------------------------------------------------------------------------
# fleet: token identity across migration + refcount drain
# ---------------------------------------------------------------------------
def test_fleet_token_identity_and_refcount_drain():
    """A 2-host + 1-prefill-worker fleet on a bursty shared-prefix
    trace: every request's tokens equal a per-host ``generate`` of the
    same prompt (across worker prefill, page migration and cache-aware
    routing), pages migrated > 0, each tenant stays on ONE host, and
    after the drain every pool's refcounts drain to zero once the
    prefix caches let go."""
    sym, params = _lm_and_params(seed=0)
    rng = np.random.RandomState(11)

    def mk():
        return _mk_pred(sym, params)

    hosts = [FleetHost("fh%d" % i,
                       DecodeServer(mk(), max_prefill=T, slots=2,
                                    max_new_tokens=6))
             for i in range(2)]
    worker = PrefillWorker(mk(), "fw0")
    router = Router(hosts, [worker], policy="cache_aware")
    prefixes = [rng.randint(0, VOCAB, (12,)) for _ in range(2)]
    prompts, rids, tenants = [], [], []
    for wave in range(2):
        for tnt in range(2):
            for _ in range(2):
                p = np.concatenate([prefixes[tnt],
                                    rng.randint(0, VOCAB, (3,))])
                prompts.append(p)
                rids.append(router.submit(p, 6))
                tenants.append(tnt)
        for _ in range(8):
            router.tick()
    res = router.drain()

    ref = mk()
    for rid, p in zip(rids, prompts):
        expect = ref.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=6, seed=0)[0]
        np.testing.assert_array_equal(res[rid], expect)

    stats = router.stats()
    assert stats["worker_prefills"] >= 1
    assert sum(stats["migrated_pages_by_host"].values()) >= 1
    assert stats["router_cache_hit_rate"] > 0
    # per-tenant affinity under cache_aware
    by_tenant = {}
    for (rid, host, matched, path), tnt in zip(router.decisions, tenants):
        by_tenant.setdefault(tnt, set()).add(host)
    assert all(len(hs) == 1 for hs in by_tenant.values()), by_tenant
    # zero retraces across admission and migration, on every pool
    for pred in [h.server._pred for h in hosts] + [worker._pred]:
        tc = pred.trace_counts
        assert all(tc[prog] <= 1 for prog in
                   ("chunk", "decode", "fork", "commit", "extract",
                    "install")), tc
    # migration refcounts drain to zero: the only refs left after the
    # drain belong to the prefix caches; releasing them empties every
    # pool (worker included)
    for pred in [h.server._pred for h in hosts] + [worker._pred]:
        mgr = pred._manager
        if mgr.prefix_cache is not None:
            mgr.prefix_cache.clear()
        assert mgr.allocator.used_pages == 0, mgr.stats()


# ---------------------------------------------------------------------------
# /metrics.json chain-summary provider
# ---------------------------------------------------------------------------
def test_metrics_json_serves_chain_summary():
    """The metrics sidecar's /metrics.json grows the mx_serve_summary
    section (chain digest + free-page/queue-depth gauges) a remote
    router polls — same payload the in-process router reads."""
    from mxnet_tpu.obs import MetricsServer

    sym, params = _lm_and_params(seed=0)
    pred = _mk_pred(sym, params)
    srv = DecodeServer(pred, max_prefill=T, slots=2, max_new_tokens=4)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, (9,))
    srv.submit(prompt)
    srv.run()

    ms = MetricsServer(port=0).start()
    try:
        ms.add_json("mx_serve_summary", srv.serve_summary)
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics.json" % ms.port).read()
        payload = json.loads(body)
        summ = payload["mx_serve_summary"]
        assert summ["host"] == srv._host
        assert summ["free_pages"] > 0 and summ["queue_depth"] == 0
        chains = summ["chains"]
        assert chains["page_tokens"] == 4
        # the served digest scores exactly like the live cache
        est = match_chains(np.concatenate([prompt, [1, 2]]), chains)
        assert est >= (prompt.size // 4) * 4
        # the registry families ride alongside (per-host labels)
        assert "mx_fleet_free_pages" in payload
    finally:
        ms.stop()
