"""Per-slot page tables + the copy-on-write append rule.

The manager is the host brain of paged serving: it owns the
:class:`~mxnet_tpu.serve.allocator.PageAllocator`, the
:class:`~mxnet_tpu.serve.prefix_cache.PrefixCache` and one page-table row
per serving slot, and turns every upcoming device write into a plan the
decode layer executes:

* :meth:`admit` — admission gate: match the prompt against the prefix
  cache, reserve the request's whole worst-case page budget (tail pages +
  generation cap + speculation window + one fork), and map the matched
  shared pages.  Returns ``None`` when the pool cannot cover it — the
  serving loop keeps the request queued (backpressure) and retries after
  retirements free pages; LRU prefix-cache pages are evicted first.
* :meth:`ensure` — called before every append (chunk prefill, decode
  step, speculative verify) with the position range about to be written:
  allocates pages for unmapped table entries and **forks** any mapped
  page whose refcount exceeds 1 (copy-on-write — the first divergent
  write of a slot that shares a prefix).  Returns the (src, dst) page
  copies the caller must run on device BEFORE the step.
* :meth:`free_slot` — retirement: decref every mapped page (pages whose
  only other holder is the prefix cache survive for future prompts),
  release the leftover reservation.  Called the moment a request
  finishes — EOS mid-speculation-window included — so the pages are
  available to the very next admission attempt.

What a node keeps a PAGE, and not a position, lives and dies with the page
and needs no bookkeeping of its own: the planes of the decode layer's pools
share the page ids this manager hands out, so the row of an int8 pool's
scale plane and the row of a sparse-selection node's index of compressed
keys (``ops.attention.paged_append_index``: one compressed key a KV head a
page) are allocated when the page is mapped and free when it is freed.  So
is a latent node's whole pool (``ops.attention.LATENT_OP``: ONE plane, a
page a row, no head axis): it is a node of the group whose capacity it has,
the full nodes' own, and forks, prefix sharing, extract and install move
its rows with the page, so :func:`why_not` has nothing to refuse for it.  A
recycled page's stale rows are never read: the slot's length says which
windows are complete, and a complete window's row has been rewritten.

Tables are plain numpy; the decode layer ships them to the device as
DATA every step (a few hundred int32s), which is what keeps one traced
program serving every page mapping — the zero-retrace invariant.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .allocator import PageAllocator
from .prefix_cache import PrefixCache

__all__ = ["PagedKVManager", "GroupedKVManager", "CacheGroup", "StateRows",
           "WHY_NOT", "why_not"]

# what a cache group that does not hold every position of the context cannot
# carry, and why, by the group's kind: the text of every refusal
WHY_NOT = {
    "window": {
        "prefix": "a matched prefix's ring content is gone once the donor "
                  "has moved on",
        "speculation": "the ring has fewer positions beyond its window "
                       "than a verify step writes rows (k + 1): a rejected "
                       "draft's keys would evict keys a later query still "
                       "sees",
        "restore": "a ring keeps its last positions only, and its pages are "
                   "not a request's whole context to extract or install",
    },
    "state": {
        "prefix": "a matched prefix has pages and no recurrent state: the "
                  "state after the prefix would have to be computed again",
        "speculation": "a rejected draft has already advanced the recurrent "
                       "state, and a state has no positions to mask",
        "restore": "a state row is not in pages: swap, extract and install "
                   "move pages only",
    },
}


def why_not(what, groups, rows=0, slack=None):
    """``(kind, reason)`` of the first of ``groups`` that cannot carry
    ``what`` ("prefix" | "speculation" | "restore"); None where all can.  A
    window group carries speculation where its ring has ``rows`` (a verify
    step's k + 1) positions beyond its window (``slack(group)``): keys are
    masked by position and not by ring index, a verify's writes then evict
    nothing a later query sees, and a rejected row is overwritten by the
    next step's first write before any query reads it."""
    for g in groups:
        if g.kind == "window" and what == "speculation" \
                and slack is not None and 0 < rows <= slack(g):
            continue
        if g.kind in WHY_NOT:
            return g.kind, WHY_NOT[g.kind][what]
    return None


def _pages_for(tokens, page_tokens):
    """Pages needed to hold ``tokens`` tokens."""
    return -(-int(tokens) // int(page_tokens))


class PagedKVManager:
    """Host-side paged-KV bookkeeping for ``slots`` serving slots of
    ``capacity`` tokens each (``capacity % page_tokens == 0``; the table
    ring-mods over ``capacity // page_tokens`` entries, so generation past
    capacity recycles the slot's own oldest page in place — the paged
    counterpart of the dense ring's wrap)."""

    def __init__(self, slots, capacity, page_tokens, pool_pages=0,
                 prefix_cache=True, kind="full", name=None):
        self.kind, self.name = kind, name or kind
        self.page_tokens = int(page_tokens)
        if capacity % self.page_tokens:
            raise MXNetError(
                "paged capacity %d is not a multiple of page_tokens %d"
                % (capacity, self.page_tokens))
        self.capacity = int(capacity)
        self.slots = int(slots)
        self.pages_per_slot = self.capacity // self.page_tokens
        self.pool_pages = self.pool_sizing(slots, capacity, page_tokens,
                                           pool_pages)
        self.allocator = PageAllocator(self.pool_pages)
        self.prefix_cache = PrefixCache(self.page_tokens, self.allocator) \
            if prefix_cache else None
        # 0 = unmapped (the scratch page)
        self.tables = np.zeros((self.slots, self.pages_per_slot), np.int32)
        # bumped on every table mutation: the decode layer keys its
        # device-side copy of the tables on it, so steady-state decode
        # ticks (no page allocated, no fork) re-ship NOTHING
        self.version = 0
        self._reserve = np.zeros(self.slots, np.int64)
        # True where the slot allocated (or forked) the page itself: the
        # slot's appends land strictly PAST any published/matched
        # coverage of such a page, so in-place writes are safe even while
        # the prefix cache (or a matching slot) also references it —
        # only non-owned pages and wrap recycles fork
        self._own = np.zeros((self.slots, self.pages_per_slot), bool)

    @staticmethod
    def pool_sizing(slots, capacity, page_tokens, pool_pages=0):
        """Resolved pool page count for a serving batch: the explicit
        ``pool_pages`` when given, else the capacity-complete default
        (every slot can fill its table, plus the scratch page).  ONE
        rule shared with ``DecodePredictor.serving_avals`` so the
        AOT-prepared program signatures can never drift from the pools
        ``serve_open`` actually allocates."""
        if not pool_pages:
            return int(slots) * (int(capacity) // int(page_tokens)) + 1
        return int(pool_pages)

    # ------------------------------------------------------------------
    def _alloc(self, slot):
        """One page for ``slot``, spending its reservation first, then
        unreserved headroom, then evicting prefix-cache LRU pages."""
        if self._reserve[slot] > 0:
            self._reserve[slot] -= 1
            return self.allocator.alloc(from_reserve=True)
        if self.allocator.available() < 1 and self.prefix_cache is not None:
            self.prefix_cache.evict(1)
        return self.allocator.alloc()

    # admission is two-phase so the serving loop can gate BEFORE touching
    # any slot state:
    def gate(self, prompt, prompt_len, max_new, spec_k=0,
             budget_wrap_forks=True):
        """Reserve the worst-case page budget for a request; returns
        ``(matched_len, pages, reserve_n)`` or ``None`` on backpressure.
        ``pages`` are prefix-cache pages covering [0, matched_len),
        already INCREFED (pinned — the eviction a tight gate triggers
        must not free the very pages this request matched); pass them to
        :meth:`map_slot`, which takes ownership of the pin.  A failed
        gate drops the pins itself.

        ``budget_wrap_forks``: when ``max_new`` is a real cap (the
        serving loop), a generation that will wrap reserves one fork per
        matched shared page up front, so the recycle-time fork can never
        raise mid-decode.  Standalone prefill passes False — its
        generation length is unknown (``max_new`` = capacity, which
        would predict a wrap always) and the rare tight-pool wrap fork
        falls back to :meth:`ensure`'s eviction path instead.
        """
        prompt_len = int(prompt_len)
        matched, pages = (0, [])
        if self.prefix_cache is not None:
            matched, pages = self.prefix_cache.match(
                np.asarray(prompt).reshape(-1)[:prompt_len])
        for page in pages:
            self.allocator.incref(page)
        # pages still to allocate for the prompt itself...
        need_now = _pages_for(prompt_len, self.page_tokens) - len(pages)
        # ... plus one fork if the first tail write lands mid-page in a
        # shared page, plus the decode/speculation growth to capacity
        fork = 1 if matched % self.page_tokens else 0
        total = prompt_len + int(max_new) + int(spec_k) + 1
        if budget_wrap_forks and total > self.capacity and pages:
            fork += len(pages)
        growth = _pages_for(min(total, self.capacity), self.page_tokens) \
            - _pages_for(prompt_len, self.page_tokens)
        need = need_now + fork + growth
        if self.allocator.available() < need and self.prefix_cache is not None:
            self.prefix_cache.evict(need - self.allocator.available())
        if not self.allocator.reserve(need):
            for page in pages:
                self.allocator.decref(page)
            return None
        return matched, pages, need

    def map_slot(self, slot, pages, reserve_n):
        """Bind a gated request to ``slot``: map the matched prefix pages
        (the gate's pin becomes the slot's reference — shared until
        forked) and record the reservation."""
        row = self.tables[slot]
        assert not row.any(), "mapping into a non-empty slot %d" % slot
        for i, page in enumerate(pages):
            row[i] = page
            self._own[slot, i] = False
        self._reserve[slot] = int(reserve_n)
        self.version += 1

    # ------------------------------------------------------------------
    def gate_pages(self, need):
        """Reserve ``need`` pages for a restore (swap-in / migrated
        prefill) — the SAME admission gate a fresh prompt passes, minus
        the prefix-cache match (restored pages arrive with their
        content).  Evicts LRU prefix-cache pages first; False on
        backpressure (nothing changed)."""
        need = int(need)
        if self.allocator.available() < need and self.prefix_cache is not None:
            self.prefix_cache.evict(need - self.allocator.available())
        return self.allocator.reserve(need)

    def restore_slot(self, slot, valid, reserve_n):
        """Bind a restored request to ``slot``: allocate one fresh page
        per True entry of ``valid`` (a (pages_per_slot,) mask of the
        saved table row — ring positions matter for wrapped decodes),
        spending the :meth:`gate_pages` reservation.  All pages are
        slot-OWNED (refcount 1, private copies), so later appends never
        fork.  Returns the new table row (0 = unmapped)."""
        row = self.tables[slot]
        assert not row.any(), "restoring into a non-empty slot %d" % slot
        self._reserve[slot] = int(reserve_n)
        for i in np.flatnonzero(np.asarray(valid).reshape(-1)):
            self._reserve[slot] -= 1
            row[i] = self.allocator.alloc(from_reserve=True)
            self._own[slot, i] = True
        self.version += 1
        return row.copy()

    def slot_page_count(self, slot):
        """Mapped pages of ``slot`` (swap accounting)."""
        return int(np.count_nonzero(self.tables[slot]))

    def pages_for(self, total):
        """Pages a request of ``total`` positions holds at its longest."""
        return _pages_for(min(int(total), self.capacity), self.page_tokens)

    def ensure(self, slot, lo, hi):
        """Make positions [lo, hi) of ``slot`` writable.

        Allocates unmapped table entries; copy-on-write forks a mapped
        page when the write would collide with another holder's view: a
        shared prefix page about to receive the slot's first divergent
        write (not owned), or a wrap recycle of a page other slots still
        read.  A slot's OWN page appends in place even while shared — its
        writes land past every published coverage — and a wrap recycle
        whose only other holder is the prefix cache releases the (now
        dead) cache entries instead of forking.  Returns the list of
        ``(src_page, dst_page)`` copies the caller must execute on device
        before the append runs.
        """
        copies = []
        if hi <= lo:
            return copies
        row = self.tables[slot]
        m = self.pages_per_slot
        v0 = self.version
        for ti in range(int(lo) // self.page_tokens,
                        (int(hi) - 1) // self.page_tokens + 1):
            idx = ti % m
            page = int(row[idx])
            wrapped = ti >= m
            if page == 0:
                row[idx] = self._alloc(slot)
                self._own[slot, idx] = True
                self.version = v0 + 1
                continue
            if wrapped and self.prefix_cache is not None \
                    and self.allocator.shared(page):
                # wrap recycle: this slot overwrites the page in place,
                # so its cached prompt content is dead — drop the
                # cache's refs rather than fork for a corpse
                self.prefix_cache.release_page(page)
            if not self.allocator.shared(page):
                self._own[slot, idx] = True
                continue
            if self._own[slot, idx] and not wrapped:
                continue        # in-place append past published coverage
            fresh = self._alloc(slot)
            copies.append((page, fresh))
            self.allocator.decref(page)
            row[idx] = fresh
            self._own[slot, idx] = True
            self.allocator.forks += 1
            self.version = v0 + 1
        if copies:
            from .. import obs as _obs

            _obs.registry.counter(
                "mx_cow_forks",
                "copy-on-write page forks planned").inc(len(copies))
            _obs.instant("cow_fork", cat="serve",
                         args={"slot": int(slot), "copies": len(copies)})
        return copies

    def within_reserve(self, slot, lo, hi):
        """Whether :meth:`ensure` over [lo, hi) of ``slot`` can be met from
        pages the slot maps or has reserved: then the append takes nothing
        from the pool's headroom or the prefix cache.  The serving loop asks
        before it queues a step whose row may be dropped (an EOS not yet
        read).  A recycled page the prefix cache alone still holds counts as
        one to fork (it may not be: the answer errs toward False)."""
        if hi <= lo:
            return True
        row, m = self.tables[slot], self.pages_per_slot
        need = 0
        for ti in range(int(lo) // self.page_tokens,
                        (int(hi) - 1) // self.page_tokens + 1):
            idx = ti % m
            page = int(row[idx])
            if page == 0 or (self.allocator.shared(page) and
                             (ti >= m or not self._own[slot, idx])):
                need += 1
        return need <= self._reserve[slot]

    def publish(self, slot, prompt, prompt_len):
        """Insert a finished prefill's prompt pages into the prefix
        cache (no-op when the cache is disabled)."""
        if self.prefix_cache is None:
            return
        n = _pages_for(int(prompt_len), self.page_tokens)
        row = self.tables[slot]
        pages = [int(row[i]) for i in range(n)]
        if any(p == 0 for p in pages):
            return      # never published a hole (defensive)
        self.prefix_cache.insert(np.asarray(prompt).reshape(-1),
                                 prompt_len, pages)

    def free_slot(self, slot):
        """Retire ``slot`` NOW: drop its page refs (prefix-cache-held
        pages survive), zero its table row, release its reservation."""
        row = self.tables[slot]
        if row.any():
            self.version += 1
        for i in range(self.pages_per_slot):
            if row[i]:
                self.allocator.decref(int(row[i]))
                row[i] = 0
            self._own[slot, i] = False
        if self._reserve[slot]:
            self.allocator.unreserve(int(self._reserve[slot]))
            self._reserve[slot] = 0

    @property
    def groups(self):
        """The cache groups this manager keeps: itself."""
        return [self]

    # ------------------------------------------------------------------
    def stats(self):
        a = self.allocator
        out = {"pool_pages": self.pool_pages,
               "used_pages": a.used_pages,
               "peak_used_pages": a.peak_used,
               "free_pages": a.free_pages,
               "cow_forks": a.forks,
               "kv_hbm_utilization": a.peak_used / max(self.pool_pages - 1,
                                                       1)}
        if self.prefix_cache is not None:
            c = self.prefix_cache
            out.update({"prefix_cache_hit_rate": c.hit_rate,
                        "prefix_cache_hits": c.hits,
                        "prefix_cache_lookups": c.lookups,
                        "prefix_cache_pages": c.pages_held})
        return out


class StateRows:
    """The "state" cache group's bookkeeping: one fixed row a slot (slot
    ``s`` holds row ``s``), never short, nothing to page.  It answers what
    :class:`GroupedKVManager` asks of a group; its "table" is the row's
    index, constant, so the programs find a slot's row as they find its
    pages.  The allocator only counts: rows in use are what the gauges and
    :meth:`stats` show."""

    kind = "state"
    capacity = 0

    def __init__(self, slots, name=None):
        self.name = name or self.kind
        self.slots = self.pool_pages = int(slots)
        self.allocator = PageAllocator(self.slots + 1)
        self.tables = np.arange(self.slots, dtype=np.int32).reshape(-1, 1)
        self.version = 0
        self._held = {}

    def pages_for(self, total):
        return 1

    def map_slot(self, slot, pages, reserve_n):
        assert slot not in self._held, "mapping into a held row %d" % slot
        self._held[slot] = self.allocator.alloc(from_reserve=True)

    def ensure(self, slot, lo, hi):
        return []

    def within_reserve(self, slot, lo, hi):
        return True

    def free_slot(self, slot):
        if slot in self._held:
            self.allocator.decref(self._held.pop(slot))

    def slot_page_count(self, slot):
        return 0

    def stats(self):
        a = self.allocator
        return {"rows": self.slots, "used_rows": a.used_pages,
                "peak_used_rows": a.peak_used}


class CacheGroup:
    """The stateful nodes of one graph that share a cache layout: their
    ``kind`` ("full": every position of the context; "window": a ring of
    the last ``capacity`` positions; "state": one recurrent state a slot,
    ``capacity`` 0), what a slot holds of each (``capacity`` positions) and
    which nodes they are (``nodes``: indices into the graph's stateful
    nodes, in topological order).  ``name`` is
    what statistics and gauges are keyed by: the kind, with the capacity
    where two groups of a graph share a kind."""

    def __init__(self, kind, capacity, nodes, name=None):
        self.kind, self.capacity = kind, int(capacity)
        self.nodes = tuple(nodes)
        self.name = name or kind

    def __repr__(self):
        return "CacheGroup(%r, %d, %r)" % (self.name, self.capacity,
                                           self.nodes)


class GroupedKVManager:
    """The manager of a graph with more than one cache group: one
    :class:`PagedKVManager` a paged group, each with its own page count,
    its own per-slot tables and its own allocator, and :class:`StateRows`
    for a "state" group, gated together — a request is admitted when every
    group can reserve its worst case, and retires from all of them at once.

    A window group's table ring-mods over its few pages (``PagedKVManager``
    at the group's ``capacity``), so its nodes hold ``capacity`` positions
    a slot however long the request: a slot recycles its own oldest page
    in place.  What one ring cannot carry is refused by name and never
    read stale: there is no prefix cache (a matched prefix's ring content
    is gone once the donor has moved on), hence no copy-on-write fork; and
    a slot's pages cannot be extracted or restored (:meth:`gate_pages`,
    :meth:`restore_slot` raise).  A state group refuses the same three, for
    its own reasons (:data:`WHY_NOT`)."""

    prefix_cache = None

    def __init__(self, slots, groups, page_tokens, pool_pages=0):
        self.slots = int(slots)
        self.page_tokens = int(page_tokens)
        # an explicit pool size sizes the first group's pool (the groups
        # are ordered widest first); a ring group is always whole
        self.groups = [
            StateRows(slots, name=g.name) if g.kind == "state" else
            PagedKVManager(slots, g.capacity, page_tokens,
                           pool_pages=pool_pages if i == 0 else 0,
                           prefix_cache=False, kind=g.kind, name=g.name)
            for i, g in enumerate(groups)]

    @property
    def allocator(self):
        """The first (widest) group's allocator: the one that runs out."""
        return self.groups[0].allocator

    @property
    def version(self):
        return sum(g.version for g in self.groups)

    @property
    def pool_pages(self):
        return sum(g.pool_pages for g in self.groups
                   if g.kind != "state")

    def _refuse_restore(self):
        raise MXNetError(
            "restoring a swapped or migrated request is not supported on a "
            "graph with cache groups %s: a %r group cannot carry it: %s"
            % (([g.name for g in self.groups],)
               + why_not("restore", self.groups)))

    def gate(self, prompt, prompt_len, max_new, spec_k=0,
             budget_wrap_forks=True):
        """Reserve every group's worst case for a request, or none:
        ``(0, [], needs)`` with ``needs`` the pages reserved a group, or
        ``None`` on backpressure."""
        total = int(prompt_len) + int(max_new) + int(spec_k) + 1
        needs = [g.pages_for(total) for g in self.groups]
        for i, (g, n) in enumerate(zip(self.groups, needs)):
            if not g.allocator.reserve(n):
                for h, m in zip(self.groups[:i], needs[:i]):
                    h.allocator.unreserve(m)
                return None
        return 0, [], needs

    def map_slot(self, slot, pages, reserve_n):
        for g, n in zip(self.groups, reserve_n):
            g.map_slot(slot, [], n)

    def ensure(self, slot, lo, hi):
        for g in self.groups:
            copies = g.ensure(slot, lo, hi)
            assert not copies, "a page of an unshared group forked"
        return []

    def within_reserve(self, slot, lo, hi):
        return all(g.within_reserve(slot, lo, hi) for g in self.groups)

    def publish(self, slot, prompt, prompt_len):
        """Nothing to publish: there is no prefix cache."""

    def free_slot(self, slot):
        for g in self.groups:
            g.free_slot(slot)

    def gate_pages(self, need):
        self._refuse_restore()

    def restore_slot(self, slot, valid, reserve_n):
        self._refuse_restore()

    def slot_page_count(self, slot):
        return sum(g.slot_page_count(slot) for g in self.groups)

    def stats(self):
        out = self.groups[0].stats()
        out["pool_pages"] = self.pool_pages
        out["groups"] = {g.name: g.stats() for g in self.groups}
        out["prefix_cache_off"] = "%r group: %s" % why_not("prefix",
                                                           self.groups)
        return out
