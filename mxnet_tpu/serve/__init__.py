"""KV-memory management for the serving path — pages, refcounts, prefixes.

The dense-ring serving plan (PR 4/6) allocates one ``(cache_len,)`` -wide
ring buffer per slot, so HBM scales with ``slots x max-context`` and
mixed-length traffic strands most of it — the fragmentation PagedAttention
identified (Kwon et al., *Efficient Memory Management for Large Language
Model Serving with PagedAttention*, SOSP 2023).  This package is the host
half of the paged plan (``MXNET_KV_PAGED``; the device kernels live in
``ops.attention.paged_gather/paged_append/paged_copy``):

* :class:`~mxnet_tpu.serve.allocator.PageAllocator` — a refcounted free
  list over one GLOBAL page-id space (page 0 reserved as the scratch
  page), with admission **reservations** so a request admitted into the
  batch can always finish: exhaustion surfaces as queue backpressure, not
  a mid-decode crash.
* :class:`~mxnet_tpu.serve.prefix_cache.PrefixCache` — copy-on-write
  prefix sharing keyed on token-hash chains (RadixAttention's insight,
  Zheng et al. 2024, at page granularity): matching prompts map their
  leading pages to shared refcounted pages, prefill computes only the
  tail, and the million-users-one-system-prompt case prefills the prompt
  once.  Entries are evictable LRU when the pool runs dry.
* :class:`~mxnet_tpu.serve.manager.PagedKVManager` — per-slot page
  tables (host numpy, passed to the traced programs as DATA — the
  zero-retrace invariant), the append-path ownership rule (a write into a
  page with refcount > 1 forks it first — copy-on-write), and
  slot-lifetime bookkeeping (map/ensure/free, utilization stats).

``decode.DecodePredictor(paged=True)`` and ``decode.DecodeServer`` drive
all three; nothing here touches jax — the manager only *decides* and the
decode layer executes the resulting fork/append plans on device.

Above the single host sit the fleet layers (docs/serving_fleet.md):

* :mod:`~mxnet_tpu.serve.swap` — restorable page records: preemption
  swap-out to host RAM and the page-migration wire format of
  prefill/decode disaggregation (one extract + one install program,
  page ids as data — zero retraces);
* :mod:`~mxnet_tpu.serve.fleet` — the front-end :class:`Router` over N
  per-host ``DecodeServer``\\ s: cache-aware routing on prefix-chain
  summaries, dedicated :class:`PrefillWorker`\\ s shipping committed
  pages DistServe-style, and preemption rehoming.
"""
from __future__ import annotations

from .allocator import PageAllocator
from .prefix_cache import PrefixCache, chain_hash
from .manager import (CacheGroup, GroupedKVManager, PagedKVManager,
                      StateRows)
from .swap import SwapStore, SwappedRequest

__all__ = ["PageAllocator", "PrefixCache", "PagedKVManager",
           "GroupedKVManager", "CacheGroup", "StateRows",
           "SwapStore", "SwappedRequest", "chain_hash"]


def __getattr__(name):
    # fleet imports obs (and through it config/metrics); keep the base
    # package import light by resolving the router layer lazily
    if name in ("FleetHost", "PrefillWorker", "Router", "match_chains"):
        from . import fleet

        return getattr(fleet, name)
    raise AttributeError(name)
