"""KV-cached autoregressive decoding — prefill/decode split + batched serving.

The ``Predictor`` runs a whole forward per call, so generating token T
re-executes the full prefix: O(T^2) work per sequence.  This module is the
TPU-era serving path (Pope et al., "Efficiently Scaling Transformer
Inference"): :class:`DecodePredictor` splits an ``attention_lm``-style
symbol into TWO jitted programs —

* **prefill** — one full causal forward over the prompt that additionally
  captures every ``dot_product_attention`` node's K/V into a preallocated
  ring-buffer cache (``ops.attention.cache_append`` layout), and samples
  the first output token;
* **decode step** — one token per call: embed the last sampled token,
  append its K/V at the next ring slot (``jax.lax.dynamic_update_slice``),
  attend the single query position against the cache with a length-masked
  softmax (``ops.attention.sdpa_decode``), sample the next token
  (``ops.sample.sample_tokens``).  The program carries ``(params, state,
  rng)`` with the state (caches + per-sequence lengths + last token)
  DONATED (``MXNET_DECODE_DONATE``), so the token loop neither re-uploads
  parameters, re-traces, nor allocates: O(1) work per token in the prefix
  length.

Under a mesh, parameters shard by the Megatron column/row plan
(``parallel.tp_rules.plan_tensor_parallel``) and the caches' E (head) dim
shards on 'model' (``parallel.tp_rules.kv_cache_pspec``): each model shard
holds and scores only its own head group's cache slice — the inference-side
counterpart of the training-side ring×TP composition.

:class:`DecodeServer` is the batched serving loop: ``MXNET_DECODE_SLOTS``
in-flight sequence slots at a FIXED batch shape (Orca-style continuous
batching) — new requests prefill into a free slot between decode steps,
sequences retire on EOS/max-len, and the freed slot refills from the
request queue, all without retracing anything.

Decode is bandwidth-bound on the cache, so this module attacks both
factors of ``bytes/token = passes/token x cache bytes``:

* **Speculative decoding** (Leviathan et al. 2023): a proposer drafts k
  tokens — a small draft model through a second ``DecodePredictor``
  (:class:`DraftProposer`) or the model-free n-gram self-speculation
  lookup (:class:`NGramProposer`) — and ONE batched verify pass
  (``ops.attention.sdpa_verify``, fixed shape in k) scores all k+1
  positions against the caches; ``ops.sample.speculative_accept``
  commits the accepted prefix plus one resampled token, preserving the
  target distribution exactly.  Rejection rolls back ``lens`` only (the
  length mask hides the dead cache entries; the next append overwrites
  them), and speculation gates off near the ring-wrap boundary (host-side
  length bookkeeping, no extra device sync) so there is exactly ONE
  draft program and ONE verify program — never a retrace.
* **Quantized KV caches** (``MXNET_KV_DTYPE``: int8 / fp8 with
  per-(token, head) scales, ``ops.attention.QuantKV``): ``cache_append``
  quantizes on the way in, ``sdpa_decode``/``sdpa_verify`` dequantize per
  head on the way out, and the cache bytes every step streams drop 2-4x.
  Scale buffers shard like the caches (``tp_rules.kv_cache_pspec`` — an
  H-split is the same head-group split).

**Paged KV caches** (``MXNET_KV_PAGED`` / ``DecodePredictor(paged=True)``)
replace the dense per-slot ring buffers with fixed-size pages in ONE shared
device pool per attention node (PagedAttention, Kwon et al. SOSP 2023):
per-slot page tables are traced *data* (``ops.attention.paged_gather`` /
``paged_append`` index through them), so HBM scales with live tokens
instead of slots x max-context and admissions / copy-on-write forks /
retirements reuse the same compiled programs — the zero-retrace invariant
extends to the memory manager.  The host half (refcounted allocator with
admission reservations, the token-hash-chain prefix cache that lets
matching prompts share their leading pages and prefill only the tail, the
fork-before-divergent-write rule) lives in ``mxnet_tpu.serve``.  Prompts
admit in fixed-size chunks (``MXNET_PREFILL_CHUNK``) interleaved with
decode steps, so a long prompt never stalls the serving batch.

The symbol contract (checked at trace time, documented in
docs/inference.md): decoder-only graphs built from position-independent ops
plus the stateful ones for sequence mixing: ``dot_product_attention`` (keys
and values a position; with ``sparse_topk`` an index of compressed keys
beside them, a row a page) and the recurrent ops :func:`state_ops` lists,
each a statement of what it keeps a sequence and how a chunk and a step
update it (``SelectiveSSM``, ``ops.ssm``: a conv tail and a state;
``LightningAttention``, ``ops.linattn``: one matrix state a head;
``KimiDeltaAttention``, ``ops.kda``, and ``GatedDeltaNet``, ``ops.gdn``: a
conv tail and a matrix state a head, square or Dk x Dv;
no positions in any but those the op's own rotation reads).  Positions enter
either
as a learned positional table added via a ``broadcast_*`` op against a
``(1, S, E)`` variable, or inside the attention node (``rotary_dim``: q and
k are rotated at the positions the walk passes, and the keys are cached
rotated) — ``models.attention_lm``, ``models.decoder_lm`` and the benchmark
LMs qualify.

**Cache groups.**  Each stateful node has a cache layout read off it at
bind time (:class:`CacheLayout`), of one of four kinds: a "full" attention
node holds ``cache_len`` positions a slot, a paged "window" node a ring of
``window + prefill_chunk`` positions rounded up to a page, a "state" node
(a recurrent op) one fixed row a slot and no positions at all, and a
"latent" node (``ops.attention.LATENT_OP``) ``cache_len`` positions as ONE
plane with no head axis, ``kv_lora_rank + qk_rope_head_dim`` values a
position, a page's positions one after the other in rows of whole lanes
(``ops.pallas_decode.latent_plane_shape``), in the full group's pages and
tables (pages
are pages: fork, copy-on-write, extract and install move the plane as they
move keys and values).  Nodes of one kind and capacity form a group
(``serve.CacheGroup``): the paged kinds with their own page count, page tables and allocator, the state group with
one row a slot whose "table" is the row's index.  A graph of one kind builds
one group and the tables, programs and page counts it built before groups
existed.  A state row has no scratch page: the decode step masks its write
by ``active``, and a chunk at ``pos0 == 0`` starts from zero state, so a
slot is reused with no clearing program.
"""
from __future__ import annotations

import functools
import time
from collections import Counter, deque
from typing import NamedTuple

import numpy as np

from .base import MXNetError
from . import context as ctx_mod
from . import obs as _obs
from .registry import OpContext, producers_of

__all__ = ["DecodePredictor", "DecodeServer", "DecodeState",
           "NGramProposer", "DraftProposer"]

# MXNET_KV_DTYPE spellings -> canonical jnp dtype names (resolved lazily so
# the module imports without jax)
_KV_DTYPES = {
    "int8": "int8", "s8": "int8",
    "float8_e4m3fn": "float8_e4m3fn", "f8e4m3": "float8_e4m3fn",
    "f8e4m3fn": "float8_e4m3fn",
    "float8_e5m2": "float8_e5m2", "f8e5m2": "float8_e5m2",
}
# spellings of "the pools in the serving type": what an empty string says,
# by a name a traffic file can state
_KV_UNQUANTIZED = ("bfloat16", "bf16")

def _pad_window(tokens, width):
    """``tokens`` left-aligned in a zero-padded (1, width) float32 window —
    the ONE place admission padding and prefill-chunk windows are derived
    (the dense admission path used to rebuild this per admit)."""
    toks = np.asarray(tokens).reshape(-1)
    out = np.zeros((1, int(width)), np.float32)
    out[0, :toks.size] = toks
    return out


# broadcast ops through which a (1, S, E) position table may meet the
# (B, t, E) activation stream; the decode walk gathers the table rows for
# the CURRENT positions before applying the op
_POSITION_BROADCAST_OPS = {
    "broadcast_add", "broadcast_plus", "broadcast_sub", "broadcast_minus",
    "broadcast_mul",
}


def _when(on, fn):
    """``fn()`` where the scalar ``on`` holds, else zeros of its types: one
    ``lax.cond``, so what ``fn`` computes costs nothing where it is not
    read (a chunk that does not end its prompt)."""
    import jax
    import jax.numpy as jnp

    zeros = lambda: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(fn))
    return jax.lax.cond(on, fn, zeros)


def _keep_tok(tok):
    """A step's tokens, copied (a function with a name, so that its
    program is module ``jit__keep_tok`` on a device trace)."""
    return tok + 0


def _split_key(key):
    """A session's key split in two, as ONE program with a name (module
    ``jit__split_key``): a sampling server runs it every tick, and a device
    trace's time is joined to programs by their names."""
    import jax

    return tuple(jax.random.split(key))


def _same_avals(a, b):
    import jax.tree_util as jtu

    what = lambda x: (x.shape, x.dtype, getattr(x, "weak_type", False),
                      getattr(x, "sharding", None))
    return jtu.tree_structure(a) == jtu.tree_structure(b) and all(
        what(x) == what(y)
        for x, y in zip(jtu.tree_leaves(a), jtu.tree_leaves(b)))


def _loaded(lowered):
    """Whether this process has already compiled ``lowered`` (a
    ``jax.stages.Lowered``).  jax caches a lowering by the traced
    function and its arguments' shardings and keeps the executable on it:
    lowering a dispatched program's avals again meets that lowering, and
    ``.compile()`` hands back the very executable the dispatch calls.
    False where jax does not show it."""
    return getattr(getattr(lowered, "_lowering", None),
                   "_executable", None) is not None


def _per_group(items):
    """What the programs take where a graph has cache groups: the one
    item of a graph with one group, else a tuple, one a group."""
    items = tuple(items)
    return items if len(items) > 1 else items[0]


def _node_pools(leaves, make):
    """A node's page pools from its probed avals ``leaves``: ``make(aval,
    is_scale=False, is_index=False)`` builds one plane.  An attention node
    brings its K and V avals: quantized pools keep one scale plane between
    them, beside the K data (``ops.attention.QuantKV``); a node with sparse
    selection brings a third aval, its index: one more plane, a row a page.
    A latent node brings one aval, its one plane."""
    from .ops.attention import QuantKV

    if len(leaves) == 1:
        return (make(leaves[0]),)
    kc, vc, *index = leaves
    more = tuple(make(a, is_index=True) for a in index)
    if isinstance(kc, QuantKV):
        return (QuantKV(make(kc.data), make(kc.scale, is_scale=True)),
                QuantKV(make(vc.data), None)) + more
    return (make(kc), make(vc)) + more


class StateOp(NamedTuple):
    """What a recurrent op keeps a slot and how the walk drives it, stated
    once so that :meth:`DecodePredictor._run` names no op: ``mix(attrs,
    *inputs, state=, pos0=, nvalid=, active=)`` -> ``(out, state, rows)`` is
    the op's one mathematics in its three forms (a sequence from zero:
    ``state`` None; a chunk of a carried state: ``nvalid``; one token a row
    in place: ``active``), ``state`` a tuple of (rows, ...) leaves whose
    shapes the shape probe reads off a (1, 1) sequence, and ``counts`` the
    name under which the rows a decode step advanced are counted (a leaf of
    :class:`DecodeState`, an argument of the ``serve.readback`` span).
    ``advanced`` and ``held`` say, in the words of the serving loop's two
    metrics of the op (``mx_<stem>_rows_total`` and ``mx_<stem>_state_bytes``,
    ``<stem>`` = ``counts`` without its ``_rows``), what a row of it is and
    what the state group holds of it."""

    mix: object
    counts: str
    advanced: str
    held: str

    def metric(self, what):
        return "mx_%s_%s" % (self.counts[:-len("_rows")], what)


def state_ops():
    """``{op name: StateOp}``: the recurrent ops the decode walk carries a
    state for, each in a "state" cache layout."""
    from .ops import gdn as _gdn, kda as _kda, linattn as _linattn, \
        ssm as _ssm

    rows = "(slot, %s node) rows whose %s state a decode step advanced " \
        "(idle and mid-prefill slots left out)"
    return {
        _ssm.OP_NAME: StateOp(
            _ssm.mix, "ssm_rows", rows % (_ssm.OP_NAME, "recurrent"),
            "bytes of the state cache group: every slot's conv tails and "
            "recurrent states"),
        _linattn.OP_NAME: StateOp(
            _linattn.mix, "linattn_rows",
            rows % (_linattn.OP_NAME, "matrix"),
            "bytes of the state cache group's LightningAttention rows: "
            "every slot's (H, D, D) float32 states"),
        _kda.OP_NAME: StateOp(
            _kda.mix, "kda_rows", rows % (_kda.OP_NAME, "matrix"),
            "bytes of the state cache group's KimiDeltaAttention rows: "
            "every slot's convolution tails and (H, D, D) float32 states"),
        _gdn.OP_NAME: StateOp(
            _gdn.mix, "gdn_rows", rows % (_gdn.OP_NAME, "matrix"),
            "bytes of the state cache group's GatedDeltaNet rows: every "
            "slot's convolution tails and (H, Dk, Dv) float32 states")}


class CacheLayout(NamedTuple):
    """What one stateful node keeps a slot, read off the node at bind
    time: ``kind`` ("full" | "window" | "state" | "latent"), its KV heads,
    and the positions it holds (``capacity``; a "state" node has neither: it
    keeps one row, whatever the sequence's length; a "latent" node has no
    heads: one plane, a row a position); the key and value widths are
    the pools' trailing dims, for a state node the conv tail's and the
    state's own (``DecodePredictor.cache_layouts`` adds them once the shapes
    are probed)."""

    kind: str
    kv_heads: int
    capacity: int
    key_width: object = None
    value_width: object = None
    index: int = 0      # positions a row of the node's index of compressed
                        # keys stands for (sparse selection); 0 = no index


class DecodeState(NamedTuple):
    """The donated per-step serving state (a jax pytree)."""

    caches: tuple       # one entry per stateful node, in graph order.  An
                        # attention node: (k, v), (B, C, E) arrays, or
                        # ops.attention.QuantKV (data + scales) under a
                        # quantized MXNET_KV_DTYPE; a paged node with
                        # sparse selection adds its index (P, H_kv * D).  A
                        # recurrent op's node (state_ops): its state's
                        # leaves, each (B, ...)
    lens: object        # (B,) int32 — tokens appended to each cache so far
    tok: object         # (B, 1) int32 — last sampled token, not yet appended
    moe: object = None  # int32 [rows held, rows elsewhere, held experts
                        # visited], summed over the gated MoE layers of the
                        # paged step that made this state; None (no leaf)
                        # for a graph without them and on the way in
    ssm: object = None  # int32: (slot, SelectiveSSM node) rows whose state
                        # the paged step advanced; None as ``moe`` is
    counts: object = None   # {name: int32} of what else the paged step's
                            # nodes counted (linattn_rows, kda_rows,
                            # gdn_rows, sparse_blocks_chosen / _live); None
                            # as ``moe`` is
    draft: object = None    # (B, 1) int32: the token the graph's own
                            # prediction block drafted for the position after
                            # ``tok``; None (no leaf) unless the state is a
                            # self-drafting server's
    draft_probs: object = None  # (B, V) float32: the distribution ``draft``
                                # was drawn from; None under greedy sampling


class DecodePredictor:
    """Incremental-decode executor for a trained attention LM.

    Parameters
    ----------
    symbol : Symbol or str
        The network — a Symbol, a JSON string, or a ``*-symbol.json`` path
        (same forms as :class:`~mxnet_tpu.predictor.Predictor`).
    params : dict, str, or bytes
        Trained parameters (``arg:``/``aux:`` prefixes optional).
    cache_len : int
        Ring-buffer KV-cache length C per attention node.  Generation past
        C tokens wraps: the cache keeps the latest C keys/values
        (sliding-window attention).
    ctx : Context, optional
        Single-device placement; defaults to cpu.  Ignored when ``mesh``
        is given.
    mesh : jax.sharding.Mesh, optional
        Shard parameters by the Megatron plan and KV caches on the
        'model' (head) / 'data' (batch) axes.
    temperature, top_k
        Sampling knobs baked into the step program (0 = greedy).
    data_name : str
        The token-input variable; other free inputs (labels) are fed zeros.
    kv_dtype : str, optional
        KV-cache storage dtype: 'int8', 'float8_e4m3fn' or 'float8_e5m2'
        (per-(token, head) scales, quantize-on-append / dequantize-in-
        kernel).  ``None`` (default) reads ``MXNET_KV_DTYPE``; empty
        string, or 'bfloat16' by name = full-precision caches: the pools
        in the type the graph computes in.
    paged : bool, optional
        Store the caches as fixed-size pages in one shared pool per
        attention node with per-slot page tables (traced data — see the
        module docstring).  ``None`` (default) reads ``MXNET_KV_PAGED``.
    page_tokens, pool_pages, prefill_chunk : int, optional
        Paged-mode knobs; default to ``MXNET_KV_PAGE_TOKENS`` /
        ``MXNET_KV_POOL_PAGES`` / ``MXNET_PREFILL_CHUNK``.
        ``cache_len`` must divide by ``page_tokens`` (the table ring-mods
        over ``cache_len // page_tokens`` entries, so paged results stay
        in parity with a dense ring of the same capacity: to the bit for
        a view of one block, within float32 tolerance where longer views
        are attended block by block, see ``ops.attention.paged_attend``).
    prefix_cache : bool
        Arm copy-on-write prefix sharing in paged mode (default on).
    """

    @_obs.phased("build.predictor")
    def __init__(self, symbol, params, cache_len, ctx=None, mesh=None,
                 temperature=0.0, top_k=0, data_name="data", kv_dtype=None,
                 paged=None, page_tokens=None, pool_pages=None,
                 prefill_chunk=None, prefix_cache=True):
        import jax
        import jax.numpy as jnp

        from . import symbol as sym_mod
        from .predictor import _as_param_dicts

        if isinstance(symbol, str):
            symbol = sym_mod.load_json(symbol) \
                if symbol.lstrip().startswith("{") else sym_mod.load(symbol)
        self._symbol = symbol
        self._cache_len = int(cache_len)
        if self._cache_len <= 0:
            raise MXNetError("cache_len must be positive")
        self._ctx = ctx if ctx is not None else ctx_mod.cpu()
        self._mesh = mesh
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._data_name = data_name

        from . import config as _config

        if kv_dtype is None:
            kv_dtype = _config.get("MXNET_KV_DTYPE")
        kv_dtype = (kv_dtype or "").strip().lower()
        if kv_dtype in _KV_UNQUANTIZED:
            kv_dtype = ""
        if kv_dtype:
            canonical = _KV_DTYPES.get(kv_dtype)
            if canonical is None:
                raise MXNetError(
                    "unsupported MXNET_KV_DTYPE %r (supported: %s)"
                    % (kv_dtype, sorted(set(_KV_DTYPES.values()))))
            self._kv_dtype = jnp.dtype(canonical)
        else:
            self._kv_dtype = None

        # an explicit paged= argument outranks the ambient env var (a
        # deliberately dense predictor under MXNET_KV_PAGED=1 — e.g. a
        # draft model — must not read as a dropped-plumbing regression)
        self._paged_from_env = paged is None
        if paged is None:
            paged = _config.get("MXNET_KV_PAGED")
        self._paged = bool(paged)
        self._prefix_cache_on = bool(prefix_cache)
        self._page_tokens = int(page_tokens) if page_tokens \
            else int(_config.get("MXNET_KV_PAGE_TOKENS"))
        self._pool_pages = int(pool_pages) if pool_pages \
            else int(_config.get("MXNET_KV_POOL_PAGES"))
        self._prefill_chunk = int(prefill_chunk) if prefill_chunk \
            else int(_config.get("MXNET_PREFILL_CHUNK"))
        if self._paged:
            if self._page_tokens <= 0:
                raise MXNetError("page_tokens must be positive")
            if self._cache_len % self._page_tokens:
                raise MXNetError(
                    "cache_len %d is not a multiple of page_tokens %d — "
                    "paged capacity must tile into whole pages"
                    % (self._cache_len, self._page_tokens))

        arg_params, aux_params = _as_param_dicts(params)
        free = [n for n in symbol.list_arguments() if n not in arg_params]
        if data_name not in free:
            raise MXNetError("%r is not a free input of the symbol (free "
                             "inputs: %s)" % (data_name, free))
        # the stateful nodes, in graph order: DecodeState.caches, the
        # layouts and the groups are indexed by position in this list
        from .ops.attention import LATENT_OP

        self._state_ops = state_ops()
        self._cache_nodes = [n for n in symbol._topo()
                             if not n.is_variable and (
                                 n.op.name in ("dot_product_attention",
                                               LATENT_OP)
                                 or n.op.name in self._state_ops)]
        self._attn_nodes = [n for n in self._cache_nodes
                            if n.op.name == "dot_product_attention"]
        latent = [n for n in self._cache_nodes if n.op.name == LATENT_OP]
        # a multi-token-prediction block: the nodes that read the variable
        # MTP_DATA (the token AFTER each position) run after the main head
        # has said what that token is (:meth:`_run`'s ``between``)
        self._late = self._nodes_after(self.MTP_DATA) \
            if self.MTP_DATA in free and len(symbol._outputs) > 1 else None
        self._heads = self._head_regions()
        if not self._attn_nodes and not latent:
            raise MXNetError("symbol has no dot_product_attention node; "
                             "nothing to cache — use Predictor")
        if latent and self._kv_dtype is not None:
            raise MXNetError(
                "a graph with %s nodes keeps its latent plane in the serving "
                "type (kv_dtype %r asked): a quantized pool's scales are a "
                "row a (position, KV head) (ops.attention.QuantKV), and a "
                "latent row has no heads" % (LATENT_OP, str(self._kv_dtype)))
        if latent and mesh is not None:
            raise MXNetError(
                "a graph with %s nodes is served on one device: "
                "parallel.tp_rules.kv_pool_pspec shards a pool's trailing "
                "dimension by head groups, and a latent row has none"
                % LATENT_OP)
        if len(self._cache_nodes) > len(self._attn_nodes) + len(latent):
            recurrent = "/".join(sorted({
                n.op.name for n in self._cache_nodes
                if n.op.name in self._state_ops}))
            if not self._paged:
                raise MXNetError(
                    "a graph with %s nodes is served paged only "
                    "(paged=True): the dense ring's prefill pads a prompt "
                    "to its window and its verify step rolls lengths back, "
                    "and a recurrent state can take neither" % recurrent)
            if mesh is not None:
                raise MXNetError(
                    "a graph with %s nodes is served on one device: "
                    "parallel.tp_rules has no plan for the mixer's heads "
                    "and groups" % recurrent)
        from .ops.attention import sparse_spec

        if any(sparse_spec(n.parsed_attrs()) for n in self._attn_nodes) \
                and (not self._paged or mesh is not None):
            raise MXNetError(
                "a graph whose attention selects its blocks (sparse_topk) "
                "is served paged and on one device: the index of compressed "
                "keys is a row a page, and the list of chosen blocks has no "
                "plan under a mesh")
        # per-attention-node head dims, recorded at trace time by _run
        # (num_heads / num_kv_heads / q_dim / kv_dim) — the grouped-layout
        # source of truth for cache meta and CacheBytesPass
        self._attn_dims = []
        # grouped-query config (any node with num_kv_heads < num_heads):
        # the kv-head count gating the cache/pool trailing-dim shard
        grouped = []
        for n in self._attn_nodes:
            a = n.parsed_attrs()
            kvh = a.get("num_kv_heads", 0) or a.get("num_heads", 1)
            if kvh != a.get("num_heads", 1):
                grouped.append(int(kvh))
        self._grouped_kv_heads = min(grouped) if grouped else None
        self._bind_cache_groups()

        self._cache_sharding = None
        self._partition_rules = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .parallel.tp_rules import (kv_cache_pspec,
                                            plan_tensor_parallel)
            from .programs.partition import build_shardings, \
                rules_from_plan

            sizes = dict(mesh.shape)
            model_par = sizes.get("model", 1)
            rep = NamedSharding(mesh, P())
            # the Megatron graph-walk plan, funneled through the ONE
            # regex partition-rule matcher (programs.partition) — the
            # same degrade-to-replicated guard, now shared with every
            # registered program's pspec plumbing
            plan = plan_tensor_parallel(symbol) if model_par > 1 else {}
            self._partition_rules = rules_from_plan(plan)
            arg_data = {n: a.data for n, a in arg_params.items()}
            coverage = {}
            self._replicated_degrades = []
            shardings = build_shardings(mesh, self._partition_rules,
                                        arg_data, coverage=coverage)
            self._sharding_coverage = {
                "mesh": {str(k): int(v) for k, v in mesh.shape.items()},
                "leaves": coverage}
            self._env = {n: jax.device_put(v, shardings[n])
                         for n, v in arg_data.items()}
            self._env.update({n: jax.device_put(a.data, rep)
                              for n, a in aux_params.items()})
            self._cache_sharding = NamedSharding(
                mesh, kv_cache_pspec(
                    mesh.shape, num_kv_heads=self._grouped_kv_heads,
                    degrades=self._replicated_degrades))
            self._token_sharding = NamedSharding(
                mesh, P("data" if sizes.get("data", 1) > 1 else None, None))
        else:
            dev = self._ctx.jax_device
            self._env = {n: jax.device_put(a.data, dev)
                         for n, a in arg_params.items()}
            self._env.update({n: jax.device_put(a.data, dev)
                              for n, a in aux_params.items()})
            self._token_sharding = dev
            self._sharding_coverage = None
            self._replicated_degrades = []

        from . import config as _config

        donate = (1,) if _config.get("MXNET_DECODE_DONATE") else ()
        self._donate = bool(donate)
        # retrace instrumentation (analysis.RetracePass): the impl bodies
        # run only while jax traces them, so these counters check the
        # serving loop's "zero retraces" claim — decode and verify must
        # each trace ONCE, prefill once per admitted (B, P) shape.
        # Probes (lowering for artifact/FLOP text) set _probing and don't
        # count.
        self.trace_counts = {"prefill": 0, "decode": 0, "verify": 0,
                             "chunk": 0, "fork": 0, "commit": 0,
                             "extract": 0, "install": 0}
        self._probing = False
        # {rows a slot: the attention paths a program's trace took}, the
        # Tiles of each node whose decode row took the kernel, the form each
        # of its gated expert layers' routed product took, in the walk's
        # order (ops.moe.MOE_PATH), and the form of each delta layer's
        # decode step (ops.kda.STEP_PATH)
        self._decode_paths = {}
        self._decode_tiles = {}
        self._moe_forms = {}
        self._delta_steps = {}
        if self._paged:
            from .programs.aot import AotDispatch

            # paged programs take (page tables, active mask) as DATA; the
            # chunk program is the whole prefill story (one fixed width).
            # Each is an AotDispatch facade: a plain jax.jit pass-through
            # until prepare_programs() arms an AOT-deserialized (or
            # freshly compiled) executable — the fleet cold-start path
            half = (1,) if self._donate else ()
            self._decode_fn = AotDispatch(
                "paged_decode_step", jax.jit(self._paged_decode_impl,
                                             donate_argnums=donate))
            self._verify_fn = AotDispatch(
                "paged_verify_step", jax.jit(self._paged_verify_impl,
                                             donate_argnums=donate))
            self._chunk_fn = AotDispatch(
                "prefill_chunk", jax.jit(self._chunk_impl,
                                         donate_argnums=half))
            self._fork_fn = AotDispatch(
                "page_fork", jax.jit(
                    self._fork_impl,
                    donate_argnums=(0,) if self._donate else ()))
            self._commit_fn = AotDispatch(
                "slot_commit", jax.jit(
                    self._commit_impl,
                    donate_argnums=(0, 1) if self._donate else ()))
            # page migration/swap: gather a slot's table row out of the
            # pools / scatter saved page contents back in.  Row ids are
            # DATA — one trace each serves every migration, swap-out and
            # readmit (serve.fleet / serve.swap)
            self._extract_fn = AotDispatch(
                "page_extract", jax.jit(self._extract_impl))
            self._install_fn = AotDispatch(
                "page_install", jax.jit(
                    self._install_impl,
                    donate_argnums=(0,) if self._donate else ()))
            # a step's tokens for the host, which reads them after the
            # next step is queued: that step's donation consumes `state.tok`
            # and leaves this copy alone
            self._keep_fn = jax.jit(_keep_tok)
            self._split_fn = jax.jit(_split_key)
            if self._late:
                # the three programs of a graph that drafts for itself; the
                # tick's name holds "paged_decode", by which a reader of a
                # device trace finds a decode program
                self._mtp_fn = AotDispatch(
                    "paged_decode_mtp_step", jax.jit(
                        self._paged_decode_mtp_impl, donate_argnums=donate))
                self._chunk_mtp_fn = AotDispatch(
                    "prefill_chunk_mtp", jax.jit(self._mtp_chunk_impl,
                                                 donate_argnums=half))
                self._commit_mtp_fn = AotDispatch(
                    "slot_commit_mtp", jax.jit(
                        self._commit_mtp_impl,
                        donate_argnums=(0, 1, 2, 3) if self._donate
                        else ()))
            self._manager = None          # serve.PagedKVManager, per batch
            self._pools_template = None   # per-node cache avals (probed)
            self._paged_lens = None       # host mirror for standalone use
            self._chunk_widths = set()    # distinct chunk widths driven
            self._aot_report = None       # last prepare_programs() result
            self._program_specs = {}      # kind -> ProgramSpec (owned
            # here; the global registry only holds weakrefs to these)
        else:
            self._decode_fn = jax.jit(self._decode_impl,
                                      donate_argnums=donate)
            self._verify_fn = jax.jit(self._verify_impl,
                                      donate_argnums=donate)
        self._verify_shapes = set()   # distinct (B, k, has_q) driven
        self._prefill_fns = {}   # (B, P) -> jitted prefill program
        # telemetry: program name -> (jitted fn, arg avals), snapped
        # once on the first dispatch so obs.programs can read the
        # program's HLO lazily (when a map is asked for, off hot paths)
        self._static_args = {}
        self._steady = None     # the state's avals from the second tick on
        # jnp dummies reused every call (sample_tokens at temperature 0
        # never reads the key, but the jit signature keeps it)
        self._zero_key = jax.random.PRNGKey(0)

    @property
    def cache_len(self):
        return self._cache_len

    # the free input a prediction block reads its next tokens from
    MTP_DATA = "mtp_data"

    @property
    def self_drafting(self):
        """Whether the graph brings a multi-token-prediction block: a second
        output that, from the main stack's hidden state at a position and
        the token after it, predicts the token after that."""
        return self._late is not None

    def _vocab_size(self):
        """Width of the head's distribution, from an abstract walk over one
        token (once)."""
        import jax
        import jax.numpy as jnp

        from .analysis.artifact import aval_of
        from .programs.spec import probing

        if getattr(self, "_vocab", None) is None:
            env = {n: aval_of(v) for n, v in self._env.items()}
            with _obs.phase("build.shape_probe", program="shape_probe"), \
                    probing(self):
                self._vocab = int(jax.eval_shape(
                    lambda e: self._run(e, jnp.zeros((1, 1), jnp.float32),
                                        None, 0)[0], env).shape[-1])
        return self._vocab

    def _nodes_after(self, variable):
        """``{id(node)}`` of every node that depends on free input
        ``variable``, itself included."""
        late = set()
        for node in self._symbol._topo():
            if (node.is_variable and node.name == variable) or any(
                    id(src) in late for src, _ in node.inputs):
                late.add(id(node))
        return late

    def _head_regions(self):
        """Per output of the symbol, ``{id(node)}`` of its head: the nodes
        that turn each row of the last hidden state into that row's
        distribution, and that a chunk program runs over the one row it
        reads (:meth:`_run`'s ``head_rows``).  Output 0's are the nodes of
        layer ``head_loss`` (``obs.scopes.layer_of``) on its path; a further
        output's (a prediction block's, whose nodes all carry the block's
        layer) the nodes of those kinds on its own path.  Empty where the
        graph names no such layer, or where a node outside reads one
        inside: such a graph is walked whole."""
        from .obs.scopes import layer_of

        topo = self._symbol._topo()

        def back(node, keep):
            region, stack = set(), [node]
            while stack:
                node = stack.pop()
                if not node.is_variable and id(node) not in region \
                        and keep(node):
                    region.add(id(node))
                    stack.extend(src for src, _ in node.inputs)
            closed = all(id(src) not in region for n in topo
                         if id(n) not in region for src, _ in n.inputs)
            return frozenset(region if closed else ())

        outputs = [node for node, _ in self._symbol._outputs]
        first = back(outputs[0], lambda n: layer_of(n) == "head_loss")
        kinds = {n.op.name for n in topo if id(n) in first}
        return (first,) + tuple(
            back(node, lambda n: n.op.name in kinds)
            for node in outputs[1:])

    def _bind_cache_groups(self):
        """One :class:`CacheLayout` per stateful node, and the nodes
        grouped by what a slot holds of them.  A paged window node keeps
        a ring of ``window + prefill_chunk`` positions (rounded up to a
        page): a chunk is appended whole before it is attended, so the
        ring has to hold the chunk and the window before its first
        query.  Every other attention node, a window node of a dense
        predictor included, keeps ``cache_len`` (its mask does the rest).
        A latent node keeps ``cache_len`` rows in one plane: it joins the
        group of that capacity, the full nodes' where the graph has them.
        A state node keeps no positions (capacity 0): its group comes
        last."""
        from .ops import attention as _attn
        from .serve.manager import CacheGroup

        pt = self._page_tokens
        kind_of = lambda cap: "state" if not cap else \
            "window" if cap < self._cache_len else "full"
        self._layouts = []
        for n in self._cache_nodes:
            if n.op.name == _attn.LATENT_OP:
                self._layouts.append(CacheLayout("latent", 0,
                                                 self._cache_len))
                continue
            if n.op.name != "dot_product_attention":
                self._layouts.append(CacheLayout("state", 0, 0))
                continue
            a = n.parsed_attrs()
            window = int(a.get("window", 0) or 0)
            cap = self._cache_len
            if window and self._paged and self._prefill_chunk:
                cap = min(cap, -(-(window + self._prefill_chunk) // pt) * pt)
            spec = _attn.sparse_spec(a)
            self._layouts.append(CacheLayout(
                kind_of(cap),
                int(a.get("num_kv_heads", 0) or a.get("num_heads", 1)), cap,
                index=spec.stride if spec else 0))
        caps = sorted({l.capacity for l in self._layouts}, reverse=True)
        kinds = [kind_of(c) for c in caps]
        self._groups = [
            CacheGroup(k, c, [i for i, l in enumerate(self._layouts)
                              if l.capacity == c],
                       name=k if kinds.count(k) == 1 else "%s%d" % (k, c))
            for k, c in zip(kinds, caps)]
        self._group_of = [caps.index(l.capacity) for l in self._layouts]

    def attn_walk(self, slots):
        """``[(capacity, block)]``, one entry per attention node that keeps
        the whole context in pages (as keys and values, or as latent rows:
        one walk serves both): the width of the blocks the decode
        step over ``slots`` slots walks its view by
        (``ops.attention.live_block_plan``, from the shapes the step
        itself shows it), or the capacity where the view is gathered
        whole."""
        from .ops import attention as _attn

        out = []
        for layout, node in zip(self._layouts, self._cache_nodes):
            if not self._paged or layout.kind not in ("full", "latent"):
                continue
            cap, pt = layout.capacity, self._page_tokens
            plan = _attn.live_block_plan(
                (slots, 1), (slots, cap // pt), pt,
                mesh_active=self._mesh is not None,
                window=int(node.parsed_attrs().get("window", 0) or 0))
            out.append((cap, plan[0] if plan else cap))
        return out

    @property
    def has_window_group(self):
        """Whether some attention node keeps a ring shorter than
        ``cache_len``: then there is no prefix sharing, and the serving
        loop refuses what a ring cannot carry."""
        return any(g.kind == "window" for g in self._groups)

    @property
    def unshared_groups(self):
        """The cache groups that hold something other than every position
        of the context (a "window" ring, a "state" row; widest first, as
        the groups are): a graph with one has no prefix sharing, and the
        serving loop refuses by name what such a group cannot carry
        (``serve.manager.WHY_NOT``)."""
        return [g for g in self._groups if g.kind != "full"]

    def ring_slack(self, group):
        """Positions a window group's ring holds beyond the widest window
        of its nodes: what a step may write before it evicts a key that a
        later query still sees."""
        return group.capacity - max(
            int(self._cache_nodes[i].parsed_attrs().get("window", 0) or 0)
            for i in group.nodes)

    def state_nodes(self, counts):
        """How many of the graph's recurrent nodes are of the op that
        counts its rows as ``counts`` ("ssm_rows", "linattn_rows",
        "kda_rows", "gdn_rows")."""
        return sum(n.op.name in self._state_ops
                   and self._state_ops[n.op.name].counts == counts
                   for n in self._cache_nodes)

    def state_row_bytes(self, counts=None):
        """Bytes one slot holds in the "state" group: every leaf of every
        recurrent node's state (0 without one); with ``counts``, of the
        nodes of that op alone (:meth:`state_nodes`)."""
        if self._pools_template is None:
            self._pools_template = self._probe_cache_shapes()
        return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                   for n, l, leaves in zip(self._cache_nodes, self._layouts,
                                           self._pools_template)
                   if l.kind == "state" and counts in (
                       None, self._state_ops[n.op.name].counts)
                   for a in leaves)

    def cache_layouts(self):
        """The per-node :class:`CacheLayout`\\ s with the key and value
        widths filled in from the probed pool shapes (paged mode)."""
        from .ops.attention import QuantKV

        if self._pools_template is None:
            self._pools_template = self._probe_cache_shapes()
        width = lambda a: int((a.data if isinstance(a, QuantKV)
                               else a).shape[-1])
        return [l._replace(key_width=width(leaves[0]),
                           value_width=width(leaves[-1]))
                for l, leaves in zip(self._layouts, self._pools_template)]

    def _tables_of(self, mgr):
        """The manager's page tables as the programs take them: one
        (B, M) array, or one a group where the graph has several.  Copies:
        the manager writes its tables in place (a retirement zeroes a row)
        while the step they were shipped with may still be queued, and an
        array made from a host buffer may go on reading that buffer."""
        import jax.numpy as jnp

        return _per_group(jnp.asarray(g.tables.copy()) for g in mgr.groups)

    def _chunk_operands(self, slot, tokens, pos, width):
        """The chunk program's small operands for ``tokens`` of ``slot``
        from position ``pos``: the slot's table rows, the window padded to
        ``width``, the start and the count.  Host arrays, which the call
        ships with its other arguments: a ``jnp.asarray`` each took the
        host 1.1 ms a chunk more (PERF.md §6, PR 39), with the device
        waiting for the chunk meanwhile."""
        rows = slice(slot, slot + 1)
        return (_per_group(g.tables[rows].copy()
                           for g in self._manager.groups),
                _pad_window(tokens, width),
                np.asarray([pos], np.int32),
                np.asarray([len(tokens)], np.int32))

    def _pool_pages_of(self, ai):
        """Pages in the pool of stateful node ``ai``: its group's (the
        rows of a state group)."""
        return self._manager.groups[self._group_of[ai]].pool_pages

    def _table_width(self, group):
        """Entries of one slot's table in ``group``: its pages, or the one
        row index of a state group."""
        return group.capacity // self._page_tokens or 1

    def _pool_shape(self, ai, aval, pages, is_scale=False, is_index=False):
        """Shape of node ``ai``'s pool (or state array) built from the
        probed batch-1 ``aval``: ``pages`` pages of ``page_tokens``
        positions, or ``pages`` state rows.  The scale plane a node's two
        quantized pools share (``is_scale``) is a row a page, (pages,
        page_tokens * 2 * H_kv; a token's stretch padded where 2 * H_kv
        does not divide a lane tile: ``ops.attention.scale_group``):
        ``ops.attention.QuantKV``; so is the index
        of a node with sparse selection (``is_index``), (pages, H_kv * D).
        A latent node's one plane holds page_tokens * (rank + rope) values
        a page (``ops.pallas_decode.latent_plane_shape``)."""
        if self._layouts[ai].kind == "state":
            return (pages,) + tuple(aval.shape[1:])
        if self._layouts[ai].kind == "latent":
            # a page's positions one after the other, in rows of whole lanes
            # where the width gives them: ops.attention, the latent
            # section's header
            from .ops.pallas_decode import latent_plane_shape

            return latent_plane_shape(pages, self._page_tokens,
                                      aval.shape[2])
        if is_index:
            return (pages, aval.shape[2])
        if is_scale:
            from .ops.attention import scale_group

            return (pages, self._page_tokens * scale_group(aval.shape[2]))
        return (pages, self._page_tokens, aval.shape[2])

    # ------------------------------------------------------------------
    # telemetry (mxnet_tpu.obs) — host-side only: the compiled programs
    # are byte-identical with telemetry on or off
    # ------------------------------------------------------------------
    def _register_hlo(self, name, fn, args):
        """Snap ``args``' avals once and register a lazy reader of the
        optimized HLO (the scope map) for program ``name`` (first
        dispatch only; later calls are one dict hit).  The first paged
        step also registers readers for the small programs the serving
        loop runs beside it (:data:`_BESIDE`)."""
        if name in self._static_args or not _obs.enabled():
            return
        import weakref

        import jax.tree_util as jtu

        from .analysis.artifact import aval_of

        self._static_args[name] = (fn, jtu.tree_map(aval_of, args))
        # weakly bound: a collected predictor must not stay pinned (env
        # params + snapped programs) by the process-global readers
        ref = weakref.ref(self)
        names = (name,) + (tuple(self._BESIDE) if self._paged and name in (
            "paged_decode_step", "paged_verify_step",
            "paged_decode_mtp_step") else ())
        for n in names:
            _obs.programs.register_hlo(
                n, lambda n=n, r=ref: (
                    r()._program_hlo(n) if r() is not None else None),
                owner=self)

    # the paged programs that hand the serving state on, and those the
    # loop dispatches beside them: map name -> (attribute of the dispatch,
    # kind in serving_avals)
    _CHUNKS = ("prefill_chunk", "prefill_chunk_mtp")
    _STEPS = _CHUNKS + ("paged_decode_step", "paged_verify_step",
                        "paged_decode_mtp_step")
    _BESIDE = {"slot_commit": ("_commit_fn", "commit"),
               "page_fork": ("_fork_fn", "fork"),
               "page_extract": ("_extract_fn", "extract"),
               "page_install": ("_install_fn", "install"),
               "keep_tok": ("_keep_fn", None),
               "key_split": ("_split_fn", None)}

    def _steady_state(self):
        """Avals of the serving state (pools, lengths, last tokens) as the
        paged programs hand it to each other from a session's second tick
        on: what the snapped step programs make of it, by abstract
        evaluation until it stops changing, committed to the device as
        outputs are.  A session's first dispatches are handed fresh arrays
        (uncommitted; and a carried value whose type one of the programs
        does not keep comes back as another), and jax traces or compiles a
        program of its own for every later one.  Computed once, inside
        ``probing``."""
        import jax
        import jax.tree_util as jtu

        if self._steady is not None:
            return self._steady
        here = None if self._mesh is not None else \
            jax.sharding.SingleDeviceSharding(self._ctx.jax_device)

        def back(old, new):
            # an argument placed by a sharding keeps it; the outputs of a
            # single device's programs are committed to it
            return jax.ShapeDtypeStruct(
                new.shape, new.dtype, weak_type=getattr(new, "weak_type",
                                                        False),
                sharding=getattr(old, "sharding", None) or here)

        snapped = {n: self._static_args[n] for n in self._STEPS
                   if n in self._static_args}
        state = next((a[1] for n, (_, a) in snapped.items()
                      if n not in self._CHUNKS), None)
        if state is None:       # chunks alone so far: the pools
            state = DecodeState(next(iter(snapped.values()))[1][1], None,
                                None)
        for _ in range(4):
            before = state
            for n, (fn, args) in snapped.items():
                if n in self._CHUNKS:
                    out = fn.eval_shape(args[0], state.caches, *args[2:])
                    new = state._replace(caches=out[0])
                else:
                    new = fn.eval_shape(args[0], state, *args[2:])[0]
                    new = state._replace(
                        caches=new.caches, lens=new.lens, tok=new.tok,
                        **({} if new.draft is None else {
                            "draft": new.draft,
                            "draft_probs": new.draft_probs}))
                state = jtu.tree_map(back, state, new)
            if _same_avals(before, state):
                break
        self._steady = state
        return state

    def _steady_args(self, name):
        """``(dispatch, avals)`` of paged program ``name`` as its dispatches
        see it from a session's second tick on; avals None where the graph
        has no such program."""
        import jax

        state = self._steady_state()
        if name in self._BESIDE:
            attr, kind = self._BESIDE[name]
            fn = getattr(self, attr)
            if name == "key_split":
                # a key split from an uncommitted key stays uncommitted
                from .analysis.artifact import aval_of

                return fn, (aval_of(self._zero_key),)
            if kind is None:
                return fn, (state.tok,)
            args = self.serving_avals(state.lens.shape[0]).get(kind)
            if args is None:
                return fn, None
            if kind == "commit":
                # the lengths, the tokens, and a chunk's first token
                first = jax.ShapeDtypeStruct(args[4].shape, args[4].dtype,
                                             sharding=state.tok.sharding)
                return fn, (state.lens, state.tok) + tuple(args[2:4]) \
                    + (first,)
            return fn, (state.caches,) + tuple(args[1:])
        fn, args = self._static_args[name]
        carried = state.caches if name in self._CHUNKS \
            else args[1]._replace(
                caches=state.caches, lens=state.lens, tok=state.tok,
                **({} if args[1].draft is None else {
                    "draft": state.draft,
                    "draft_probs": state.draft_probs}))
        return fn, (args[0], carried) + tuple(args[2:])

    def _program_hlo(self, name):
        """Optimized HLO text of the executable that program ``name``
        dispatches, for ``obs.programs``' maps; None where it has not
        run.  An armed ``AotDispatch`` hands over the executable that
        last answered.  For a ``jax.jit`` the avals are lowered again, a
        paged program's first as every dispatch after a session's first
        sees them (:meth:`_steady_state`), then as snapped at the first:
        the lowering that already holds a loaded executable is the one the
        dispatch calls, and its text is read with nothing compiled.
        Where neither does, a snapped program is compiled as snapped (the
        reader sees the compile and marks the map ``"relowered"``)
        and a program beside the steps is left without a map."""
        from .programs.spec import probing

        fn, snapped = self._static_args[name] if name in self._static_args \
            else (getattr(self, self._BESIDE[name][0]), None)
        armed = getattr(fn, "executable", None)
        if armed is not None and armed() is not None:
            return armed().as_text()
        with probing(self):
            tries = []
            if name in self._STEPS or name in self._BESIDE:
                steady = self._steady_args(name)[1]
                if steady is None:
                    return None     # no such program on this graph
                tries.append(steady)
            if snapped is not None:
                tries.append(snapped)
            for avals in tries:
                lowered = fn.lower(*avals)
                if _loaded(lowered):
                    return lowered.compile().as_text()
            return None if snapped is None else lowered.compile().as_text()

    # ------------------------------------------------------------------
    # the shared graph walk (traced inside both programs)
    # ------------------------------------------------------------------
    def _run(self, env, tokens, caches, pos0, tables=None, active=None,
             valid=None, between=None, head_rows=None):
        """Execute the symbol on (B, t) tokens.

        ``caches is None`` = prefill mode: full causal attention, fresh
        ring buffers captured from each attention node's K/V.  Otherwise
        decode mode: append K/V at ``pos0`` (per-sequence), length-masked
        attention against the cache.  With ``tables`` given the caches
        are shared page pools: appends scatter through the per-slot page
        tables (``active``/``valid`` masks redirect non-writes to the
        scratch page) and attention gathers what the slots have reached
        of the dense-ring view (``ops.attention.paged_attend``) — paged
        storage, every live position attended; a node with sparse
        selection keeps its index beside them and attends what it chooses
        (``ops.attention.paged_attend_sparse``).  A recurrent op's node
        (:func:`state_ops`) carries its state's rows the same way: from zero
        in prefill mode; one token a row in place, its write masked by
        ``active``, in a decode step; the rows its group's table names, the
        padding past ``valid`` skipped, in a chunk.  Returns ``(probs (B, t,
        V), caches)``; ``self._counts`` holds, by name, what each such node
        counted (the rows it advanced, the blocks it chose), for the
        program that called.

        A graph with a prediction block (:attr:`self_drafting`) is walked in
        two parts: the main stack and its head, then ``between(probs (B, t,
        V))`` says which token follows each row (``MTP_DATA``, (B, t)), then
        the block, at the same rows and positions; its distributions are
        left in ``self._mtp_probs`` (B, t, V).  Without ``between`` a walk
        over caches leaves the block out and hands its cache on as it came;
        a walk that builds caches (prefill, the shape probe) feeds it zeros.

        With ``head_rows`` (B,) int32, a chunk program's walk, the nodes of
        each output's head (:meth:`_head_regions`) are left out of the walk,
        and what the walk hands on as an output's distributions (returned,
        given to ``between``, left in ``self._mtp_probs``) is a function of
        no arguments that runs them over row ``head_rows`` of each sequence
        alone, (B, 1, V): the caller calls it where that row is read, behind
        its conditional (:func:`_when`), or not at all.
        """
        import jax
        import jax.numpy as jnp

        from .obs.scopes import node_scope as _node_scope
        from .ops import attention as _attn
        from .ops import kda as _kda
        from .ops import moe as _moe

        b, t = tokens.shape[0], tokens.shape[1]
        new_caches = []
        self._counts = counts = {}
        # which of paged_attend's / cache_attend's paths this walk's
        # attention nodes took, recorded at trace time by the rows a slot
        # of the program that called: artifact meta says from it whether
        # the program holds the decode row's kernel
        self._decode_paths[t] = paths = set()
        self._decode_tiles[t] = kernels = []
        self._moe_forms[t] = forms = []
        self._delta_steps[t] = steps = []
        ci = qi = 0
        values = {}
        base_key = jax.random.PRNGKey(0)
        order = list(enumerate(self._symbol._topo()))
        late, next_tokens = self._late, None
        if late:
            # no node of the stack reads one of the block's: the stack
            # first, each part in the order it had
            order.sort(key=lambda sn: id(sn[1]) in late)
        cut = self._heads if head_rows is not None else ()

        def plain(seq, node, attrs, ins, aux_ins):
            return node.op.fcompute(attrs, ins, aux_ins, OpContext(
                is_train=False, rng=jax.random.fold_in(base_key, seq),
                mesh_active=self._mesh is not None, mesh=self._mesh,
                producers=producers_of(node)))[0]

        def head(which):
            """Output ``which``: (B, t, V), or under ``head_rows`` what
            computes (B, 1, V) from the rows of what its head reads."""
            if head_rows is None:
                return self._head_probs(values, which, b, t)
            at = jnp.asarray(head_rows, jnp.int32).reshape(b)

            def rows(src, x):
                shape = tuple(getattr(x, "shape", ()))
                if src.is_variable and src.name in env:
                    return x        # a parameter
                if shape[:2] == (b, t):
                    return jnp.take_along_axis(
                        x, at.reshape((b, 1) + (1,) * (x.ndim - 2)), axis=1)
                if shape[:1] == (b * t,):
                    # the rows of every sequence, flattened
                    return rows(src, x.reshape((b, t) + shape[1:])).reshape(
                        (b,) + shape[1:])
                return x            # nothing with a row a position

            def read():
                if not cut[which]:
                    # no head to cut: the row of what the walk computed
                    return rows(self._symbol._outputs[which][0],
                                self._head_probs(values, which, b, t))
                local = {}
                for seq, node in order:
                    if id(node) not in cut[which]:
                        continue
                    for src, i in node.inputs:
                        if id(src) not in cut[which]:
                            local[(id(src), i)] = rows(
                                src, values[(id(src), i)])
                    attrs = node.parsed_attrs()
                    n_args = node.op.n_inputs(attrs)
                    got = [local[(id(src), i)] for src, i in node.inputs]
                    with _node_scope(node):
                        outs = plain(seq, node, attrs, got[:n_args],
                                     got[n_args:])
                    local.update(((id(node), i), o)
                                 for i, o in enumerate(outs))
                return self._head_probs(local, which, b, 1)

            return read

        for seq, node in order:
            if any(id(node) in region for region in cut):
                continue
            if late and id(node) in late:
                if between is None and caches is not None:
                    if not node.is_variable and (
                            node.op.name in ("dot_product_attention",
                                             _attn.LATENT_OP)
                            or node.op.name in self._state_ops):
                        new_caches.append(caches[ci])
                        ci += 1
                    continue
                if next_tokens is None:
                    next_tokens = jnp.zeros((b, t), jnp.float32) \
                        if between is None else between(head(0))
            if node.is_variable:
                if node.name == self._data_name:
                    val = tokens
                elif late and node.name == self.MTP_DATA:
                    val = next_tokens
                elif node.name in env:
                    val = env[node.name]
                else:
                    # unfed free input (loss labels): zeros, forward-unused
                    val = jnp.zeros((b, t), jnp.float32)
                values[(id(node), 0)] = val
                continue
            attrs = node.parsed_attrs()
            n_args = node.op.n_inputs(attrs)
            ins = [values[(id(s), i)] for s, i in node.inputs[:n_args]]
            aux_ins = [values[(id(s), i)] for s, i in node.inputs[n_args:]]
            opname = node.op.name
            with _node_scope(node):
                if opname == "dot_product_attention":
                    q, k, v = ins[:3]
                    heads = attrs.get("num_heads", 1)
                    # grouped-query attention: the K/V stream (and so the
                    # cache/pool) is physically kv_heads wide — every append/
                    # quantize below works in kv-head units, attends map
                    # q-head h to kv group h // G
                    kv_heads = attrs.get("num_kv_heads", 0) or heads
                    ai = ci
                    ci += 1
                    dims = dict(num_heads=int(heads),
                                num_kv_heads=int(kv_heads),
                                q_dim=int(q.shape[-1]),
                                kv_dim=int(k.shape[-1]))
                    if qi < len(self._attn_dims):
                        self._attn_dims[qi] = dims
                    else:
                        self._attn_dims.append(dims)
                    qi += 1
                    scale = attrs.get("scale", 0.0) or None
                    # what the node adds to plain causal attention: a
                    # window, a value scale, its scope, a sink (the fourth
                    # input).  A plain node is called as it always was
                    extra = _attn.node_extras(
                        attrs, ins[3] if len(ins) > 3 else None)
                    at = {"layer": extra["layer"]} if extra else {}
                    if attrs.get("rotary_dim", 0):
                        # rotated at the positions this call runs at; the
                        # keys go into the cache rotated
                        q, k = _attn.rotate_qk(
                            attrs, q, k,
                            jnp.asarray(pos0, jnp.int32).reshape(-1, 1)
                            + jnp.arange(t, dtype=jnp.int32)[None, :])
                    spec = _attn.sparse_spec(attrs)
                    if spec is not None:
                        # keys, values and the index of compressed keys; the
                        # blocks each row chose and could have, counted
                        if caches is None:
                            outs = [_attn.sdpa_sparse(
                                q, k, v, spec, num_heads=heads, scale=scale,
                                num_kv_heads=kv_heads, **at)]
                            new_caches.append((
                                self._fill_cache(k, kv_heads),
                                self._fill_cache(v, kv_heads), k[:, :1]))
                        else:
                            tbl = tables[self._group_of[ai]] \
                                if isinstance(tables, tuple) else tables
                            kc, vc, index = caches[ai]
                            kc, vc = _attn.paged_append_kv(
                                kc, vc, tbl, k, v, pos0, num_heads=kv_heads,
                                active=active, valid=valid, **at)
                            index = _attn.paged_append_index(
                                index, kc, tbl, pos0, t, spec, active=active,
                                valid=valid, num_kv_heads=kv_heads, **at)
                            out, (chosen, live) = _attn.paged_attend_sparse(
                                q, kc, vc, index, tbl, jnp.asarray(
                                    pos0, jnp.int32).reshape(-1) + t, spec,
                                num_heads=heads, scale=scale,
                                num_kv_heads=kv_heads, active=active, **at)
                            outs = [out]
                            counts.setdefault("sparse_blocks_chosen",
                                              []).append(chosen)
                            counts.setdefault("sparse_blocks_live",
                                              []).append(live)
                            if t > 1:
                                # a chunk's rows go the walk's way or the
                                # chunk kernel's; a decode row attends the
                                # list it chose, no path of those
                                paths.add(_attn.DECODE_PATH["last"])
                            new_caches.append((kc, vc, index))
                    elif caches is None:
                        outs = [_attn.sdpa(q, k, v, num_heads=heads,
                                           causal=attrs.get("causal", False),
                                           scale=scale,
                                           num_kv_heads=kv_heads, **extra)]
                        new_caches.append((self._fill_cache(k, kv_heads),
                                           self._fill_cache(v, kv_heads)))
                    else:
                        kc, vc = caches[ai]
                        pos = jnp.asarray(pos0, jnp.int32).reshape(-1)
                        mesh_on = self._mesh is not None
                        if tables is not None:
                            # a graph with several cache groups brings one
                            # table a group
                            tbl = tables[self._group_of[ai]] \
                                if isinstance(tables, tuple) else tables
                            kc, vc = _attn.paged_append_kv(
                                kc, vc, tbl, k, v, pos0, num_heads=kv_heads,
                                active=active, valid=valid, **at)
                            outs = [_attn.paged_attend(
                                q, kc, vc, tbl, pos + t, num_heads=heads,
                                scale=scale, mesh_active=mesh_on,
                                num_kv_heads=kv_heads, **extra)]
                        else:
                            kc = _attn.cache_append(kc, k, pos0,
                                                    num_heads=kv_heads, **at)
                            vc = _attn.cache_append(vc, v, pos0,
                                                    num_heads=kv_heads, **at)
                            outs = [_attn.cache_attend(q, kc, vc, pos + t,
                                                       num_heads=heads,
                                                       scale=scale,
                                                       mesh_active=mesh_on,
                                                       num_kv_heads=kv_heads,
                                                       **extra)]
                        paths.add(_attn.DECODE_PATH["last"])
                        if _attn.DECODE_PATH["last"] == "decode-kernel":
                            kernels.append(_attn.DECODE_PATH["tiles"])
                        new_caches.append((kc, vc))
                elif opname == _attn.LATENT_OP:
                    # one plane of rows a position; which of the op's forms
                    # a call takes follows from its own shapes
                    ai = ci
                    ci += 1
                    if caches is None:
                        out, rows = _attn.latent_mix(attrs, *ins, pos0=pos0)
                        carried = (self._fill_cache(rows),)
                    else:
                        tbl = tables[self._group_of[ai]] \
                            if isinstance(tables, tuple) else tables
                        out, plane = _attn.latent_mix(
                            attrs, *ins, cache=caches[ai][0], table=tbl,
                            pos0=pos0, active=active, valid=valid,
                            mesh_active=self._mesh is not None)
                        paths.add(_attn.DECODE_PATH["last"])
                        carried = (plane,)
                    outs = [out]
                    new_caches.append(carried)
                elif opname in self._state_ops:
                    op = self._state_ops[opname]
                    ai = ci
                    ci += 1
                    if caches is None:
                        out, carried, rows = op.mix(attrs, *ins)
                    elif valid is not None:
                        # a chunk: the rows the state group's table names
                        at = tables[self._group_of[ai]][:, 0]
                        out, rows_new, rows = op.mix(
                            attrs, *ins, pos0=pos0, nvalid=valid,
                            state=tuple(jnp.take(a, at, axis=0)
                                        for a in caches[ai]))
                        carried = tuple(
                            a.at[at].set(r) for a, r in zip(caches[ai],
                                                            rows_new))
                    elif t == 1 and active is not None:
                        # a decode step: every slot's row in place, the
                        # write masked (no scratch row to send junk to)
                        _kda.STEP_PATH["last"] = None
                        out, carried, rows = op.mix(
                            attrs, *ins, state=caches[ai], pos0=pos0,
                            active=active)
                        if _kda.STEP_PATH["last"]:
                            steps.append(_kda.STEP_PATH["last"])
                    else:
                        raise MXNetError(
                            "decode: node %r (%s) carries a recurrent state "
                            "one token at a time or one chunk at a time; a "
                            "step of %d tokens over a carried state (the "
                            "speculative verify window) would advance it "
                            "past what a rejected draft can roll back"
                            % (node.name, opname, t))
                    counts.setdefault(op.counts, []).append(rows)
                    outs = [out]
                    new_caches.append(carried)
                else:
                    if opname in _POSITION_BROADCAST_OPS and len(ins) == 2 \
                            and getattr(ins[0], "ndim", 0) == 3 \
                            and getattr(ins[1], "ndim", 0) == 3 \
                            and ins[0].shape[1] != ins[1].shape[1] \
                            and t in (ins[0].shape[1], ins[1].shape[1]):
                        # learned positional table vs the (B, t, E) stream:
                        # gather the rows for the CURRENT positions
                        big_i = 0 if ins[0].shape[1] != t else 1
                        big = ins[big_i]
                        if big.shape[0] != 1:
                            raise MXNetError(
                                "decode: node %r mixes time-lengths %s "
                                "without a broadcastable (1, S, E) side" %
                                (node.name, (ins[0].shape, ins[1].shape)))
                        s_len = big.shape[1]
                        idx = (jnp.asarray(pos0, jnp.int32).reshape(-1, 1)
                               + jnp.arange(t, dtype=jnp.int32)[None, :])
                        idx = jnp.clip(idx, 0, s_len - 1)
                        ins = list(ins)
                        ins[big_i] = jnp.take(big[0], idx, axis=0)
                    outs = plain(seq, node, attrs, ins, aux_ins)
                    if opname == "MoEFFN" and attrs.get("gated"):
                        forms.append(_moe.MOE_PATH["last"])
            for i, o in enumerate(outs):
                values[(id(node), i)] = o
        if late and between is not None:
            self._mtp_probs = head(1)
        return head(0), tuple(new_caches)

    def _head_probs(self, values, which, b, t):
        """Output ``which`` of the symbol as (B, t, V)."""
        head_node, head_idx = self._symbol._outputs[which]
        out = values[(id(head_node), head_idx)]
        if out.ndim == 2 and out.shape[0] == b * t:
            out = out.reshape(b, t, -1)
        elif out.ndim != 3:
            raise MXNetError("decode: head output shape %s is not (B*t, V) "
                             "or (B, t, V)" % (out.shape,))
        return out

    def _fill_cache(self, x, num_heads=1):
        """(B, t, E) prefill K/V -> a (B, C, E) ring buffer holding the t
        tokens at their ``pos % C`` slots (prefill enforces t <= C).
        Under a quantized ``kv_dtype`` the buffer is an
        ``ops.attention.QuantKV`` — data quantized per (token, head), pad
        slots at a floor scale; the fp32 scale plane shards like the data
        (``kv_cache_pspec`` — its trailing H dim is the same head-group
        split as E)."""
        import jax
        import jax.numpy as jnp

        from .ops import attention as _attn

        b, t, e = x.shape
        buf = jnp.zeros((b, self._cache_len, e), x.dtype)
        buf = jax.lax.dynamic_update_slice(buf, x, (0, 0, 0))
        if self._kv_dtype is not None:
            q = _attn.quantize_kv(buf, self._kv_dtype, num_heads)
            # _probing also covers the paged shape probe: an eval_shape at
            # B=1 must not trip a batch-axis divisibility check
            if self._cache_sharding is not None and not self._probing:
                q = _attn.QuantKV(
                    jax.lax.with_sharding_constraint(q.data,
                                                     self._cache_sharding),
                    jax.lax.with_sharding_constraint(
                        q.scale, self._scale_sharding(num_heads)))
            return q
        if self._cache_sharding is not None and not self._probing:
            buf = jax.lax.with_sharding_constraint(buf, self._cache_sharding)
        return buf

    @property
    def _greedy(self):
        from .ops.sample import is_greedy_policy

        return is_greedy_policy(self._temperature, self._top_k)

    def _scale_sharding(self, num_heads):
        """Sharding for a (B, C, H) scale plane: the cache spec's head
        axis when H divides it, else replicated heads.  The data plane's
        E-split can be finer than a head split (E % axis == 0 with
        heads % axis != 0 — legal, GSPMD handles the einsum), and the
        tiny scale plane must not turn that config into a trace error."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = self._cache_sharding.spec
        head_ax = spec[2]
        if head_ax is not None and \
                num_heads % dict(self._mesh.shape)[head_ax] != 0:
            return NamedSharding(self._mesh, P(spec[0], None, None))
        return self._cache_sharding

    def _sample(self, key, probs):
        import jax.numpy as jnp

        from .obs.scopes import scope as _scope
        from .ops.sample import sample_tokens

        with _scope("head_loss", "sample"):
            if self._greedy:
                # argmax(p) == argmax(log p): skip the log on the hot path
                return jnp.argmax(probs, axis=-1).astype(jnp.int32)[:, None]
            logits = jnp.log(probs.astype(jnp.float32) + 1e-30)
            return sample_tokens(key, logits, self._temperature,
                                 self._top_k)[:, None]

    def _policy_probs(self, probs):
        """The EXACT sampling distribution :meth:`_sample` draws from, as
        explicit probability vectors — what speculative acceptance must
        compare against.  Softmax of the SAME ``policy_logits`` the
        sampler's categorical draws over (one implementation, so the two
        cannot drift)."""
        import jax
        import jax.numpy as jnp

        from .ops.sample import policy_logits

        logits = jnp.log(probs.astype(jnp.float32) + 1e-30)
        return jax.nn.softmax(
            policy_logits(logits, self._temperature, self._top_k), axis=-1)

    # ------------------------------------------------------------------
    # the two programs
    # ------------------------------------------------------------------
    def _prefill_impl(self, env, tokens, lens, key):
        import jax.numpy as jnp

        if not self._probing:
            self.trace_counts["prefill"] += 1
        probs3, caches = self._run(env, tokens, None, 0)
        # output at the last REAL prompt position, per sequence
        last = jnp.clip(lens - 1, 0, tokens.shape[1] - 1)
        probs = jnp.take_along_axis(
            probs3, last[:, None, None], axis=1)[:, 0]
        tok = self._sample(key, probs)
        return DecodeState(caches, lens, tok), probs

    def _decode_impl(self, env, state, key):
        if not self._probing:
            self.trace_counts["decode"] += 1
        probs3, caches = self._run(env, state.tok, state.caches, state.lens)
        probs = probs3[:, 0]
        tok = self._sample(key, probs)
        return DecodeState(caches, state.lens + 1, tok), probs

    def _verify_impl(self, env, state, draft_toks, draft_probs, key):
        """ONE batched speculative verify pass: score the last committed
        token + k drafts, accept a prefix, resample at the first
        mismatch.  The cache gets all k+1 K/V appended at fixed width;
        rejection rolls back ``lens`` only — slots past it are masked and
        the next append overwrites them in place."""
        import jax.numpy as jnp

        from .ops.sample import speculative_accept

        if not self._probing:
            self.trace_counts["verify"] += 1
        toks_in = jnp.concatenate(
            [state.tok.astype(jnp.int32), draft_toks.astype(jnp.int32)],
            axis=1)                                        # (B, k+1)
        probs3, caches = self._run(env, toks_in, state.caches, state.lens)
        pi = probs3 if self._greedy else self._policy_probs(probs3)
        counts, out = speculative_accept(key, pi, draft_toks, draft_probs,
                                         greedy=self._greedy)
        tok = jnp.take_along_axis(out, (counts - 1)[:, None], axis=1)
        return (DecodeState(caches, state.lens + counts, tok), out, counts)

    # ------------------------------------------------------------------
    # paged mode — the same programs over shared page pools; page tables
    # and active masks ride in as DATA (mxnet_tpu.serve decides, these
    # execute)
    # ------------------------------------------------------------------
    def _paged_decode_impl(self, env, state, tables, active, key):
        """One paged decode step at fixed batch shape.  ``active`` (B,)
        0/1 gates rows that are empty or mid-chunked-prefill: their
        appends redirect to the scratch page, their recurrent state comes
        out as it went in, and their lens/tok are preserved, so one traced
        program carries every batch occupancy."""
        import jax.numpy as jnp

        from .ops.moe import collecting

        if not self._probing:
            self.trace_counts["decode"] += 1
        with collecting(real=active) as moe_rows:
            probs3, caches = self._run(env, state.tok, state.caches,
                                       state.lens, tables=tables,
                                       active=active)
        probs = probs3[:, 0]
        tok = self._sample(key, probs)
        act = jnp.asarray(active).reshape(-1, 1).astype(bool)
        tok = jnp.where(act, tok, state.tok)
        lens = state.lens + jnp.asarray(active, jnp.int32).reshape(-1)
        # beside the sampled tokens, and read with them: no new sync
        counted = {name: sum(got) for name, got in self._counts.items()}
        return DecodeState(caches, lens, tok,
                           sum(moe_rows) if moe_rows else None,
                           counted.pop("ssm_rows", None),
                           counted or None), probs

    def _paged_verify_impl(self, env, state, tables, active, draft_toks,
                           draft_probs, key):
        """Speculative verify over page tables — same acceptance rule as
        the dense :meth:`_verify_impl`, appends scattered through the
        tables, inactive rows commit zero tokens."""
        import jax.numpy as jnp

        from .ops.sample import speculative_accept

        if not self._probing:
            self.trace_counts["verify"] += 1
        toks_in = jnp.concatenate(
            [state.tok.astype(jnp.int32), draft_toks.astype(jnp.int32)],
            axis=1)
        probs3, caches = self._run(env, toks_in, state.caches, state.lens,
                                   tables=tables, active=active)
        pi = probs3 if self._greedy else self._policy_probs(probs3)
        counts, out = speculative_accept(key, pi, draft_toks, draft_probs,
                                         greedy=self._greedy)
        act = jnp.asarray(active).reshape(-1).astype(bool)
        counts = jnp.where(act, counts, 0)
        k = draft_toks.shape[1]
        tok = jnp.take_along_axis(
            out, jnp.clip(counts - 1, 0, k)[:, None], axis=1)
        tok = jnp.where(act[:, None], tok, state.tok)
        return (DecodeState(caches, state.lens + counts, tok), out, counts)

    def _chunk_impl(self, env, caches, table1, toks, pos0, nvalid, last,
                    key):
        """One fixed-width prefill chunk for a single slot: append the
        chunk's K/V at positions [pos0, pos0 + nvalid) of the slot's page
        table (pad positions past ``nvalid`` are never written) and attend
        causally against everything cached so far; a recurrent state
        advances over the real positions only, from zero where ``pos0`` is
        0.  The output head runs on the chunk's last real row alone, and
        only where ``last`` (1,) is not 0, the chunk that ends its prompt:
        its sample IS the request's first token.  Any other chunk returns
        zeros for ``probs`` and ``tok``, which nobody reads.
        One trace per chunk width — chunked prefill never retraces."""
        import jax.numpy as jnp

        from .ops.moe import collecting

        if not self._probing:
            self.trace_counts["chunk"] += 1
        ones = jnp.ones((toks.shape[0],), jnp.int32)
        # built only if a gated layer asks: other graphs trace what they did
        def real():
            return jnp.arange(toks.shape[1])[None, :] \
                < jnp.asarray(nvalid, jnp.int32).reshape(-1, 1)

        row = jnp.clip(jnp.asarray(nvalid, jnp.int32).reshape(-1) - 1, 0,
                       toks.shape[1] - 1)
        with collecting(real=real) as moe_rows:
            head, caches = self._run(env, toks, caches, pos0,
                                     tables=table1, active=ones,
                                     valid=nvalid, head_rows=row)

        def read():
            probs = head()[:, 0]
            return probs, self._sample(key, probs)

        probs, tok = _when(jnp.asarray(last).reshape(-1)[0] != 0, read)
        if moe_rows:
            return caches, probs, tok, sum(moe_rows)
        return caches, probs, tok

    # ------------------------------------------------------------------
    # a graph that drafts for itself (a multi-token-prediction block): the
    # tick verifies the last draft, commits one or two tokens a slot and
    # leaves the next draft, in one program
    # ------------------------------------------------------------------
    def _draft_of(self, key, probs):
        """``(draft (B, 1), its distribution (B, V) or None)`` from the
        block's output at one row a slot: drawn as :meth:`_sample` draws,
        from the distribution :meth:`_policy_probs` states (None under
        greedy sampling: the draft is the row's argmax)."""
        return self._sample(key, probs), \
            None if self._greedy else self._policy_probs(probs)

    def _paged_decode_mtp_impl(self, env, state, tables, active, key):
        """One self-drafting tick at fixed batch shape: the stack over the
        two rows ``[tok, draft]`` of every slot, ``speculative_accept`` with
        the draft's own distribution, then the prediction block over the
        same two rows with the tokens that were committed after them, and a
        new draft from the last committed row.  Returns ``(state, out (B,
        2), counts (B,), probs (B, 2, V), block_probs (B, V))``: ``counts``
        tokens of ``out`` were committed (0 for an inactive row); the
        stack's distributions at the two rows and the block's at the row
        the new draft came from are what a comparison reads, and the
        serving loop leaves them on the device.  A rejected draft's keys
        lie past the slot's new length in every pool, the block's included,
        and the next tick's first row overwrites them before anything reads
        them."""
        import jax
        import jax.numpy as jnp

        from .ops.moe import collecting
        from .ops.sample import speculative_accept

        if not self._probing:
            self.trace_counts["decode"] += 1
        k_accept, k_draft = jax.random.split(key)
        act = jnp.asarray(active).reshape(-1).astype(bool)
        toks_in = jnp.concatenate([state.tok.astype(jnp.int32),
                                   state.draft.astype(jnp.int32)], axis=1)
        got = {}

        def between(probs3):
            pi = probs3 if self._greedy else self._policy_probs(probs3)
            counts, out = speculative_accept(
                k_accept, pi, state.draft,
                None if self._greedy else state.draft_probs[:, None, :],
                greedy=self._greedy)
            got.update(counts=counts, out=out, moe_main=len(moe_rows),
                       probs=probs3)
            return out

        with collecting(real=lambda: jnp.repeat(act, 2)) as moe_rows:
            _, caches = self._run(env, toks_in, state.caches, state.lens,
                                  tables=tables, active=active,
                                  between=between)
        counts, out = got["counts"], got["out"]
        last = (counts - 1)[:, None]
        block = jnp.take_along_axis(self._mtp_probs, last[:, :, None],
                                    axis=1)[:, 0]
        draft, dprobs = self._draft_of(k_draft, block)
        tok = jnp.take_along_axis(out, last, axis=1)
        keep = lambda new, old: jnp.where(act[:, None], new, old)
        counts = jnp.where(act, counts, 0)
        counted = {name: sum(v) for name, v in self._counts.items()}
        n_main = got["moe_main"]
        if moe_rows[n_main:]:
            # the block's experts, apart: its time is the block's too
            rows = sum(moe_rows[n_main:])
            counted.update(mtp_moe_rows_held=rows[0],
                           mtp_moe_expert_visits=rows[2])
        return (DecodeState(
            caches, state.lens + counts, keep(tok, state.tok),
            sum(moe_rows[:n_main]) if n_main else None,
            counted.pop("ssm_rows", None), counted or None,
            keep(draft, state.draft),
            None if dprobs is None else keep(dprobs, state.draft_probs)),
            out, counts, got["probs"], block)

    def _mtp_chunk_impl(self, env, caches, table1, toks, pos0, nvalid,
                        next_tok, key):
        """:meth:`_chunk_impl` for a graph that drafts for itself: the
        prediction block runs over the chunk's rows too, each with the
        prompt's own next token (``next_tok`` (1,) after the chunk's last
        row; negative where the prompt ends there, and the token sampled
        from that row takes its place), so the block's cache is whole when
        decoding starts and the chunk that ends a prompt leaves the first
        draft.  Returns ``(caches, probs, tok, draft, draft_probs,
        block_probs[, moe])``, ``block_probs`` (1, V) the block's own
        distribution at the chunk's last row.  Both heads run on that row
        alone and only in the chunk that ends a prompt; the block itself
        runs over every row of every chunk."""
        import jax
        import jax.numpy as jnp

        from .ops.moe import collecting

        if not self._probing:
            self.trace_counts["chunk"] += 1
        k_tok, k_draft = jax.random.split(key)
        ones = jnp.ones((toks.shape[0],), jnp.int32)
        width = toks.shape[1]
        nvalid = jnp.asarray(nvalid, jnp.int32).reshape(-1)
        last = jnp.clip(nvalid - 1, 0, width - 1)
        nxt = jnp.asarray(next_tok, jnp.int32).reshape(-1, 1)
        ends = nxt[0, 0] < 0
        got = {}

        def between(head):
            def read():
                probs = head()[:, 0]
                return probs, self._sample(k_tok, probs)

            probs, tok = _when(ends, read)
            got.update(probs=probs, tok=tok)
            shifted = jnp.concatenate(
                [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
            return jnp.where(jnp.arange(width)[None, :] == last[:, None],
                             jnp.where(nxt < 0, tok, nxt).astype(toks.dtype),
                             shifted)

        def real():
            return jnp.arange(width)[None, :] < nvalid[:, None]

        with collecting(real=real) as moe_rows:
            _, caches = self._run(env, toks, caches, pos0, tables=table1,
                                  active=ones, valid=nvalid,
                                  between=between, head_rows=last)

        def read_block():
            block = self._mtp_probs()[:, 0]
            return self._draft_of(k_draft, block) + (block,)

        out = (caches, got["probs"], got["tok"]) + _when(ends, read_block)
        return out + ((sum(moe_rows),) if moe_rows else ())

    def _commit_mtp_impl(self, lens, tok, draft, dprobs, slot, new_len,
                         new_tok, new_draft, new_dprobs):
        """:meth:`_commit_impl` with the slot's first draft and its
        distribution (None under greedy sampling) beside the first token."""
        import jax
        import jax.numpy as jnp

        if not self._probing:
            self.trace_counts["commit"] += 1
        zero = jnp.int32(0)
        put = lambda full, one: jax.lax.dynamic_update_slice(
            full, one.astype(full.dtype), (slot, zero))
        return (jax.lax.dynamic_update_slice(lens, new_len, (slot,)),
                put(tok, new_tok), put(draft, new_draft),
                None if dprobs is None else put(dprobs, new_dprobs))

    def _fork_impl(self, caches, src, dst):
        """Copy-on-write fork: duplicate page ``src`` into ``dst`` across
        every pool (page ids are one global space).  Traced once — the
        ids are data."""
        import jax.tree_util as jtu

        if not self._probing:
            self.trace_counts["fork"] += 1
        return jtu.tree_map(lambda pool: pool.at[dst].set(pool[src]),
                            caches)

    def _commit_impl(self, lens, tok, slot, new_len, new_tok):
        """Activate a freshly prefilled slot: splice its prompt length and
        first token into the batch state (traced slot index)."""
        import jax

        if not self._probing:
            self.trace_counts["commit"] += 1
        import jax.numpy as jnp

        lens = jax.lax.dynamic_update_slice(lens, new_len, (slot,))
        tok = jax.lax.dynamic_update_slice(tok, new_tok,
                                           (slot, jnp.int32(0)))
        return lens, tok

    def _extract_impl(self, caches, row):
        """Gather one slot's (M,) table row out of every pool — per node
        an (M, page_tokens, E) block of page contents, data AND scale
        planes (QuantKV rides the tree).  The page ids are data, so ONE
        trace serves every migration and swap-out; unmapped entries
        gather the scratch page, whose content is never read."""
        import jax.tree_util as jtu

        if not self._probing:
            self.trace_counts["extract"] += 1
        return jtu.tree_map(lambda pool: pool[row], caches)

    def _install_impl(self, caches, row, data):
        """Scatter extracted page contents back into the pools at a
        (freshly allocated) table row — the receiving half of page
        migration and swap-in.  Unmapped row entries are 0: their
        writes land in the scratch page (harmless by design), so one
        fixed-(M,) program carries any live page count.  Donated like
        the step programs — the pools update in place."""
        import jax.tree_util as jtu

        if not self._probing:
            self.trace_counts["install"] += 1
        return jtu.tree_map(lambda pool, d: pool.at[row].set(d),
                            caches, data)

    def extract_pages(self, caches, row):
        """Host-side (numpy) copy of one slot's pages: the shippable
        payload of the page-migration protocol — quantized data plus
        per-(token, head) scales, in table-row order."""
        import jax.numpy as jnp
        import jax.tree_util as jtu

        with _obs.program_span("page_extract"):
            out = self._extract_fn(caches,
                                   jnp.asarray(row, jnp.int32).reshape(-1))
            return jtu.tree_map(lambda x: np.asarray(x), out)

    def install_pages(self, caches, row, data):
        """Write a shipped page payload into this predictor's pools at
        ``row`` (0 = unmapped, redirected to the scratch page).  Returns
        the updated pools; the input pools are donated."""
        import jax.numpy as jnp
        import jax.tree_util as jtu

        with _obs.program_span("page_install"):
            return self._install_fn(
                caches, jnp.asarray(row, jnp.int32).reshape(-1),
                jtu.tree_map(jnp.asarray, data))

    @_obs.phased("build.shape_probe", program="shape_probe")
    def _probe_cache_shapes(self):
        """Per-attention-node cache avals — (1, C, E) K/V (or QuantKV)
        from an abstract prefill at (1, 1), the shape source for building
        page pools without running a dense prefill."""
        import jax
        import jax.numpy as jnp

        from .programs.spec import probing

        env = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for n, v in self._env.items()}
        toks = jax.ShapeDtypeStruct((1, 1), jnp.float32)
        with probing(self):
            return jax.eval_shape(
                lambda e, t: self._run(e, t, None, 0)[1], env, toks)

    def _place_pool(self, buf, is_scale=False):
        """Mesh placement for a (P, page_tokens, E) pool: heads shard on
        'model' (``tp_rules.kv_pool_pspec``), page dim replicated.  A
        node's scale plane is (P, page_tokens * 2 * H_kv), a page a row
        (``ops.attention.QuantKV``): a split of its trailing dimension
        would cut tokens, not head groups, so under a mesh it replicates
        (1/16 of the data planes' bytes at heads of 64), recorded beside
        the trailing dims the model axis does not divide."""
        import jax

        from .ops.attention import apply_kv_layout

        if self._mesh is None:
            # single-device pools take the probe-chosen device layout
            # (MXNET_KV_LAYOUT, benchmarks/layout_probe.py --kv); mesh-
            # sharded pools keep GSPMD's layout choice below
            return apply_kv_layout(buf, self._ctx.jax_device)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .parallel.tp_rules import kv_pool_pspec

        spec = kv_pool_pspec(self._mesh.shape,
                             num_kv_heads=self._grouped_kv_heads,
                             degrades=self._replicated_degrades)
        axis = spec[2]
        if is_scale:
            if axis is not None:
                self._replicated_degrades.append({
                    "site": "pool-scale",
                    "reason": "a row of %d is tokens x heads: no split of "
                    "it on %s is a head-group split" % (buf.shape[1], axis)})
            spec = P(None, None)
        elif axis is not None and \
                buf.shape[2] % dict(self._mesh.shape)[axis] != 0:
            self._replicated_degrades.append({
                "site": "pool",
                "reason": "trailing dim %d %% %s=%d != 0"
                % (buf.shape[2], axis, dict(self._mesh.shape)[axis])})
            spec = P(None, None, None)
        return jax.device_put(buf, NamedSharding(self._mesh, spec))

    def paged_batch_state(self, slots, drafting=False):
        """Fresh paged serving state over ``slots`` slots: a new
        :class:`~mxnet_tpu.serve.PagedKVManager` (allocator + prefix
        cache + page tables) and zeroed pools.  Pool shapes depend only
        on (pool_pages, page_tokens, E), so repeated batches at one
        sizing reuse every compiled program.  ``drafting``: the state of a
        self-drafting server, with a draft token a slot and (unless
        sampling is greedy) the distribution it was drawn from."""
        import jax
        import jax.numpy as jnp

        from .serve import GroupedKVManager, PagedKVManager

        if len(self._groups) > 1:
            self._manager = GroupedKVManager(
                slots, self._groups, self._page_tokens,
                pool_pages=self._pool_pages)
        else:
            # prefix sharing needs the whole context behind a matched
            # prefix: a lone window group has none
            group = self._groups[0]
            self._manager = PagedKVManager(
                slots, group.capacity, self._page_tokens,
                pool_pages=self._pool_pages,
                prefix_cache=self._prefix_cache_on
                and group.kind == "full", kind=group.kind,
                name=group.name)
        if self._pools_template is None:
            self._pools_template = self._probe_cache_shapes()

        pools = []
        for ai, leaves in enumerate(self._pools_template):
            pp = self._pool_pages_of(ai)
            if self._layouts[ai].kind == "state":
                # one row a slot; no layout to choose, nothing to shard
                pools.append(tuple(
                    jax.device_put(jnp.zeros(self._pool_shape(ai, a, pp),
                                             a.dtype), self._ctx.jax_device)
                    for a in leaves))
                continue

            def pool_of(aval, is_scale=False, is_index=False):
                return self._place_pool(
                    jnp.zeros(self._pool_shape(ai, aval, pp, is_scale,
                                               is_index),
                              aval.dtype), is_scale=is_scale or is_index)

            pools.append(_node_pools(leaves, pool_of))
        self._paged_lens = np.zeros(slots, np.int64)
        state = DecodeState(tuple(pools), jnp.zeros((slots,), jnp.int32),
                            jnp.zeros((slots, 1), jnp.int32))
        if drafting:
            vocab = self._vocab_size()
            state = state._replace(
                draft=jnp.zeros((slots, 1), jnp.int32),
                draft_probs=None if self._greedy else jnp.full(
                    (slots, vocab), 1.0 / vocab, jnp.float32))
        return state

    def pool_bytes(self):
        """Static bytes of the shared page pools — the paged serving HBM
        bill (what ``tokens_per_sec_per_gb`` divides by), sized through
        the same width table as :meth:`cache_bytes`."""
        import jax.tree_util as jtu

        from .analysis.hlo_parse import shape_bytes, shape_str

        if self._manager is None:
            raise MXNetError("pool_bytes before any paged prefill/serve")
        if self._pools_template is None:
            self._pools_template = self._probe_cache_shapes()
        # a node's scale plane holds what a (pages, page_tokens, H) plane a
        # pool would: the template's two
        return sum(shape_bytes(shape_str(
            self._pool_shape(ai, aval, self._pool_pages_of(ai),
                             is_index=i >= 2), aval.dtype))
            for ai, leaves in enumerate(self._pools_template)
            for i, leaf in enumerate(leaves)
            for aval in jtu.tree_leaves(leaf))

    # ------------------------------------------------------------------
    # AOT-serialized program preparation — the fleet cold-start path
    # (mxnet_tpu.programs.aot, docs/programs.md)
    # ------------------------------------------------------------------
    # donation maps of the paged serving programs, by kind (must mirror
    # the jit donate_argnums above; _donate off zeroes them all)
    _AOT_DONATE = {"decode": (1,), "verify": (1,), "chunk": (1,),
                   "commit": (0, 1), "fork": (0,), "extract": (),
                   "install": (0,)}

    def _aot_dispatches(self):
        """kind -> the :class:`~mxnet_tpu.programs.aot.AotDispatch`
        facade serving it (paged mode only)."""
        return {"chunk": self._chunk_fn, "decode": self._decode_fn,
                "verify": self._verify_fn, "commit": self._commit_fn,
                "fork": self._fork_fn, "extract": self._extract_fn,
                "install": self._install_fn}

    def _symbol_fingerprint(self):
        """Digest of the model graph — the program-identity component
        of the AOT cache key (two predictors with equal avals but
        different symbols must never share an executable).

        Auto-generated OP node names are canonicalized to their topo
        index before hashing: gensym counters depend on how many
        symbols a process built earlier, and two hosts constructing
        the same model after different warmup must still produce the
        SAME key (graph edges are index-based in the json, so op-node
        labels are decorative; variable names stay — they key the
        param env and are already part of the aval treedef)."""
        import hashlib
        import json as _json

        d = getattr(self, "_sym_digest", None)
        if d is None:
            g = _json.loads(self._symbol.tojson())
            for i, node in enumerate(g.get("nodes", ())):
                if node.get("op") not in (None, "null"):
                    node["name"] = "n%d" % i
            blob = _json.dumps(g, sort_keys=True)
            d = hashlib.blake2b(blob.encode(),
                                digest_size=16).hexdigest()
            self._sym_digest = d
        return d

    def serving_avals(self, slots, chunk_w=None, spec_k=0):
        """Abstract args of every paged serving program at batch width
        ``slots`` — the exact signatures the serving loop drives, built
        WITHOUT tracing, compiling or allocating pools (the cache-shape
        probe is ``jax.eval_shape`` only).  This is what lets a fleet
        host fingerprint and AOT-load its programs before it has served
        a single token."""
        import jax
        import jax.numpy as jnp

        from .analysis.artifact import aval_of
        from .serve.manager import PagedKVManager

        if not self._paged:
            raise MXNetError("serving_avals needs a paged predictor")
        if self._pools_template is None:
            self._pools_template = self._probe_cache_shapes()
        slots = int(slots)
        pt = self._page_tokens
        # per cache group: table width and pool pages (an explicit pool
        # size sizes the first group, as the managers do)
        ms = [self._table_width(g) for g in self._groups]
        pps = [slots if g.kind == "state" else PagedKVManager.pool_sizing(
            slots, g.capacity, pt, self._pool_pages if i == 0 else 0)
            for i, g in enumerate(self._groups)]
        m = ms[0]
        sds = jax.ShapeDtypeStruct

        def build(shape_of):
            pools = []
            for ai, leaves in enumerate(self._pools_template):
                make = lambda a, is_scale=False, is_index=False, ai=ai: sds(
                    shape_of(ai, a, is_scale, is_index), a.dtype)
                pools.append(
                    tuple(make(a) for a in leaves)
                    if self._layouts[ai].kind == "state"
                    else _node_pools(leaves, make))
            return tuple(pools)

        caches = build(lambda ai, a, is_scale=False, is_index=False:
                       self._pool_shape(ai, a, pps[self._group_of[ai]],
                                        is_scale, is_index))
        env = {n: aval_of(v) for n, v in self._env.items()}
        lens = sds((slots,), jnp.int32)
        tok = sds((slots, 1), jnp.int32)
        state = DecodeState(caches, lens, tok)

        def tables_of(rows):
            return _per_group(sds((rows, w), jnp.int32) for w in ms)

        active = sds((slots,), jnp.int32)
        key = aval_of(self._zero_key)
        i32 = sds((), jnp.int32)
        cw = int(chunk_w or self._prefill_chunk or self._cache_len)
        out = {
            # its last small operand: whether the chunk ends its prompt
            "chunk": (env, caches, tables_of(1),
                      sds((1, cw), jnp.float32), sds((1,), jnp.int32),
                      sds((1,), jnp.int32), sds((1,), jnp.int32), key),
            "decode": (env, state, tables_of(slots), active, key),
            "commit": (lens, tok, i32, sds((1,), jnp.int32),
                       sds((1, 1), jnp.int32)),
        }
        if len(self._groups) == 1:
            # page ids are one space a group: fork, extract and install
            # are programs of a graph with one group
            row = sds((m,), jnp.int32)
            # one slot's extracted pages: the pool gathered at an (M,) row
            data = build(lambda ai, a, is_scale=False, is_index=False:
                         self._pool_shape(ai, a, m, is_scale, is_index))
            out.update({"fork": (caches, i32, i32),
                        "extract": (caches, row),
                        "install": (caches, row, data)})
        if spec_k:
            out["verify"] = (env, state, tables_of(slots), active,
                             sds((slots, int(spec_k)), jnp.int32), None,
                             key)
        if self._late:
            # a graph that drafts for itself: its tick and its chunk
            drafting = state._replace(
                draft=tok, draft_probs=None if self._greedy
                else sds((slots, self._vocab_size()), jnp.float32))
            out["mtp_step"] = (env, drafting, tables_of(slots), active, key)
            # (there the operand is the prompt's next token, negative
            # where the chunk ends it)
            out["mtp_chunk"] = out["chunk"]
        return out

    def prepare_programs(self, slots, chunk_w=None, spec_k=0,
                         mode="aot", save_ok=True):
        """Make every paged serving program READY at batch width
        ``slots`` before the first request: load the AOT-serialized
        executable from the content-addressed program cache (a
        deserialize — milliseconds), or trace + lower + compile now on
        a miss (saved back when ``save_ok``, so the next host's cold
        start is a deserialize).  Loaded executables are armed on the
        dispatch facades: serving then runs them with ZERO traces and
        byte-identical results to the JIT path.

        ``mode="compile"`` bypasses the cache entirely (pure
        trace+lower+compile, nothing saved) — the cold-start bench's
        JIT baseline.  Returns the readiness report: per-program
        {source, key, seconds} plus hit/miss counts and total wall;
        idempotent per (slots, chunk width, spec_k) in ``"aot"`` mode.
        """
        import time as _time

        from .programs import aot as _aot, registry as _registry

        sig = (int(slots), int(chunk_w or 0), int(spec_k or 0))
        rep = self._aot_report
        if mode == "aot" and rep is not None \
                and rep.get("signature") == sig:
            return rep
        avals = self.serving_avals(slots, chunk_w=chunk_w, spec_k=spec_k)
        report = {"signature": sig, "programs": {}, "hits": 0,
                  "misses": 0, "wall_s": 0.0}
        t_all = _time.perf_counter()
        for kind, args in avals.items():
            spec = self._aot_spec(kind, args)
            disp = self._aot_dispatches()[kind]
            self._program_specs[kind] = _registry.register(spec)
            t0 = _time.perf_counter()
            if mode == "compile":
                key = spec.fingerprint(args)
                exe, source = spec.compiled(args), "compile"
            else:
                exe, source, key = _aot.load_or_compile(
                    spec, args, save_ok=save_ok)
            dt = _time.perf_counter() - t0
            if exe is not None:
                disp.arm(exe, source, key)
            report["programs"][kind] = {
                "name": disp.name, "source": source, "key": key,
                "seconds": round(dt, 6)}
            if source == "cache":
                report["hits"] += 1
            elif mode != "compile":
                report["misses"] += 1
        report["wall_s"] = round(_time.perf_counter() - t_all, 6)
        if mode == "aot":
            self._aot_report = report
        return report

    def _aot_spec(self, kind, args):
        """The :class:`~mxnet_tpu.programs.spec.ProgramSpec` of one
        paged serving program at concrete abstract args — donation map,
        partition rules, trace counter and the program-identity
        fingerprint extras all registered in one place."""
        from .programs.spec import ProgramSpec

        disp = self._aot_dispatches()[kind]
        extra = {"symbol": self._symbol_fingerprint(),
                 "cache_len": self._cache_len,
                 "page_tokens": self._page_tokens,
                 "kv_dtype": str(self._kv_dtype),
                 "temperature": self._temperature, "top_k": self._top_k,
                 "donate": self._donate, "kind": kind}
        return ProgramSpec(
            disp.name, disp, owner=self,
            donate_argnums=self._AOT_DONATE[kind] if self._donate else (),
            abstract_args=lambda a=args: a,
            trace_count=lambda c=kind: self.trace_counts.get(c),
            partition_rules=self._partition_rules,
            fingerprint_extra=extra)

    def program_fingerprints(self, slots, chunk_w=None, spec_k=0):
        """kind -> content-address of each paged serving program at this
        sizing — equal keys across hosts/workers PROVE byte-identical
        programs (the serve-what-was-audited invariant)."""
        avals = self.serving_avals(slots, chunk_w=chunk_w, spec_k=spec_k)
        return {kind: self._aot_spec(kind, args).fingerprint(args)
                for kind, args in avals.items()}

    def _run_forks(self, caches, copies):
        """Execute a manager-planned list of (src, dst) page copies —
        copy-on-write forks — before the append step that needs them."""
        import jax.numpy as jnp

        for src, dst in copies:
            caches = self._fork_fn(caches, jnp.int32(src), jnp.int32(dst))
        return caches

    def paged_prepare(self, state, lens_h, width, active=None):
        """Make positions [lens, lens + width) of every active row
        writable (allocate/fork through the manager, run the forks) and
        return ``(state', tables, active)`` ready for the step.  The
        device copies of the tables and the activity mask are cached
        against the manager's mutation version / the mask bytes — a
        steady-state decode tick (no page allocated, no fork, same
        occupancy) re-ships NOTHING to the device."""
        import jax.numpy as jnp

        mgr = self._manager
        act = np.ones(mgr.slots, np.int32) if active is None \
            else np.asarray(active).astype(np.int32).reshape(-1)
        caches = state.caches
        for s in range(mgr.slots):
            if act[s]:
                copies = mgr.ensure(s, int(lens_h[s]),
                                    int(lens_h[s]) + int(width))
                if copies:
                    caches = self._run_forks(caches, copies)
        cached = getattr(self, "_tables_dev", None)
        if cached is None or cached[0] is not mgr \
                or cached[1] != mgr.version:
            self._tables_dev = (mgr, mgr.version, self._tables_of(mgr))
        act_key = act.tobytes()
        cached = getattr(self, "_act_dev", None)
        if cached is None or cached[0] != act_key:
            self._act_dev = (act_key, jnp.asarray(act))
        return (DecodeState(caches, state.lens, state.tok,
                            draft=state.draft,
                            draft_probs=state.draft_probs),
                self._tables_dev[2], self._act_dev[1])

    def paged_step(self, state, lens_h, key=None, active=None):
        """One paged decode step: ensure pages, run forks, step.  The
        caller owns the host length vector (``lens_h``) and advances it
        by the returned activity."""
        state, tables, act = self.paged_prepare(state, lens_h, 1, active)
        args = (self._env, state, tables, act,
                key if key is not None else self._zero_key)
        self._register_hlo("paged_decode_step", self._decode_fn, args)
        with _obs.program_span("paged_decode_step"):
            return self._decode_fn(*args)

    def paged_mtp_step(self, state, lens_h, key=None, active=None,
                       behind=0):
        """One self-drafting tick (:meth:`_paged_decode_mtp_impl`), its whole
        result: ``(state, out, counts, probs, block_probs)``.  ``lens_h`` is
        the most each slot can hold by now and ``behind`` how far below it
        the slot's length may lie (2 a tick the host has not read): the two
        rows written start somewhere in between, and every page they may
        land on is made writable."""
        lo = np.maximum(np.asarray(lens_h, np.int64) - int(behind), 0)
        state, tables, act = self.paged_prepare(
            state, lo, 2 + int(behind), active)
        args = (self._env, state, tables, act,
                key if key is not None else self._zero_key)
        self._register_hlo("paged_decode_mtp_step", self._mtp_fn, args)
        with _obs.program_span("paged_decode_mtp_step"):
            return self._mtp_fn(*args)

    def mtp_chunk(self, caches, slot, prompt, pos, width, key):
        """One chunk of ``prompt`` from ``pos`` through the self-drafting
        chunk program (:meth:`_mtp_chunk_impl`), the prompt's next token
        beside it: the program's whole result."""
        n = min(int(width), int(prompt.size) - int(pos))
        end = int(pos) + n
        args = (self._env, caches) + self._chunk_operands(
            slot, prompt[pos:end], pos, width) + (np.asarray(
                [prompt[end] if end < prompt.size else -1], np.int32), key)
        # its dispatch wall accrues to the "prefill" row; only the scope
        # map knows the chunk program by its own name
        self._register_hlo("prefill_chunk_mtp", self._chunk_mtp_fn, args)
        with _obs.program_span("prefill"):
            return self._chunk_mtp_fn(*args)

    def mtp_commit(self, state, slot, plen, tok, draft, dprobs):
        """``state`` with a prefilled slot's length, first token and first
        draft spliced in (:meth:`_commit_mtp_impl`)."""
        import jax.numpy as jnp

        args = (state.lens, state.tok, state.draft, state.draft_probs,
                np.int32(slot), jnp.asarray([plen], jnp.int32), tok, draft,
                dprobs)
        self._register_hlo("slot_commit_mtp", self._commit_mtp_fn, args)
        lens, tok, draft, dprobs = self._commit_mtp_fn(*args)
        return DecodeState(state.caches, lens, tok, draft=draft,
                           draft_probs=dprobs)

    def mtp_prefill(self, tokens, prompt_len=None, key=None):
        """:meth:`prefill` for a graph that drafts for itself, by the
        programs a self-drafting server runs: ``(state, probs (B, V),
        block_probs (B, V))``, the stack's distribution of each row's first
        token and the block's of the token after it."""
        import jax
        import jax.numpy as jnp

        tokens = np.asarray(tokens)
        b = tokens.shape[0]
        lens_h = np.broadcast_to(np.asarray(
            tokens.shape[1] if prompt_len is None else prompt_len,
            np.int64).reshape(-1), (b,)).copy()
        state = self.paged_batch_state(b, drafting=True)
        mgr = self._manager
        key = key if key is not None else self._zero_key
        width = max(1, min(self._prefill_chunk or tokens.shape[1],
                           self._cache_len))
        probs_out, block_out = [], []
        for row in range(b):
            prompt = tokens[row, :int(lens_h[row])].astype(np.int64)
            gate = mgr.gate(prompt, prompt.size, self._cache_len, 1,
                            budget_wrap_forks=False)
            if gate is None:
                raise MXNetError(
                    "KV page pool cannot admit a %d-token prompt — raise "
                    "MXNET_KV_POOL_PAGES (pool: %d pages)"
                    % (prompt.size, mgr.pool_pages))
            mgr.map_slot(row, gate[1], gate[2])
            pos, caches = int(gate[0]), state.caches
            while pos < prompt.size:
                copies = mgr.ensure(row, pos, min(pos + width, prompt.size))
                if copies:
                    caches = self._run_forks(caches, copies)
                key, sub = jax.random.split(key)
                caches, probs, tok, draft, dprobs, block = self.mtp_chunk(
                    caches, row, prompt, pos, width, sub)[:6]
                pos += width
            mgr.publish(row, prompt, prompt.size)
            state = self.mtp_commit(state._replace(caches=caches), row,
                                    prompt.size, tok, draft, dprobs)
            probs_out.append(probs)
            block_out.append(block)
        self._chunk_widths.add(width)
        self._paged_lens = lens_h
        return (state, jnp.concatenate(probs_out, axis=0),
                jnp.concatenate(block_out, axis=0))

    def mtp_step(self, state, key=None):
        """One self-drafting tick after :meth:`mtp_prefill`:
        :meth:`_paged_decode_mtp_impl`'s whole result.  Reads the counts, to
        advance the host's lengths by what was committed."""
        out = self.paged_mtp_step(state, self._paged_lens, key)
        self._paged_lens += np.asarray(out[2]).astype(np.int64)
        return out

    def paged_verify(self, state, lens_h, draft_toks, draft_probs=None,
                     key=None, active=None):
        """One paged speculative macro-step (see :meth:`verify_step`)."""
        import jax.numpy as jnp

        draft_toks = jnp.asarray(draft_toks, jnp.int32)
        k = draft_toks.shape[1]
        state, tables, act = self.paged_prepare(state, lens_h, k + 1,
                                                active)
        self._verify_shapes.add((draft_toks.shape[0], int(k),
                                 draft_probs is not None))
        args = (self._env, state, tables, act, draft_toks, draft_probs,
                key if key is not None else self._zero_key)
        self._register_hlo("paged_verify_step", self._verify_fn, args)
        with _obs.program_span("paged_verify_step"):
            return self._verify_fn(*args)

    def _paged_prefill(self, tokens, prompt_len=None, key=None):
        """Paged prefill = chunked cached-forward, one row at a time:
        match the prefix cache, map shared pages, compute only the tail
        through the chunk program, publish the prompt's pages.  Resets
        the page bookkeeping for a fresh (B,)-slot batch."""
        import jax
        import jax.numpy as jnp

        tokens = np.asarray(tokens)
        b, p = tokens.shape
        if p > self._cache_len:
            raise MXNetError("prompt width %d exceeds cache_len %d"
                             % (p, self._cache_len))
        if prompt_len is None:
            prompt_len = p
        lens_h = np.broadcast_to(
            np.asarray(prompt_len, np.int64).reshape(-1), (b,)).copy()
        state = self.paged_batch_state(b)
        mgr = self._manager
        key = key if key is not None else self._zero_key
        caches = state.caches
        toks_out, probs_out = [], []
        for row in range(b):
            prompt = tokens[row, :int(lens_h[row])].astype(np.int64)
            gate = mgr.gate(prompt, prompt.size, self._cache_len,
                            budget_wrap_forks=False)
            if gate is None:
                raise MXNetError(
                    "KV page pool cannot admit a %d-token prompt — raise "
                    "MXNET_KV_POOL_PAGES (pool: %d pages)"
                    % (prompt.size, mgr.pool_pages))
            matched, pages, reserve_n = gate
            mgr.map_slot(row, pages, reserve_n)
            caches, tok, probs = self._chunked_fill(
                caches, row, prompt, matched, jax.random.fold_in(key, row))
            mgr.publish(row, prompt, prompt.size)
            toks_out.append(tok)
            probs_out.append(probs)
        self._paged_lens = lens_h
        state = DecodeState(caches, jnp.asarray(lens_h, jnp.int32),
                            jnp.concatenate(toks_out, axis=0))
        return state, jnp.concatenate(probs_out, axis=0)

    def _chunked_fill(self, caches, slot, prompt, start, key, width=None):
        """Run [start, len(prompt)) of one row's prompt through the chunk
        program in fixed-width windows; returns (caches, first-token,
        first-token probs) from the final chunk."""
        import jax

        mgr = self._manager
        total = int(prompt.size)
        w = int(width or self._prefill_chunk or (total - int(start)))
        w = max(1, min(w, self._cache_len))
        self._chunk_widths.add(w)
        pos = int(start)
        tok = probs = None
        greedy = self._greedy
        while pos < total:
            n = min(w, total - pos)
            copies = mgr.ensure(slot, pos, pos + n)
            if copies:
                caches = self._run_forks(caches, copies)
            # greedy sampling never reads the key: skip the per-chunk
            # split dispatch
            sub = key if greedy else None
            if sub is None:
                key, sub = jax.random.split(key)
            with _obs.program_span("prefill"):
                # (a graph with gated MoE layers returns their row counts
                # too: the serving loop reads them, this path does not)
                caches, probs, tok = self._chunk_fn(
                    self._env, caches,
                    *self._chunk_operands(slot, prompt[pos:pos + n], pos, w),
                    np.asarray([pos + n >= total], np.int32), sub)[:3]
            pos += n
        return caches, tok, probs

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------
    def prefill(self, tokens, prompt_len=None, key=None):
        """Process a (B, P) prompt batch once; returns ``(state, probs)``.

        ``prompt_len`` (int or (B,)) marks the real length per row of a
        padded batch — cache slots past it stay masked until decode
        overwrites them.  ``probs`` is the model's (B, V) output at each
        row's last real position; ``state.tok`` the sampled first token.
        Jitted per (B, P) shape; repeated calls at one shape reuse the
        compiled program (the serving loop's fixed-shape prefill).  In
        paged mode this is chunked prefill over fresh page tables (one
        slot per row, prefix cache consulted per row).
        """
        import jax
        import jax.numpy as jnp

        if self._paged:
            return self._paged_prefill(tokens, prompt_len, key)
        tokens = self._place_tokens(tokens)
        b, p = tokens.shape
        if p > self._cache_len:
            # a wider window would have to wrap PADDED rows over real
            # tokens for rows shorter than the window — refuse instead of
            # silently attending pad K/V; bind a larger cache_len (decode
            # itself may still wrap past it)
            raise MXNetError("prompt width %d exceeds cache_len %d"
                             % (p, self._cache_len))
        if prompt_len is None:
            prompt_len = p
        lens = jnp.broadcast_to(
            jnp.asarray(prompt_len, jnp.int32).reshape(-1), (b,))
        fn = self._prefill_fns.get((b, p))
        if fn is None:
            fn = jax.jit(self._prefill_impl)
            self._prefill_fns[(b, p)] = fn
        args = (self._env, tokens, lens,
                key if key is not None else self._zero_key)
        self._register_hlo("prefill", fn, args)
        with _obs.program_span("prefill"):
            return fn(*args)

    def step(self, state, key=None):
        """One decode step: append ``state.tok``'s K/V, attend, sample.

        Returns ``(state', probs)`` with ``probs`` the (B, V) distribution
        the new ``state'.tok`` was drawn from.  The input state is donated
        (``MXNET_DECODE_DONATE``) — do not reuse it after the call.
        """
        if self._paged:
            out = self.paged_step(state, self._paged_lens, key)
            self._paged_lens += 1
            return out
        args = (self._env, state,
                key if key is not None else self._zero_key)
        self._register_hlo("decode_step", self._decode_fn, args)
        with _obs.program_span("decode_step"):
            return self._decode_fn(*args)

    def verify_step(self, state, draft_toks, draft_probs=None, key=None):
        """One speculative macro-step: verify k drafted tokens in ONE
        target forward, commit the accepted prefix plus a resampled
        token.

        ``draft_toks`` is (B, k) int32; ``draft_probs`` (B, k, V) are the
        proposal distributions they were drawn from (``None`` for a
        deterministic proposer — n-gram lookup or a greedy draft).
        Returns ``(state', out_toks, counts)``: ``out_toks`` (B, k+1) are
        the emitted tokens, valid through ``counts`` (B,) in [1, k+1];
        ``state'.tok`` is the last emitted token, ``state'.lens`` advanced
        by ``counts`` (rejection rollback — rejected cache entries stay
        masked until overwritten).  The caller must keep the verify
        window inside the ring: ``lens + k + 1 <= cache_len`` for every
        live row (the serving loop's host-side gate).  Fixed shape in k —
        one trace per (B, k, has-draft-probs) signature, donated like
        :meth:`step`.
        """
        import jax.numpy as jnp

        if self._paged:
            st, out, counts = self.paged_verify(
                state, self._paged_lens, draft_toks, draft_probs, key)
            self._paged_lens += np.asarray(counts, np.int64)
            return st, out, counts
        draft_toks = jnp.asarray(draft_toks, jnp.int32)
        self._verify_shapes.add((draft_toks.shape[0], draft_toks.shape[1],
                                 draft_probs is not None))
        args = (self._env, state, draft_toks, draft_probs,
                key if key is not None else self._zero_key)
        self._register_hlo("verify_step", self._verify_fn, args)
        with _obs.program_span("verify_step"):
            return self._verify_fn(*args)

    def generate_speculative(self, tokens, prompt_len=None,
                             max_new_tokens=16, seed=0, eos_id=None,
                             k=None, draft=None, proposer=None):
        """Speculative :meth:`generate`: a (B, N) int32 array of sampled
        tokens, but each loop iteration drafts ``k`` tokens and commits
        1..k+1 of them through one verify pass.  With ``eos_id``, a row
        retires AT its EOS — the speculation window's tail is discarded
        (the serving loop's rule) and the row pads with its last token,
        where plain :meth:`generate` keeps decoding garbage past EOS —
        slice per row in both cases.

        ``draft`` is an optional small draft model (a second
        ``DecodePredictor`` over the same vocabulary — wrapped in a
        :class:`DraftProposer`); without one, ``proposer`` defaults to the
        model-free :class:`NGramProposer`.  Greedy sampling
        (temperature=0) emits EXACTLY the target-only greedy sequence;
        stochastic sampling preserves the target distribution (the
        acceptance-rejection identity) though not the per-seed sample
        path.  Near the ring-wrap boundary the loop falls back to plain
        single-token steps — both programs already traced, so the
        fallback never retraces.
        """
        import jax

        from . import config as _config

        if k is None:
            k = int(_config.get("MXNET_SPEC_K")) or 4
        k = int(k)
        if k <= 0:
            raise MXNetError("speculative k must be positive (got %d)" % k)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tokens = np.asarray(tokens)
        b = tokens.shape[0]
        if prompt_len is None:
            prompt_len = tokens.shape[1]
        lens_h = np.broadcast_to(
            np.asarray(prompt_len, np.int64).reshape(-1), (b,)).copy()
        state, _ = self.prefill(tokens, prompt_len, sub)

        if proposer is None:
            proposer = DraftProposer(draft, k) if draft is not None \
                else NGramProposer(k)
        else:
            # the proposer's draft width IS the verify shape
            k = int(getattr(proposer, "k", k))
        hist = [list(tokens[i, :lens_h[i]].astype(np.int64))
                for i in range(b)]
        first = np.asarray(state.tok)[:, 0]
        rows = [[int(t)] for t in first]
        for i in range(b):
            hist[i].append(int(first[i]))
        if getattr(proposer, "needs_prefill", False):
            key, sub = jax.random.split(key)
            proposer.start(tokens, prompt_len, sub)

        done = np.array([eos_id is not None and rows[i][-1] == eos_id
                         for i in range(b)])
        # the verify window must not wrap the target ring; a draft model
        # appends k entries to its OWN ring too (proposer.cache_len)
        limit = self._cache_len
        if getattr(proposer, "cache_len", None):
            limit = min(limit, proposer.cache_len + 1)
        while True:
            live = [i for i in range(b) if len(rows[i]) < max_new_tokens
                    and not done[i]]
            if not live:
                break
            key, sub = jax.random.split(key)
            if max(lens_h[i] for i in live) + k + 1 <= limit:
                draft_toks, draft_probs = proposer.propose(
                    hist, state, lens_h, sub)
                key, sub = jax.random.split(key)
                state, out, counts = self.verify_step(
                    state, draft_toks, draft_probs, sub)
                out_h = np.asarray(out)
                counts_h = np.asarray(counts)
            else:
                state, _ = self.step(state, sub)
                out_h = np.asarray(state.tok)
                counts_h = np.ones(b, np.int64)
            lens_h += counts_h
            for i in range(b):
                emitted = [int(t) for t in out_h[i, :counts_h[i]]]
                # history tracks everything COMMITTED to the cache —
                # including any window tail past an EOS
                hist[i].extend(emitted)
                if i in live:
                    if eos_id is not None and eos_id in emitted:
                        # discard the speculation-window tail after EOS
                        # (same rule as DecodeServer's deliver)
                        emitted = emitted[:emitted.index(eos_id) + 1]
                        done[i] = True
                    rows[i].extend(emitted)
        n = min(max_new_tokens, max(len(r) for r in rows))
        out = np.zeros((b, n), np.int32)
        for i in range(b):
            row = (rows[i] + [rows[i][-1]] * n)[:n]
            out[i] = row
        return out

    def generate(self, tokens, prompt_len=None, max_new_tokens=16,
                 seed=0, eos_id=None):
        """Prefill + ``max_new_tokens`` decode steps; returns a (B, N)
        int32 numpy array of sampled tokens (rows keep decoding past
        their EOS — slice per row; the serving loop retires properly)."""
        import jax

        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        state, _ = self.prefill(tokens, prompt_len, sub)
        out = [np.asarray(state.tok)]
        done = (out[0][:, 0] == eos_id) if eos_id is not None else None
        for _ in range(max_new_tokens - 1):
            if done is not None and done.all():
                break
            key, sub = jax.random.split(key)
            state, _ = self.step(state, sub)
            out.append(np.asarray(state.tok))
            if done is not None:
                done |= out[-1][:, 0] == eos_id
        return np.concatenate(out, axis=1)

    def _place_tokens(self, tokens):
        import jax

        from .ndarray import NDArray

        if isinstance(tokens, NDArray):
            tokens = tokens.data
        elif not isinstance(tokens, jax.Array):
            tokens = np.asarray(tokens, np.float32)
        return jax.device_put(tokens, self._token_sharding)

    def _paged_probe_args(self, state):
        """Concrete (tables, active) matching this state's batch — the
        extra decode/verify operands in paged mode."""
        import jax.numpy as jnp

        b = state.lens.shape[0]
        if self._manager is not None and self._manager.slots == b:
            tables = self._tables_of(self._manager)
        else:
            tables = _per_group(
                jnp.zeros((b, self._table_width(g)), jnp.int32)
                for g in self._groups)
        return tables, jnp.ones((b,), jnp.int32)

    def decode_step_text(self, state, key=None):
        """Lowered (pre-optimization) StableHLO of the decode-step program
        at this state's shapes — feed to ``parallel.hlo_stats.dot_flops``
        for the O(1)-in-prefix FLOP assertion (bench_decode.py)."""
        from .programs.spec import probe_lowered_text

        key = key if key is not None else self._zero_key
        if self._paged:
            tables, active = self._paged_probe_args(state)
            return probe_lowered_text(
                self, self._decode_fn,
                (self._env, state, tables, active, key))
        return probe_lowered_text(self, self._decode_fn,
                                  (self._env, state, key))

    def _prefill_args(self, b, p):
        import jax
        import jax.numpy as jnp

        env = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for n, v in self._env.items()}
        tokens = jax.ShapeDtypeStruct((b, p), jnp.float32)
        lens = jax.ShapeDtypeStruct((b,), jnp.int32)
        key = jax.ShapeDtypeStruct(self._zero_key.shape,
                                   self._zero_key.dtype)
        return env, tokens, lens, key

    def prefill_text(self, b, p):
        """Lowered StableHLO of the (b, p) prefill program — the
        recompute-the-prefix cost baseline for the FLOP assertion."""
        import jax

        if self._paged:
            raise MXNetError("paged mode prefills through the chunk "
                             "program; there is no one-shot prefill "
                             "program to probe")
        from .programs.spec import probe_lowered_text

        fn = self._prefill_fns.get((b, p)) or jax.jit(self._prefill_impl)
        return probe_lowered_text(self, fn, self._prefill_args(b, p))

    def prefill_artifact(self, b, p, name="prefill"):
        """:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` of the
        (b, p) prefill program.  Prefill donates nothing (its caches are
        born inside the program); expected traces = one per distinct
        admitted (B, P) shape."""
        import jax

        from .programs.spec import probe_artifact

        if self._paged:
            raise MXNetError("paged mode prefills through the chunk "
                             "program; there is no one-shot prefill "
                             "program to snapshot")
        fn = self._prefill_fns.get((b, p)) or jax.jit(self._prefill_impl)
        return probe_artifact(
            self, fn, self._prefill_args(b, p), name, donated_leaves=0,
            mesh_shape=dict(self._mesh.shape)
            if self._mesh is not None else None,
            trace_count=self.trace_counts["prefill"],
            expected_traces=max(len(self._prefill_fns), 1),
            cache_len=self._cache_len)

    def cache_bytes(self, state):
        """Static byte size of the ring caches behind ``state`` — data
        AND scale planes — sized through the analysis width table
        (``analysis.hlo_parse.shape_bytes``, f8/sub-byte aware), so the
        number mxlint budgets and the bench's tokens/s/GB headline share
        one accounting."""
        import jax.tree_util as jtu

        from .analysis.hlo_parse import shape_bytes, shape_str

        return sum(shape_bytes(shape_str(leaf.shape, leaf.dtype))
                   for leaf in jtu.tree_leaves(state.caches))

    def _cache_meta(self, state, fn=None):
        """Cache metadata for artifacts: the static byte budget plus the
        DATA dtypes actually stored (the cache-bytes pass flags an f32
        data plane inside a quantized config from these) and the cache
        layout (the pass flags a dense-ring allocation under a paged
        config — the memory-manager plumbing was dropped).  ``fn`` is
        the dispatch whose AOT provenance the artifact describes
        (default: the decode step's)."""
        from . import config as _config
        from .ops.attention import QuantKV

        # the keys' and values' planes; a state node's rows are not theirs
        kv = [pair for l, pair in zip(self._layouts, state.caches)
              if l.kind != "state"]
        dtypes = set()
        for planes in kv:
            for c in planes[:2]:
                dtypes.add(str((c.data if isinstance(c, QuantKV)
                                else c).dtype))
        meta = {"cache_bytes": self.cache_bytes(state),
                "kv_dtype": str(self._kv_dtype)
                if self._kv_dtype is not None else None,
                "cache_data_dtypes": sorted(dtypes),
                "cache_layout": "paged" if self._paged else "dense",
                "kv_paged": bool(self._paged or (
                    self._paged_from_env
                    and _config.get("MXNET_KV_PAGED")))}
        if self._grouped_kv_heads is not None:
            # grouped-K/V promise + the widths actually allocated: the
            # cache-bytes pass errors when a cache/pool plane comes out
            # H_q heads wide under this promise (a dropped num_kv_heads
            # silently forfeits the G× pool shrink)
            meta["num_kv_heads"] = int(self._grouped_kv_heads)
            meta["attn_dims"] = [dict(d) for d in self._attn_dims]
            widths = set()
            for planes in kv:
                for c in planes[:2]:
                    widths.add(int((c.data if isinstance(c, QuantKV)
                                    else c).shape[2]))
            meta["cache_kv_dims"] = sorted(widths)
        if self._paged:
            meta["page_tokens"] = self._page_tokens
            if self._manager is not None:
                meta["pool_pages"] = self._manager.pool_pages
            # AOT provenance: a dispatch armed with a cached
            # (deserialized) or freshly compiled executable serves with
            # zero traces BY CONSTRUCTION — the retrace pass reads this
            # instead of flagging the 0-count as uninstrumented.  Per
            # program: the verify artifact must not inherit the decode
            # step's source when only decode was prepared
            src = getattr(fn if fn is not None else self._decode_fn,
                          "source", "jit")
            if src != "jit":
                meta["aot"] = src
        # sharding-coverage lint surfaces: the per-leaf partition-rule
        # match records from placement time, plus every K/V degrade the
        # pspec helpers took (deduped — _place_pool runs per buffer)
        if getattr(self, "_sharding_coverage", None) is not None:
            meta["sharding_coverage"] = self._sharding_coverage
        degrades, seen = [], set()
        for rec in getattr(self, "_replicated_degrades", ()):
            key = (rec.get("site"), rec.get("reason"))
            if key not in seen:
                seen.add(key)
                degrades.append(rec)
        if degrades:
            meta["replicated_degrades"] = degrades
        return meta

    def _refine_decode_meta(self, art, rows=1):
        """Say in the artifact's meta which paths the attention nodes of
        the program with ``rows`` query rows a slot took when it was traced
        (``ops.attention.DECODE_PATH``, one of ``decode-kernel`` /
        ``chunk-kernel`` / ``walk`` / ``whole`` a node), and promise a
        Pallas kernel over the live blocks where a node took one: the
        flop-dtype pass then demands a ``pallas_call`` in the program, so a
        kernel the rule chose and the lowering lost is a lint error."""
        paths = self._decode_paths.get(int(rows), ())
        art.meta["attn_paths"] = sorted(paths)
        # which products the decode row's kernel takes, a node that took
        # it: "grouped" / "whole" (pallas_decode.Tiles.body)
        art.meta["decode_bodies"] = [
            t.body for t in self._decode_tiles.get(int(rows), ())]
        art.meta["moe_forms"] = list(self._moe_forms.get(int(rows), ()))
        art.meta["delta_steps"] = list(self._delta_steps.get(int(rows), ()))
        art.meta["pallas_decode"] = bool(
            {"decode-kernel", "chunk-kernel", "absorbed-kernel"} & set(paths))
        return art

    def decode_artifact(self, state, key=None, name="decode_step"):
        """:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` of the
        donated decode-step program at this state's shapes — the "zero
        retraces / zero allocation per token" serving claims as checkable
        metadata (donated leaves = every cache/len/token buffer; cache
        byte + dtype meta for the cache-bytes pass)."""
        import jax.tree_util as jtu

        from .analysis.artifact import aval_of as _aval
        from .programs.spec import probe_artifact

        env = {n: _aval(v) for n, v in self._env.items()}
        astate = jtu.tree_map(_aval, state)
        akey = _aval(key if key is not None else self._zero_key)
        donated = len(jtu.tree_leaves(astate)) if self._donate else 0
        if self._paged:
            tables, active = self._paged_probe_args(state)
            args = (env, astate, jtu.tree_map(_aval, tables), _aval(active),
                    akey)
        else:
            args = (env, astate, akey)
        return probe_artifact(
            self, self._decode_fn, args, name,
            refine=self._refine_decode_meta, donated_leaves=donated,
            mesh_shape=dict(self._mesh.shape)
            if self._mesh is not None else None,
            trace_count=self.trace_counts["decode"], expected_traces=1,
            cache_len=self._cache_len, **self._cache_meta(state))

    def verify_artifact(self, state, k, draft_probs=None, key=None,
                        name="verify_step"):
        """:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` of the
        donated speculative-verify program at this state's shapes and
        draft width ``k`` — same donation/retrace/cache-byte contract as
        the decode step (expected traces = one per driven (B, k, has-q)
        signature).  ``draft_probs`` (array or aval) selects the
        with-proposal-distribution variant; ``None`` the deterministic-
        proposer one."""
        import jax.numpy as jnp
        import jax.tree_util as jtu

        from .analysis.artifact import aval_of as _aval
        from .programs.spec import probe_artifact

        import jax

        env = {n: _aval(v) for n, v in self._env.items()}
        astate = jtu.tree_map(_aval, state)
        b = state.lens.shape[0]
        atoks = jax.ShapeDtypeStruct((b, int(k)), jnp.int32)
        aq = _aval(draft_probs) if draft_probs is not None else None
        akey = _aval(key if key is not None else self._zero_key)
        donated = len(jtu.tree_leaves(astate)) if self._donate else 0
        if self._paged:
            tables, active = self._paged_probe_args(state)
            args = (env, astate, jtu.tree_map(_aval, tables), _aval(active),
                    atoks, aq, akey)
        else:
            args = (env, astate, atoks, aq, akey)
        return probe_artifact(
            self, self._verify_fn, args, name,
            refine=functools.partial(self._refine_decode_meta,
                                     rows=int(k) + 1),
            donated_leaves=donated,
            mesh_shape=dict(self._mesh.shape)
            if self._mesh is not None else None,
            trace_count=self.trace_counts["verify"],
            expected_traces=max(len(self._verify_shapes), 1),
            cache_len=self._cache_len, spec_k=int(k),
            **self._cache_meta(state, fn=self._verify_fn))


def _build_insert_fn():
    """Jitted splice of a batch-1 :class:`DecodeState` into slot ``slot``
    of a batch state (traced slot index — admission never retraces).
    Generic over the cache pytree, so quantized caches (data + scale
    leaves) and draft-model states ride the same machinery."""
    import jax

    from . import config as _config

    donate = (0,) if _config.get("MXNET_DECODE_DONATE") else ()

    def insert(state, one, slot):
        import jax.numpy as jnp
        import jax.tree_util as jtu

        slot = jnp.asarray(slot, jnp.int32)

        def put(full, single):
            idx = (slot,) + (jnp.int32(0),) * (full.ndim - 1)
            return jax.lax.dynamic_update_slice(full, single, idx)

        return jtu.tree_map(put, state, one)

    return jax.jit(insert, donate_argnums=donate)


def _empty_batch_state(one, slots):
    """An all-zero batch state with ``slots`` rows shaped like the
    batch-1 state ``one``."""
    import jax.numpy as jnp
    import jax.tree_util as jtu

    return jtu.tree_map(
        lambda x: jnp.zeros((slots,) + tuple(x.shape[1:]), x.dtype), one)


class NGramProposer:
    """Model-free draft proposer: n-gram lookup over each sequence's own
    history (prompt-lookup / self-speculation).

    Matches the last ``ngram`` committed tokens (``MXNET_SPEC_NGRAM``)
    against earlier history and proposes the k tokens that followed the
    most recent earlier occurrence, backing off to shorter suffixes and
    finally to repeating the last token — always exactly k proposals, so
    the verify shape stays fixed.  Deterministic, so its proposal
    distribution is a delta and :func:`ops.sample.speculative_accept`
    needs no q vectors (``draft_probs=None``).  Pure host-side numpy: the
    proposer costs no device program at all, which is what makes
    self-speculation profitable even at high rejection rates.
    """

    cache_len = None      # no draft ring to keep inside
    needs_prefill = False

    def __init__(self, k, ngram=None):
        from . import config as _config

        self.k = int(k)
        if self.k <= 0:
            raise MXNetError("NGramProposer k must be positive")
        self.ngram = int(ngram) if ngram is not None \
            else int(_config.get("MXNET_SPEC_NGRAM"))
        self.ngram = max(1, self.ngram)

    def propose(self, histories, state=None, lens=None, key=None):
        out = np.zeros((len(histories), self.k), np.int32)
        for r, h in enumerate(histories):
            out[r] = self._row(np.asarray(h, np.int64).reshape(-1))
        return out, None

    def _row(self, h):
        k = self.k
        if h.size == 0:
            return np.zeros(k, np.int32)
        for n in range(min(self.ngram, h.size - 1), 0, -1):
            # vectorized suffix match over every window start with a
            # continuation (body drops the last element, so i + n < |h|
            # holds for free and the suffix's own occurrence is excluded)
            body = h[:-1]
            if body.size < n:
                continue
            win = np.lib.stride_tricks.sliding_window_view(body, n)
            hits = np.flatnonzero((win == h[-n:]).all(axis=1))
            if hits.size:
                i = int(hits[-1])            # most recent earlier match
                cont = h[i + n:i + n + k]
                pad = np.full(k - cont.size, cont[-1], np.int64)
                return np.concatenate([cont, pad]).astype(np.int32)
        return np.full(k, h[-1], np.int32)


class DraftProposer:
    """Draft-model proposer: k autoregressive steps of a SMALL
    :class:`DecodePredictor` over the same vocabulary.

    The draft keeps its own ring caches in lockstep with the target's
    committed prefix: each macro-step it resumes from the target's
    (lens, tok) — rejection rollback is free, rejected draft cache
    entries sit past ``lens`` where the length mask hides them until the
    next append overwrites them.  Committed tokens the draft never
    stepped through (the k-th draft of a fully-accepted window; tokens
    decoded by plain near-wrap fallback steps) are healed by a
    teacher-forced CATCH-UP at the top of :meth:`propose`: per-row
    ``filled`` counters (host-side, fed by the caller's committed-token
    histories — no extra device sync) replay the missing inputs through
    the same decode-step program, so the draft cache never holds a
    permanent hole and acceptance does not decay over long serves.  A
    greedy draft proposes deterministically (``draft_probs=None``, delta
    proposals); a stochastic draft returns its exact per-step sampling
    distributions so the acceptance ratio p/q and the residual are
    well-defined.  One decode-step program on the draft, traced once —
    the "draft" program mxlint audits.
    """

    needs_prefill = True

    def __init__(self, predictor, k):
        self._pred = predictor
        if getattr(predictor, "_paged", False):
            raise MXNetError(
                "DraftProposer needs a dense-cache DecodePredictor: the "
                "draft's per-admission prefill would reset a paged "
                "predictor's page bookkeeping (drafts are small — dense "
                "ring buffers cost them little)")
        self.k = int(k)
        if self.k <= 0:
            raise MXNetError("DraftProposer k must be positive")
        self.cache_len = predictor.cache_len
        self._state = None
        self._insert = None
        self._filled = None     # (B,) host int64: cache valid through

    @property
    def predictor(self):
        return self._pred

    def start(self, tokens, prompt_len, key=None):
        """Prefill the draft on the same (B, P) prompt batch (the
        fixed-batch :meth:`DecodePredictor.generate_speculative` path)."""
        self._state, _ = self._pred.prefill(tokens, prompt_len, key)
        b = self._state.lens.shape[0]
        self._filled = np.broadcast_to(
            np.asarray(prompt_len, np.int64).reshape(-1), (b,)).copy()

    def admit(self, tokens, prompt_len, slot, slots, key=None):
        """Prefill ONE request and splice it into draft slot ``slot`` —
        the serving-loop path (mirrors the server's own admission)."""
        one, _ = self._pred.prefill(tokens, prompt_len, key)
        if self._state is None:
            self._state = _empty_batch_state(one, slots)
            self._filled = np.zeros(slots, np.int64)
        if self._insert is None:
            self._insert = _build_insert_fn()
        self._state = self._insert(self._state, one, np.int32(slot))
        self._filled[slot] = int(prompt_len)

    def _hist_tok(self, histories, pos):
        """(B, 1) int32 of each row's committed token at ``pos`` (host;
        clamped — rows past their history just replay their last
        token, which only touches already-dead cache slots)."""
        out = np.zeros((len(histories), 1), np.int32)
        for r, h in enumerate(histories):
            out[r, 0] = int(h[min(int(pos[r]), len(h) - 1)])
        return out

    def propose(self, histories, state, lens, key=None):
        """Teacher-forced catch-up to the target's committed prefix,
        then k draft steps; returns ``(draft_toks (B, k), draft_probs
        (B, k, V) | None)``.  ``lens`` is the caller's HOST-side
        committed-length vector (the serving loops already track it)."""
        import jax
        import jax.numpy as jnp

        if self._state is None:
            raise MXNetError("DraftProposer.propose before start()/admit()")
        if key is None:
            key = jax.random.PRNGKey(0)
        lens_h = np.broadcast_to(
            np.asarray(lens, np.int64).reshape(-1),
            (self._state.lens.shape[0],)).copy()

        # --- catch-up: replay committed tokens the draft never saw
        # (position `filled` onward) through the same step program.
        # Rows already caught up harmlessly re-append their pending
        # token at `lens` — the very slot the proposal steps below
        # overwrite first.  Usual gap is 0 or 1 (the k-th draft of a
        # fully-accepted window); fallback eras pay theirs here too.
        cur = np.minimum(self._filled, lens_h)
        st = self._state
        for _ in range(int((lens_h - cur).max()) if cur.size else 0):
            st = DecodeState(st.caches, jnp.asarray(cur, jnp.int32),
                             jnp.asarray(self._hist_tok(histories, cur)))
            key, sub = jax.random.split(key)
            st, _ = self._pred.step(st, sub)
            cur = np.minimum(cur + 1, lens_h)

        # --- k proposal steps from the target's committed (lens, tok).
        # Fresh copies: the draft step DONATES its state, and lens/tok
        # here are the target's live buffers.
        st = DecodeState(st.caches, state.lens + 0, state.tok + 0)
        toks, qs = [], []
        for _ in range(self.k):
            key, sub = jax.random.split(key)
            st, probs = self._pred.step(st, sub)
            # st.tok is donated into the NEXT draft step — keep a copy
            toks.append(st.tok + 0)
            if not self._pred._greedy:
                qs.append(self._pred._policy_probs(probs))
        self._state = st
        # appended inputs were [tok, d_1..d_{k-1}]: valid through the
        # accepted prefix, which the caller's next `lens` reveals
        self._filled = lens_h + self.k
        return (jnp.concatenate(toks, axis=1),
                jnp.stack(qs, axis=1) if qs else None)


class DecodeServer:
    """Continuous batching over a :class:`DecodePredictor`.

    ``slots`` in-flight sequences decode as ONE fixed-shape batch; between
    steps, finished sequences (EOS or per-request max-len) retire and free
    slots refill from the request queue via a single-sequence prefill
    spliced into the batch state with ``jax.lax.dynamic_update_slice``
    (slot index traced, so admission never retraces).  Single-threaded by
    design: the serving loop IS the schedule (Orca iteration-level
    scheduling), callers queue requests with :meth:`submit` and drain with
    :meth:`run`.

    The paged loop is additionally a fleet citizen
    (``mxnet_tpu.serve.fleet``, docs/serving_fleet.md): it runs as a
    persistent SESSION one :meth:`serve_tick` at a time so a router can
    interleave hosts, accepts page-restorable records through
    :meth:`inject` (migrated prefills, swapped-out requests), publishes
    its routing view via :meth:`serve_summary` (``/metrics.json``), and
    preempts under pressure — a higher-priority waiter, or any waiter
    after ``MXNET_FLEET_DECODE_BOUND`` pool-blocked iterations, swaps
    the lowest-priority slot's pages to host RAM
    (``MXNET_FLEET_SWAP``); the victim readmits bit-exactly here or on
    any other host.
    """

    @_obs.phased("build.server")
    def __init__(self, predictor, max_prefill, slots=None, eos_id=None,
                 max_new_tokens=None, seed=0, spec_k=None, proposer=None,
                 draft=None, metrics_port=None, host=None):
        from . import config as _config

        self._pred = predictor
        # fleet identity: the per-host label on the mx_fleet_* metric
        # families (serve.fleet sets it; standalone servers are "local")
        self._host = str(host) if host is not None else "local"
        self._max_prefill = int(max_prefill)
        if self._max_prefill > predictor.cache_len:
            raise MXNetError("max_prefill %d exceeds the predictor's "
                             "cache_len %d" % (self._max_prefill,
                                               predictor.cache_len))
        self._slots = int(slots or _config.get("MXNET_DECODE_SLOTS"))
        self._eos_id = eos_id
        self._max_new = int(max_new_tokens) if max_new_tokens is not None \
            else int(_config.get("MXNET_DECODE_MAX_NEW"))
        self._seed = seed
        self._queue = deque()
        self._next_id = 0
        self._insert_fn = None
        self._req = {}          # rid -> submit/admit/first/retire times
        self._done_rids = deque()   # retired rids, oldest first (pruning)
        # chunked-prefill width (paged mode): the predictor's configured
        # chunk, clamped to the admission window — ONE width, one trace
        self._chunk_w = min(
            int(getattr(predictor, "_prefill_chunk", 0) or max_prefill),
            int(max_prefill))
        # --- speculative decoding (MXNET_SPEC_K / explicit args) ---
        if spec_k is None:
            spec_k = int(_config.get("MXNET_SPEC_K"))
        if proposer is not None:
            spec_k = int(getattr(proposer, "k", spec_k))
        elif draft is not None:
            spec_k = int(spec_k) or 4
            proposer = DraftProposer(draft, spec_k)
        # a graph with a prediction block of its own drafts for itself, on
        # the device and inside the tick's program: no proposer, and the
        # loop still reads one tick behind
        self._mtp = bool(spec_k) and proposer is None \
            and getattr(predictor, "self_drafting", False) \
            and getattr(predictor, "_paged", False)
        if self._mtp:
            if int(spec_k) != 1:
                raise MXNetError(
                    "a graph that drafts with its own prediction block "
                    "drafts one token a tick (spec_k=1); got spec_k=%d"
                    % int(spec_k))
        elif spec_k and proposer is None:
            proposer = NGramProposer(spec_k)
        self._spec_k = int(spec_k or 0)
        self._proposer = proposer
        # a 'window' cache group keeps a ring of its last positions and a
        # 'state' group one recurrent state a slot: what such a group
        # cannot carry is refused here, by name and with the group's own
        # reason, and never served from a recycled page or a stale row
        from .serve.manager import why_not

        self._unshared = predictor.unshared_groups \
            if getattr(predictor, "_paged", False) else []
        refused = why_not("speculation", self._unshared,
                          rows=self._spec_k + 1,
                          slack=predictor.ring_slack) \
            if self._unshared and (self._spec_k or proposer is not None) \
            else None
        if refused:
            raise MXNetError(
                "speculative decoding is not supported on a graph with a "
                "%r cache group (groups: %s): %s"
                % (refused[0], [g.name for g in predictor._groups],
                   refused[1]))
        if proposer is not None and getattr(proposer, "cache_len", None):
            if self._max_prefill > proposer.cache_len:
                raise MXNetError(
                    "max_prefill %d exceeds the draft's cache_len %d"
                    % (self._max_prefill, proposer.cache_len))
        self.steps = 0          # device steps executed (bench accounting)
        self.spec_steps = 0     # of which speculative verify steps
        self.tokens_out = 0     # tokens delivered to finished requests
        self.proposed = 0       # drafted tokens offered to verify
        self.accepted = 0       # drafted tokens accepted
        # registry mirrors of the loop counters (scrapeable over
        # /metrics; the python ints above stay the bench's source)
        self._m_steps = _obs.registry.counter(
            "mx_serve_steps", "device steps executed by the serving loop")
        self._m_spec = _obs.registry.counter(
            "mx_serve_spec_steps", "speculative verify steps")
        self._m_tokens = _obs.registry.counter(
            "mx_serve_tokens", "tokens delivered to finished requests")
        self._m_proposed = _obs.registry.counter(
            "mx_spec_proposed", "drafted tokens offered to verify")
        self._m_accepted = _obs.registry.counter(
            "mx_spec_accepted", "drafted tokens accepted by the target")
        self._m_ticks = _obs.registry.counter(
            "mx_serve_ticks_total",
            "serve_tick calls: behind, the tick queued its programs before "
            "it read the previous tick's tokens; first, nothing was unread "
            "when it queued them (a first tick, a proposer, a drain)",
            labels=("read",))
        self._m_chunks = _obs.registry.counter(
            "mx_serve_chunks_total",
            "prefill chunks the loop queued: run, the chunk ended its "
            "prompt and ran the output head on its last row; skipped, an "
            "earlier chunk, whose program skips the head",
            labels=("head",))
        self._m_dropped = _obs.registry.counter(
            "mx_serve_dropped_rows_total",
            "rows of a decode step whose token was dropped: the step was "
            "queued before the host had read the slot's EOS")
        self._m_moe_rows = _obs.registry.counter(
            "mx_moe_rows_total",
            "(token, chosen expert) pairs routed by the gated MoE layers: "
            "to an expert this chip holds, or elsewhere",
            labels=("program", "where"))
        self._attn_walk = None      # the predictor's attn_walk, counted
        self._m_attn_blocks = _obs.registry.counter(
            "mx_attn_blocks_total",
            "blocks of the full-context page tables a decode step's "
            "attention nodes attended (live) and hold (view); a table "
            "gathered whole is one block a slot",
            labels=("kind",))
        self._m_moe_visits = _obs.registry.counter(
            "mx_moe_expert_visits_total",
            "held experts with at least one row, summed over MoE layers",
            labels=("program",))
        self._m_moe_calls = _obs.registry.counter(
            "mx_moe_calls_total",
            "runs of a program with gated MoE layers",
            labels=("program",))
        self._m_ssm_chunk_tokens = _obs.registry.counter(
            "mx_ssm_chunk_tokens_total",
            "(prompt token, SelectiveSSM node) pairs the chunk program "
            "scanned (a chunk's padding left out)")
        # each recurrent op's rows counter and state-bytes gauge, and how
        # many nodes of it the graph has: {counts: (counter, gauge, nodes)}
        nodes_of = getattr(predictor, "state_nodes", lambda counts: 0)
        self._m_state = {
            op.counts: (_obs.registry.counter(op.metric("rows_total"),
                                              op.advanced),
                        _obs.registry.gauge(op.metric("state_bytes"),
                                            op.held),
                        nodes_of(op.counts))
            for op in state_ops().values()}
        self._ssm_nodes = self._m_state["ssm_rows"][2]
        self._m_sparse_blocks = _obs.registry.counter(
            "mx_attn_sparse_blocks_total",
            "(slot, KV group, node) blocks of the attention nodes with "
            "sparse selection in a decode step: those a row attended "
            "(chosen) and those its context holds (live)",
            labels=("kind",))
        self._m_latent_rows = _obs.registry.counter(
            "mx_attn_latent_rows_total",
            "(query slot, cached position, LatentAttention node) triples a "
            "dispatch attended, by the form it took: a decode step's live "
            "rows (absorbed), a prefill chunk's context (expanded)",
            labels=("form",))
        self._latent_nodes = sum(
            l.kind == "latent" for l in getattr(predictor, "_layouts", ()))
        # --- fleet/preemption state (paged loop) ---
        # fair admission: after this many consecutive pool-gate-blocked
        # iterations the lowest-priority slot is preempted (swap-out) so
        # a long decode can no longer wedge the admission gate
        self._fair_bound = int(_config.get("MXNET_FLEET_DECODE_BOUND"))
        # preemption moves a slot's pages to the host and back: neither a
        # ring's pages nor a state row are restorable, so such a group
        # disarms it
        self._swap_armed = bool(_config.get("MXNET_FLEET_SWAP")) \
            and not self._unshared and not self._mtp
        self._preempt_cb = None     # serve.fleet routes records back out
        self._verify_restore = False   # tests: assert restore bit-parity
        self._ps = None             # persistent paged session (tick API)
        self.aot_report = None      # serve_open's AOT readiness report
        self.swap_outs = 0
        self.swap_ins = 0
        self._bind_host_metrics(self._host)
        # Prometheus-text exporter (heritage: kvstore_server.py's server
        # process contract): MXNET_METRICS_PORT / metrics_port= arms the
        # process-wide HTTP sidecar serving the registry + timeline —
        # shared per port, so sequential/concurrent servers coexist
        if metrics_port is None:
            metrics_port = int(_config.get("MXNET_METRICS_PORT"))
        self.metrics_server = _obs.serve_metrics(metrics_port) \
            if metrics_port else None
        # /metrics.json grows the fleet-routing summary: the chain
        # digest + load gauges a remote router scores this host by —
        # one mx_serve_summary:<host> section PER SERVER, so several
        # servers sharing the process-wide port cannot clobber each
        # other's routing view
        self._summary_key = None
        self._register_summary()

    def _register_summary(self):
        """(Re)register this server's ``/metrics.json`` section under
        its current host label (renames drop the old key)."""
        if getattr(self, "metrics_server", None) is None:
            return
        key = "mx_serve_summary:%s" % self._host
        if self._summary_key and self._summary_key != key:
            self.metrics_server.remove_json(self._summary_key)
        self._summary_key = key
        self.metrics_server.add_json(key, self.serve_summary)

    def _bind_host_metrics(self, host):
        """(Re)bind the per-host mx_fleet_* children — the fleet layer
        names its hosts after construction, and the labeled series must
        follow the name or every host's counts land on one label."""
        self._host = str(host)
        self._register_summary()
        lab = {"host": self._host}
        self._m_swapped_pages = _obs.registry.counter(
            "mx_fleet_swapped_pages",
            "pages moved to host RAM by preemption swap-outs",
            labels=("host",)).labels(**lab)
        self._m_migrated_pages = _obs.registry.counter(
            "mx_fleet_migrated_pages",
            "pages installed from migrated/restored records",
            labels=("host",)).labels(**lab)
        self._m_queue_depth = _obs.registry.gauge(
            "mx_fleet_queue_depth", "requests waiting in the host queue",
            labels=("host",)).labels(**lab)
        self._m_free_pages = _obs.registry.gauge(
            "mx_fleet_free_pages", "free pages in the host's KV pool",
            labels=("host",)).labels(**lab)
        self._m_pages_in_use = _obs.registry.gauge(
            "mx_kv_pages_in_use", "allocated pages of a cache group's pool",
            labels=("group",))
        self._m_pages_total = _obs.registry.gauge(
            "mx_kv_pages_total",
            "pages of a cache group's pool (the scratch page included)",
            labels=("group",))
        self._m_ttft = _obs.registry.histogram(
            "mx_fleet_ttft", "seconds from submit to first token",
            labels=("host",)).labels(**lab)

    @property
    def accept_rate(self):
        """Fraction of drafted tokens the target accepted (the k-tuning
        signal: tokens/step = 1 + accept_rate * k on average)."""
        return self.accepted / max(self.proposed, 1)

    def _note_step(self, spec=False):
        """One device step executed (python counters + registry mirror)."""
        self.steps += 1
        self._m_steps.inc()
        if spec:
            self.spec_steps += 1
            self._m_spec.inc()

    def _note_moe(self, unread, note):
        """Count what the gated MoE layers of this tick's programs routed:
        ``unread`` holds (program, int32 [held, elsewhere, visits]) as
        ``serve.readback`` fetched them with the step's tokens.  The
        decode step's own three also go into ``note``, the arguments of
        the tick's ``serve.readback`` span, so that a reader can tell one
        tick's routing from another's."""
        for program, vec in unread:
            held, elsewhere, visits = (int(v) for v in vec)
            self._m_moe_rows.labels(program=program, where="held").inc(held)
            self._m_moe_rows.labels(program=program,
                                    where="elsewhere").inc(elsewhere)
            self._m_moe_visits.labels(program=program).inc(visits)
            self._m_moe_calls.labels(program=program).inc()
            if program == "decode":
                note.update(moe_rows_held=held, moe_rows_elsewhere=elsewhere,
                            moe_expert_visits=visits)

    def _note_counts(self, note):
        """Mirror into the registry what the decode step's recurrent and
        sparse nodes counted (``note`` holds them by name, as the step's
        ``serve.readback`` span shows them)."""
        for counts, (rows, _, _) in self._m_state.items():
            if counts in note:
                rows.inc(note[counts])
        for kind in ("chosen", "live"):
            if "sparse_blocks_" + kind in note:
                self._m_sparse_blocks.labels(kind=kind).inc(
                    note["sparse_blocks_" + kind])

    def _note_attn_blocks(self, slot_lens, act_mask, note):
        """Count the blocks this tick's decode step attends, from the host's
        own lengths (no device value is read): over the attention nodes
        that keep the whole context, a slot that decodes reaches ``ceil((len
        + 1) / block)`` of its table's blocks (all of them once its ring
        has wrapped), any other slot one.  live / view is the share of
        the tables the step attended; both also go into ``note``."""
        if self._attn_walk is None:
            self._attn_walk = Counter(self._pred.attn_walk(len(slot_lens)))
        live = view = 0
        lens = np.where(act_mask > 0, slot_lens + 1, 0)
        for (cap, block), nodes in self._attn_walk.items():
            nb = -(-cap // block)
            live += nodes * int(np.where(lens >= cap, nb, np.clip(
                -(-lens // block), 1, nb)).sum())
            view += nodes * nb * len(lens)
        self._m_attn_blocks.labels(kind="live").inc(live)
        self._m_attn_blocks.labels(kind="view").inc(view)
        note.update(attn_blocks_live=live, attn_blocks_view=view)

    def _note_latent_rows(self, rows, lens, note=None):
        """Count the cached positions the latent nodes of one dispatch
        attend, from the host's own lengths: ``rows`` query rows a slot
        say which form it took (``ops.attention.latent_form``), ``lens`` the
        positions each of its slots holds once its rows are appended.  A
        decode step's count also goes into ``note`` as ``latent_rows``.
        Called where the graph has such nodes (``_latent_nodes``)."""
        from .ops.attention import latent_form

        n = self._latent_nodes * int(np.sum(lens))
        self._m_latent_rows.labels(form=latent_form(rows)).inc(n)
        if note is not None:
            note["latent_rows"] = n

    def _note_accept(self, proposed, accepted):
        """One slot's speculative window accounted."""
        self.proposed += proposed
        self.accepted += accepted
        self._m_proposed.inc(proposed)
        self._m_accepted.inc(accepted)

    def submit(self, tokens, max_new_tokens=None, priority=0):
        """Queue a prompt (1-D int sequence); returns the request id.

        ``priority`` matters only under preemption (paged mode with
        ``MXNET_FLEET_SWAP``): higher values are swapped out LAST when
        the pool runs dry.  Admission order stays FIFO."""
        tokens = np.asarray(tokens).reshape(-1)
        if tokens.size > self._max_prefill:
            raise MXNetError("prompt length %d exceeds max_prefill %d"
                             % (tokens.size, self._max_prefill))
        rid = self._next_id
        self._next_id += 1
        cap = int(max_new_tokens) if max_new_tokens is not None \
            else self._max_new
        if self._mtp and tokens.size + cap + 2 > self._pred.cache_len:
            raise MXNetError(
                "a self-drafting server keeps a whole request in its "
                "pages: prompt %d + max_new_tokens %d + a verify step's 2 "
                "rows exceed cache_len %d"
                % (tokens.size, cap, self._pred.cache_len))
        self._queue.append({"rid": rid, "prompt": tokens, "cap": cap,
                            "prio": int(priority), "swap": None})
        self._req[rid] = {"submit": time.time()}
        return rid

    def inject(self, record, front=False):
        """Queue a restorable :class:`~mxnet_tpu.serve.swap.
        SwappedRequest` — a page-migrated prefill from a dedicated
        prefill worker, or a request another host swapped out.  The
        record admits through the normal reservation gate and restores
        by installing its saved pages (no prefill); SLO timestamps carry
        over so fleet TTFT stays honest.  Returns this host's rid."""
        if self._unshared:
            from .serve.manager import why_not

            raise MXNetError(
                "inject: restoring a swapped or migrated request is not "
                "supported on a graph with a %r cache group: %s"
                % why_not("restore", self._unshared))
        self._settle()      # a restore splices into a batch that is read
        rid = self._next_id
        self._next_id += 1
        entry = {"rid": rid, "prompt": record.prompt, "cap": record.cap,
                 "prio": record.priority, "swap": record}
        (self._queue.appendleft if front else self._queue.append)(entry)
        rec = {"submit": record.submit_ts
               if record.submit_ts is not None else time.time()}
        self._req[rid] = rec
        return rid

    # retained retired-request records (stats percentiles); older ones
    # are pruned so a long-lived server cannot grow without bound (the
    # profiler-side store has the same cap)
    _REQ_CAP = 4096

    def _finish(self, rid, ntokens):
        """Close a request's SLO record and publish it to the profiler
        (queue wait, time to first token, decode tokens/s)."""
        from . import profiler as _prof

        rec = self._req.get(rid)
        if rec is None or "retire" in rec:
            return
        now = time.time()
        rec["retire"] = now
        rec["tokens"] = int(ntokens)
        first = rec.get("first", now)
        self._m_ttft.observe(max(first - rec["submit"], 0.0))
        _prof.record_request(
            rec.get("admit", rec["submit"]) - rec["submit"],
            first - rec["submit"], ntokens, now - first, rid=rid)
        _obs.instant("retire", cat="serve",
                     args={"rid": rid, "tokens": int(ntokens)})
        self._done_rids.append(rid)
        while len(self._done_rids) > self._REQ_CAP:
            self._req.pop(self._done_rids.popleft(), None)

    def _deliver(self, rec, emitted):
        """Append a window of emitted tokens to a request, honoring its
        cap and retiring at an EOS inside the window (shared by the
        dense and paged loops — ONE copy of the retirement rule)."""
        toks, max_new = rec["toks"], rec["cap"]
        if self._eos_id is not None and toks and toks[-1] == self._eos_id:
            # ended at its EOS, which was read after this step was queued
            return
        for t in emitted:
            if len(toks) >= max_new:
                break
            toks.append(int(t))
            if self._eos_id is not None and t == self._eos_id:
                break

    def _retire_finished(self, active, results, on_retire=None):
        """Retire every finished request in ``active`` (EOS delivered or
        cap reached): record the result, close its SLO record, free the
        slot — plus ``on_retire(slot)`` for loop-specific cleanup (the
        paged loop frees the slot's pages here, immediately)."""
        for slot in list(active):
            rec = active[slot]
            if self._ended(rec):
                self._close(rec, results)
                del active[slot]
                if on_retire is not None:
                    on_retire(slot)

    def _ended(self, rec):
        """Whether the tokens a request HOLDS end it: an EOS delivered, or
        its cap reached."""
        toks = rec["toks"]
        return (self._eos_id is not None and bool(toks)
                and toks[-1] == self._eos_id) or len(toks) >= rec["cap"]

    def _count_out(self, rec):
        """Book a request's tokens as out of the slots: all it holds when it
        leaves its slot, the rest when its last token has been read, so that
        ``tokens_out`` plus what the slots hold is every token delivered."""
        n = len(rec["toks"]) - rec.get("counted", 0)
        rec["counted"] = len(rec["toks"])
        self.tokens_out += n
        self._m_tokens.inc(n)

    def _close(self, rec, results):
        """Record a finished request's result and close its SLO record."""
        rec["closed"] = True
        results[rec["rid"]] = np.asarray(rec["toks"], np.int32)
        self._count_out(rec)
        self._finish(rec["rid"], len(rec["toks"]))

    def stats(self):
        """Serving-side SLO snapshot: loop counters, per-request
        percentiles (queue wait, TTFT, decode tokens/s) and — in paged
        mode — pool utilization and prefix-cache hit accounting."""
        from .profiler import _percentile

        self._settle()
        done = [r for r in self._req.values() if "retire" in r]
        out = {"steps": self.steps, "spec_steps": self.spec_steps,
               "tokens_out": self.tokens_out,
               "accept_rate": self.accept_rate,
               "requests_completed": len(done),
               "requests_queued": len(self._queue)}
        if done:
            qw = sorted(r.get("admit", r["submit"]) - r["submit"]
                        for r in done)
            tf = sorted(r.get("first", r["retire"]) - r["submit"]
                        for r in done)
            out["queue_wait_p50_s"] = _percentile(qw, 0.50)
            out["queue_wait_p95_s"] = _percentile(qw, 0.95)
            out["ttft_p50_s"] = _percentile(tf, 0.50)
            out["ttft_p95_s"] = _percentile(tf, 0.95)
            rates = sorted(
                (r["tokens"] - 1)
                / max(r["retire"] - r.get("first", r["retire"]), 1e-9)
                for r in done if r["tokens"] > 1)
            if rates:
                out["decode_tokens_per_sec_p50"] = _percentile(rates, 0.50)
                out["decode_tokens_per_sec_p95"] = _percentile(rates, 0.95)
        if getattr(self._pred, "_paged", False) \
                and self._pred._manager is not None:
            out.update(self._pred._manager.stats())
        out["swap_outs"] = self.swap_outs
        out["swap_ins"] = self.swap_ins
        return out

    def serve_summary(self):
        """The routing view a fleet front-end scores this host by —
        served inside ``/metrics.json`` (the ``mx_serve_summary:<host>``
        key)
        when the metrics HTTP sidecar is armed, and read directly by an
        in-process :class:`~mxnet_tpu.serve.fleet.Router`: free-page /
        queue-depth load signals plus the prefix-cache CHAIN SUMMARY
        (content-free token-chain hashes, ``PrefixCache.summary``) the
        cache-aware policy matches prompts against."""
        mgr = getattr(self._pred, "_manager", None)
        active = len(self._ps["active"]) if self._ps is not None else 0
        pending = 1 if self._ps is not None and self._ps["pending"] else 0
        out = {"host": self._host,
               "slots": self._slots,
               "active": active + pending,
               "queue_depth": len(self._queue),
               "free_pages": mgr.allocator.free_pages
               if mgr is not None else None,
               "swap_outs": self.swap_outs,
               "chains": None}
        if mgr is not None and mgr.prefix_cache is not None:
            out["chains"] = mgr.prefix_cache.summary()
        return out

    def run(self):
        """Drain the queue; returns ``{request_id: np.int32 array}`` of
        generated tokens (EOS included when hit).

        With speculation armed (``spec_k``/``MXNET_SPEC_K``/``proposer``/
        ``draft``), each iteration drafts k tokens per slot and commits
        1..k+1 through ONE verify pass; a sequence that emits EOS or hits
        its cap MID-WINDOW retires immediately — the window's later
        tokens are discarded from the result (their cache entries are
        dead weight the next admission overwrites) and the freed slot
        refills before the next step.  Near the ring-wrap boundary the
        loop falls back to plain single-token steps (both programs
        already traced — still zero retraces).

        With a paged predictor the loop instead drives the page-managed
        schedule (:meth:`_run_paged`): prompts admit in fixed-size chunks
        interleaved with decode steps, prefix-cache hits skip their
        matched pages' prefill, copy-on-write forks run before divergent
        writes, and retirement frees pages immediately.
        """
        import jax

        if getattr(self._pred, "_paged", False):
            return self._run_paged()
        key = jax.random.PRNGKey(self._seed)
        state = None
        active = {}     # slot -> [rid, tokens list, max_new]
        results = {}
        histories = {}  # slot -> committed token list (proposer food)
        slot_lens = np.zeros(self._slots, np.int64)
        proposer = self._proposer
        k = self._spec_k
        limit = self._pred.cache_len
        if proposer is not None and getattr(proposer, "cache_len", None):
            limit = min(limit, proposer.cache_len + 1)
        if self._insert_fn is None:
            self._insert_fn = _build_insert_fn()

        def retire():
            self._retire_finished(active, results)

        deliver = self._deliver

        while self._queue or active:
            # admit: prefill one request per free slot, splice into batch
            while self._queue and len(active) < self._slots:
                entry = self._queue.popleft()
                rid, prompt = entry["rid"], entry["prompt"]
                padded = _pad_window(prompt, self._max_prefill)
                key, sub = jax.random.split(key)
                one, _ = self._pred.prefill(padded, prompt.size, sub)
                rec = self._req[rid]
                rec["admit"] = rec["first"] = time.time()
                slot = next(s for s in range(self._slots)
                            if s not in active)
                _obs.instant("admit", cat="serve",
                             args={"rid": rid, "slot": slot})
                if state is None:
                    state = _empty_batch_state(one, self._slots)
                first = int(np.asarray(one.tok)[0, 0])
                state = self._insert_fn(state, one, np.int32(slot))
                if proposer is not None \
                        and getattr(proposer, "needs_prefill", False):
                    key, sub = jax.random.split(key)
                    proposer.admit(padded, prompt.size, slot, self._slots,
                                   sub)
                active[slot] = {"rid": rid, "toks": [first],
                                "cap": entry["cap"],
                                "prio": entry["prio"], "prompt": prompt}
                histories[slot] = list(prompt.astype(np.int64)) + [first]
                slot_lens[slot] = prompt.size
            retire()
            if not active:
                continue
            key, sub = jax.random.split(key)
            can_spec = proposer is not None and k > 0 and \
                max(slot_lens[s] for s in active) + k + 1 <= limit
            if can_spec:
                hists = [histories.get(s) or [0] for s in range(self._slots)]
                draft_toks, draft_probs = proposer.propose(
                    hists, state, slot_lens, sub)
                key, sub = jax.random.split(key)
                state, out, counts = self._pred.verify_step(
                    state, draft_toks, draft_probs, sub)
                out_h = np.asarray(out)
                counts_h = np.asarray(counts).astype(np.int64)
                self._note_step(spec=True)
                for slot, rec in active.items():
                    emitted = out_h[slot, :counts_h[slot]]
                    self._note_accept(k, int(counts_h[slot]) - 1)
                    deliver(rec, emitted)
                    histories[slot].extend(int(t) for t in emitted)
                slot_lens += counts_h
            else:
                state, _ = self._pred.step(state, sub)
                self._note_step()
                toks = np.asarray(state.tok)[:, 0]
                for slot, rec in active.items():
                    deliver(rec, toks[slot:slot + 1])
                    histories[slot].append(int(toks[slot]))
                slot_lens += 1
            retire()
        return results

    # ------------------------------------------------------------------
    # the paged serving schedule — a persistent SESSION driven one
    # iteration at a time (:meth:`serve_tick`), so a fleet router
    # (``serve.fleet``) can interleave hosts, inject migrated state and
    # collect preemptions between iterations; :meth:`run` drives the
    # same tick loop to drain the local queue.
    # ------------------------------------------------------------------
    def serve_open(self):
        """Get-or-create the paged serving session: fresh page pools,
        manager and batch bookkeeping.  Idempotent while a session is
        live; :meth:`serve_reset` closes it (compiled programs are
        per-predictor and survive — a reopened session retraces
        nothing)."""
        if self._ps is not None:
            return self._ps
        with _obs.phase("build.serve_open"):
            return self._open_session()

    def _open_session(self):
        import jax

        pred = self._pred
        slots = self._slots
        # AOT cold start (MXNET_AOT): before the first request, load
        # every serving program's serialized executable from the
        # content-addressed program cache (or compile-and-save on a
        # miss) — host readiness becomes a deserialize, and the loaded
        # programs serve with zero traces (docs/programs.md)
        from .programs import aot as _aot

        if _aot.enabled() and getattr(pred, "_paged", False) \
                and pred._mesh is None:
            # a proposer that supplies draft PROBABILITIES (a non-greedy
            # draft model) gives verify a different signature than the
            # deterministic-proposer one prepared here — leave verify on
            # the JIT path then, instead of arming an executable every
            # verify step would mismatch into a fallback
            prop = self._proposer
            probs_prop = getattr(prop, "predictor", None) is not None \
                and not prop.predictor._greedy
            with _obs.phase("build.serve_open.aot"):
                self.aot_report = pred.prepare_programs(
                    slots, chunk_w=self._chunk_w,
                    spec_k=0 if probs_prop else self._spec_k)
        elif _aot.enabled():
            import logging

            logging.getLogger(__name__).info(
                "MXNET_AOT is armed but this server's predictor is %s; "
                "AOT preparation covers paged single-host predictors "
                "only (docs/programs.md) — keeping the JIT path",
                "mesh-sharded" if getattr(pred, "_mesh", None) is not None
                else "dense (non-paged)")
        self._ps = {
            "key": jax.random.PRNGKey(self._seed),
            "state": pred.paged_batch_state(slots, drafting=True)
            if self._mtp else pred.paged_batch_state(slots),
            "active": {},       # slot -> request record dict
            "results": {},
            "histories": {},
            "slot_lens": np.zeros(slots, np.int64),
            "act_mask": np.zeros(slots, np.int32),
            "pending": None,    # the one admission mid-chunked-prefill
            "blocked": 0,       # consecutive pool-gate-blocked ticks
            "tick": 0,          # serve_tick calls (the serve.tick span's arg)
            "unread": None,     # what the last tick queued and nobody has
                                # read yet (_tick's `cur`); _settle reads it
        }
        if getattr(pred._manager, "prefix_cache", None) is not None:
            # the copy-on-write fork is the one program of the loop that a
            # fill does not run: it waits for the first write into a page a
            # prefix shares, thousands of ticks on, and the tick that needs
            # it first then stands still while it compiles.  Page 0 onto
            # itself now: the same program, nothing moved
            state = self._ps["state"]
            self._ps["state"] = state._replace(
                caches=pred._run_forks(state.caches, [(0, 0)]))
        for g in pred._manager.groups:      # a pool's size: once a session
            self._m_pages_total.labels(group=g.name).set(g.pool_pages)
        for counts, (_, held, nodes) in self._m_state.items():
            if nodes:
                held.set(slots * pred.state_row_bytes(counts))
        return self._ps

    def serve_reset(self):
        """Close the paged session (pools, manager, batch state).  The
        next :meth:`serve_open` starts cold — same compiled programs,
        fresh memory manager.  The predictor's manager is dropped NOW,
        not at reopen: a fleet router polls :meth:`serve_summary`
        before the first tick, and scoring prompts against the previous
        session's ghost chains would mis-route the whole first burst.  A
        step still unread is read and delivered first: a request whose last
        token it holds is closed, not lost."""
        self._settle()
        self._ps = None
        if getattr(self._pred, "_paged", False):
            self._pred._manager = None

    @property
    def has_work(self):
        """Whether the paged session still has queued, mid-prefill or
        decoding requests, or tokens on the device that no tick has read."""
        if self._ps is None:
            return bool(self._queue)
        ps = self._ps
        return bool(self._queue or ps["active"] or ps["pending"]
                    or ps["unread"])

    def serve_results(self, clear=True):
        """``{rid: np.int32 tokens}`` finished since the session opened
        (or since the last ``clear``).  Reads first what the last tick left
        unread, so the answer is that of a loop that reads every tick at
        its end."""
        if self._ps is None:
            return {}
        self._settle()
        out = dict(self._ps["results"])
        if clear:
            self._ps["results"].clear()
        return out

    def _run_paged(self):
        """Drain the local queue through the tick loop (fresh session
        per call — :meth:`run`'s historical contract)."""
        self.serve_reset()
        self.serve_open()
        while self.has_work:
            self.serve_tick()
        return self.serve_results(clear=True)

    def _paged_limit(self):
        limit = self._pred.cache_len
        prop = self._proposer
        if prop is not None and getattr(prop, "cache_len", None):
            limit = min(limit, prop.cache_len + 1)
        return limit

    def _on_retire_paged(self, ps):
        def on_retire(slot):
            ps["act_mask"][slot] = 0
            # pages back to the pool NOW — the very next admission
            # gate sees them (not "at next admission")
            self._pred._manager.free_slot(slot)
        return on_retire

    def _admit_one(self, ps):
        """Gate the queue head: a fresh prompt starts chunked prefill
        (returns its pending dict, stored in ``ps``); a restorable
        record (swap-in / migrated prefill) installs its pages and the
        slot activates immediately (returns True).  None = the pool
        cannot cover it yet (backpressure)."""
        mgr = self._pred._manager
        entry = self._queue[0]
        if entry["swap"] is not None:
            return self._try_restore(ps, entry)
        rid, prompt, cap = entry["rid"], entry["prompt"], entry["cap"]
        gate = mgr.gate(prompt, prompt.size, cap, self._spec_k)
        if gate is None:
            return None
        self._queue.popleft()
        matched, pages, reserve_n = gate
        slot = next(s for s in range(self._slots)
                    if s not in ps["active"])
        mgr.map_slot(slot, pages, reserve_n)
        self._req[rid]["admit"] = time.time()
        _obs.instant("admit", cat="serve",
                     args={"rid": rid, "slot": slot,
                           "prefix_matched": int(matched)})
        ps["pending"] = {"slot": slot, "rid": rid,
                         "prompt": np.asarray(prompt).reshape(-1)
                         .astype(np.int64), "cap": cap,
                         "prio": entry["prio"], "pos": int(matched)}
        return ps["pending"]

    def _try_restore(self, ps, entry):
        """Admit a :class:`~mxnet_tpu.serve.swap.SwappedRequest` by
        restoring its pages: reserve through the normal gate, allocate
        fresh pages at the SAME ring positions, scatter the saved
        contents back (one traced install program), splice lens/tok.
        Zero prefill, zero retraces; bit-parity with the pre-swap pool
        (``_verify_restore`` re-extracts and asserts it in tests)."""
        import jax.numpy as jnp

        pred = self._pred
        mgr = pred._manager
        rec = entry["swap"]
        if getattr(rec, "kv_heads", None) != pred._grouped_kv_heads:
            # page planes are raw pool bytes with no head structure of
            # their own — installing a grouped record into an MHA host
            # (or across different G) would silently misread every page
            raise MXNetError(
                "swap restore: record kv layout (kv_heads=%r) does not "
                "match this host's (kv_heads=%r)"
                % (rec.kv_heads, pred._grouped_kv_heads))
        m = mgr.pages_per_slot
        remaining = max(rec.cap - len(rec.delivered), 0)
        total = rec.lens + remaining + self._spec_k + 1
        target = min(-(-min(total, pred.cache_len)
                       // mgr.page_tokens), m)
        # a record that re-publishes its prompt chain AND will wrap must
        # budget one fork per prompt page up front (the gate's
        # budget_wrap_forks rule): a later request may map the published
        # pages, turning the wrap recycle into a copy-on-write fork
        fork = -(-rec.prompt.size // mgr.page_tokens) \
            if rec.publish and total > pred.cache_len else 0
        need = rec.n_pages + max(target - rec.n_pages, 0) + fork
        if not mgr.gate_pages(need):
            return None
        self._settle()      # the splice below joins a batch that is read
        self._queue.popleft()
        slot = next(s for s in range(self._slots)
                    if s not in ps["active"])
        row = mgr.restore_slot(slot, rec.row_valid, need)
        state = ps["state"]
        caches = pred.install_pages(state.caches, row, rec.data)
        lens2, tok2 = pred._commit_fn(
            state.lens, state.tok, np.int32(slot),
            jnp.asarray([rec.lens], jnp.int32),
            jnp.asarray([[rec.tok]], jnp.int32))
        ps["state"] = DecodeState(caches, lens2, tok2)
        if self._verify_restore:
            back = pred.extract_pages(ps["state"].caches, row)
            import jax.tree_util as jtu

            for a, b in zip(jtu.tree_leaves(back),
                            jtu.tree_leaves(rec.data)):
                assert np.array_equal(
                    np.asarray(a)[rec.row_valid],
                    np.asarray(b)[rec.row_valid]), \
                    "restored pages are not bit-identical"
        if rec.publish:
            mgr.publish(slot, rec.prompt, rec.prompt.size)
        if self._proposer is not None \
                and getattr(self._proposer, "needs_prefill", False):
            import jax

            ps["key"], sub = jax.random.split(ps["key"])
            self._proposer.admit(
                _pad_window(rec.prompt, self._max_prefill),
                rec.prompt.size, slot, self._slots, sub)
        rid = entry["rid"]
        req = self._req[rid]
        req["admit"] = time.time()
        if rec.first_ts is not None:
            req["first"] = rec.first_ts
        else:
            req["first"] = req["admit"]
        ps["histories"][slot] = hist = list(rec.history)
        ps["active"][slot] = {"rid": rid, "toks": list(rec.delivered),
                              "cap": rec.cap, "prio": rec.priority,
                              "prompt": rec.prompt, "hist": hist,
                              "unread": 0}
        ps["slot_lens"][slot] = rec.lens
        ps["act_mask"][slot] = 1
        if rec.kind == "swap":
            self.swap_ins += 1
        else:
            self._m_migrated_pages.inc(rec.n_pages)
        _obs.instant("swap_in" if rec.kind == "swap" else "page_migrate",
                     cat="serve", args={"rid": rid, "slot": slot,
                                        "pages": rec.n_pages})
        self._retire_finished(ps["active"], ps["results"],
                              self._on_retire_paged(ps))
        return True

    def _swap_out(self, ps, slot):
        """Preempt ``slot``: extract its pages to host RAM (one traced
        program), free them, and hand the restorable record to the
        fleet's preemption callback — or re-queue it locally at the
        back, so the blocked waiter admits and the victim resumes
        later.  Returns the record."""
        from .serve.swap import SwappedRequest

        pred = self._pred
        mgr = pred._manager
        rec = ps["active"][slot]
        row = mgr.tables[slot].copy()
        valid = row != 0
        data = pred.extract_pages(ps["state"].caches, row)
        req = self._req.get(rec["rid"], {})
        record = SwappedRequest(
            rec["prompt"], rec["toks"], ps["histories"][slot],
            rec["cap"], rec["prio"], int(ps["slot_lens"][slot]),
            int(np.asarray(ps["state"].tok)[slot, 0]),
            valid, data, kind="swap",
            submit_ts=req.get("submit"), first_ts=req.get("first"),
            rid=rec["rid"], kv_heads=pred._grouped_kv_heads)
        mgr.free_slot(slot)
        ps["act_mask"][slot] = 0
        ps["slot_lens"][slot] = 0
        del ps["active"][slot]
        del ps["histories"][slot]
        self.swap_outs += 1
        self._m_swapped_pages.inc(record.n_pages)
        _obs.instant("swap_out", cat="serve",
                     args={"rid": record.rid, "slot": int(slot),
                           "pages": record.n_pages})
        if self._preempt_cb is not None:
            # the SLO record travels WITH the record (submit/first ts);
            # the readmitting host creates its own — drop ours or a
            # fleet host under preemption churn leaks one _req entry
            # per swap-out forever (never retired, never pruned)
            self._req.pop(record.rid, None)
            self._preempt_cb(record)
        else:
            self._queue.append({"rid": record.rid,
                                "prompt": record.prompt,
                                "cap": record.cap,
                                "prio": record.priority,
                                "swap": record})
        return record

    def _preempt_for_waiter(self, ps, allow_bound):
        """ONE copy of the preemption rule, for both blocking modes
        (slot-full and pool-gate-blocked): the queue head evicts the
        lowest-priority (then longest-running) slot when it strictly
        outranks it — or, with ``allow_bound``, when the fair-admission
        bound has been exceeded.  Swaps, re-admits, resets the blocked
        counter on success; returns the re-admission result (None = no
        preemption or still blocked)."""
        active = ps["active"]
        if not (self._swap_armed and active and self._queue):
            return None
        victim = min(active,
                     key=lambda s: (active[s]["prio"],
                                    -int(ps["slot_lens"][s])))
        bound_hit = allow_bound and self._fair_bound > 0 \
            and ps["blocked"] >= self._fair_bound
        if active[victim]["prio"] >= self._queue[0]["prio"] \
                and not bound_hit:
            return None
        if ps["unread"] is not None:
            # a swap-out takes the victim's tokens with it: read them first
            # (the choice above needed none).  An EOS among them frees a
            # slot and its pages, and then the waiter may need nobody's
            n = len(active)
            self._settle()
            if len(active) < n:
                # (1a) leaves the freed slot to the gate that follows it
                return self._admit_one(ps) if allow_bound else None
        self._swap_out(ps, victim)
        got = self._admit_one(ps)
        # one swap per bound window: the counter restarts even when the
        # waiter is still blocked, so preemption cannot cascade through
        # every resident in consecutive ticks
        ps["blocked"] = 0
        return got

    def serve_tick(self):
        """ONE iteration of the paged serving schedule.

        (1) gate at most one queued request through the page allocator —
        reservation failure is BACKPRESSURE, the request stays queued
        until retirements free pages; fair admission: after
        ``MXNET_FLEET_DECODE_BOUND`` consecutive gate-blocked decode
        iterations the lowest-priority (then longest) slot is preempted
        to host RAM (``MXNET_FLEET_SWAP``), so a long decode can no
        longer wedge the admission gate; (2) advance the in-flight
        admission by ONE prefill chunk (prefix-cache-matched pages were
        mapped at the gate, only the tail computes), so a long prompt
        interleaves with decode instead of stalling the batch; (3) on
        the final chunk, splice the first token/length into the batch
        state on the device, publish the prompt's pages to the prefix
        cache and activate the slot; (4) queue THIS tick's decode (or
        speculative verify) step over the active slots, inactive rows
        masked; (5) read, deliver and settle what the PREVIOUS tick left
        on the device — its step's tokens, the first token of a slot it
        committed and what its programs counted, one transfer — and return.
        The device has step n behind step n - 1 before the host waits for
        n - 1, so the host's part of a tick runs beside the device's.

        What lags, and what does not.  A token of step n reaches its
        request during tick n + 1 (or at the next :meth:`serve_results`,
        :meth:`stats`, :meth:`serve_reset`, :meth:`inject`, which read
        first).  A request that ends by its CAP ends at a count the host
        holds: it leaves its slot as its last step is queued, its pages and
        slot are free for the next tick's admission, and its record closes
        when that token has been read.  A request that ends by ``eos_id``
        is seen one step late: the slot rides the next step, that row is
        dropped (``mx_serve_dropped_rows_total``) and the slot retires at
        the read, with the tokens it would have had; where the dropped
        row's position would need a page beyond the slot's reservation the
        tick reads first.  What needs the tokens reads first, chosen from
        what the loop sees and not from an option: with a proposer (drafts
        come from the histories) a tick reads its own step at its end, tick
        for tick the order before; a swap-out and a restore read before
        they touch the batch.  ``mx_serve_ticks_total{read=}`` counts the
        ticks that queued their programs ``behind`` an unread step and
        those that had nothing unread (``first``); the ``serve.tick``
        span's arguments carry the same word.  Every device program here
        was traced once — page tables, active masks, slot indices, page
        ids and swapped page contents are all data.

        Host phases land on the timeline as one ``serve.tick`` span with
        the children ``serve.admit`` / ``serve.prefill`` /
        ``serve.commit`` / ``serve.decode_dispatch`` / ``serve.readback``
        (the wait for the previous tick's step) / ``serve.deliver``
        (docs/observability.md); a request's spans share its ``rid``.
        """
        ps = self.serve_open()
        ps["tick"] += 1
        args = {"tick": ps["tick"]}
        with _obs.top_span("serve.tick", cat="serve", args=args):
            args["read"] = self._tick(ps)
        self._m_ticks.labels(read=args["read"]).inc()

    def _tick(self, ps):
        import jax
        import jax.numpy as jnp

        pred = self._pred
        mgr = pred._manager
        slots = self._slots
        greedy = pred._greedy

        def next_key():
            # greedy sampling never reads the key: skip the per-tick
            # split dispatches (a measurable slice of small-batch serve)
            if greedy:
                return pred._zero_key
            ps["key"], sub = getattr(pred, "_split_fn",
                                     jax.random.split)(ps["key"])
            return sub

        active = ps["active"]
        histories = ps["histories"]
        slot_lens = ps["slot_lens"]
        act_mask = ps["act_mask"]
        proposer = self._proposer
        k = self._spec_k
        limit = self._paged_limit()
        on_retire = self._on_retire_paged(ps)

        def retire():
            self._retire_finished(active, ps["results"], on_retire)

        def leave_due():
            """Retirement by count, taken at dispatch: a request whose
            delivered and queued tokens reach its cap leaves its slot now
            (masked out of the next step, pages and slot free for the next
            admission); :meth:`_settle` closes it at its last token."""
            for slot, rec in list(active.items()):
                if len(rec["toks"]) + rec["unread"] >= rec["cap"]:
                    del active[slot]
                    on_retire(slot)
                    self._count_out(rec)

        deliver = self._deliver
        # what this tick queues and leaves on the device for the next tick
        # (or a drain) to read: ps["unread"] once the tick is over
        cur = {"firsts": [],    # (record, device token) of a commit
               "moe": [],       # (program, device row counts)
               "ssm": None,     # device count of state rows stepped
               "counts": {},    # {name: device count} of the step's others
               "toks": None,    # the step's tokens, a copy not donated on
               "accepts": None,     # a self-drafting step: how many of its
                                    # two tokens a slot each row committed
               "rows": [],      # (slot, record) the step computed for
               "note": {}}      # the arguments of its serve.readback span

        with _obs.span("serve.admit", cat="serve"):
            # --- (1a) slot-full priority preemption: a waiter that OUTRANKS
            # the lowest-priority resident evicts it even when the block is
            # slots, not pages — priority scheduling; equal priorities keep
            # the classic wait-for-retirement behavior
            if ps["pending"] is None and len(active) >= slots:
                self._preempt_for_waiter(ps, allow_bound=False)
            # --- (1) admission gate: one request starts (or restores)
            if ps["pending"] is None and self._queue and len(active) < slots:
                got = self._admit_one(ps)
                if got is None:
                    ps["blocked"] += 1
                    if not active:
                        # nothing running to free pages: spill the whole
                        # prefix cache, then the pool is genuinely too small
                        if mgr.prefix_cache is not None:
                            mgr.prefix_cache.evict(mgr.pool_pages)
                            got = self._admit_one(ps)
                        if got is None:
                            raise MXNetError(
                                "KV page pool (%d pages) cannot admit a "
                                "%d-token request even with an empty batch "
                                "— raise MXNET_KV_POOL_PAGES"
                                % (mgr.pool_pages,
                                   self._queue[0]["prompt"].size))
                    else:
                        # pool-gate preemption: a HIGHER-priority waiter
                        # evicts immediately; any waiter evicts the
                        # lowest-priority slot once the gate has blocked
                        # MXNET_FLEET_DECODE_BOUND consecutive iterations.
                        # The waiter admits on the freed pages and the
                        # victim resumes bit-exactly
                        got = self._preempt_for_waiter(ps, allow_bound=True)
                if got is not None:
                    ps["blocked"] = 0
        # --- (2) one prefill chunk of the in-flight admission
        if ps["pending"] is not None:
            p = ps["pending"]
            state = ps["state"]
            n = min(self._chunk_w, p["prompt"].size - p["pos"])
            # the output head runs in the chunk that ends the prompt alone
            ends = bool(p["pos"] + n >= p["prompt"].size)
            where = {"rid": p["rid"], "slot": p["slot"], "pos": p["pos"],
                     "tokens": int(n), "head": ends}
            self._m_chunks.labels(head="run" if ends else "skipped").inc()
            with _obs.span("serve.prefill", cat="serve", args=where):
                copies = mgr.ensure(p["slot"], p["pos"], p["pos"] + n)
                caches = pred._run_forks(state.caches, copies) \
                    if copies else state.caches
                sub = next_key()
                if self._mtp:
                    # the block reads each row's next token too: the
                    # prompt's own after the chunk, none where it ends
                    caches, probs, tok, draft, dprobs, _, *moe = \
                        pred.mtp_chunk(caches, p["slot"], p["prompt"],
                                       p["pos"], self._chunk_w, sub)
                else:
                    args = (pred._env, caches) + pred._chunk_operands(
                        p["slot"], p["prompt"][p["pos"]:p["pos"] + n],
                        p["pos"], self._chunk_w) + (
                            np.asarray([ends], np.int32), sub)
                    # its dispatch's span is named "prefill"; only the
                    # scope map knows the chunk program by its own name
                    pred._register_hlo("prefill_chunk", pred._chunk_fn,
                                       args)
                    with _obs.program_span("prefill"):
                        caches, probs, tok, *moe = pred._chunk_fn(*args)
                if moe:
                    cur["moe"].append(("chunk", moe[0]))
                self._m_ssm_chunk_tokens.inc(int(n) * self._ssm_nodes)
                if self._latent_nodes:
                    self._note_latent_rows(self._chunk_w, p["pos"] + n)
                ps["state"] = state = DecodeState(
                    caches, state.lens, state.tok, draft=state.draft,
                    draft_probs=state.draft_probs)
                p["pos"] += n
                pred._chunk_widths.add(self._chunk_w)
            if p["pos"] >= p["prompt"].size:
                # --- (3) commit: the slot joins the batch.  The splice
                # takes the first token where it is, on the device; the
                # host reads it with the tokens of the step that is queued
                # behind the chunk, one tick on.  A proposer drafts from
                # the histories, so with one it is read here
                with _obs.span("serve.commit", cat="serve",
                               args={"rid": p["rid"]}):
                    slot, plen = p["slot"], p["prompt"].size
                    if self._mtp:
                        ps["state"] = pred.mtp_commit(state, slot, plen, tok,
                                                      draft, dprobs)
                    else:
                        lens2, tok2 = pred._commit_fn(
                            state.lens, state.tok, np.int32(slot),
                            jnp.asarray([plen], jnp.int32), tok)
                        ps["state"] = DecodeState(state.caches, lens2, tok2)
                    mgr.publish(slot, p["prompt"], plen)
                    if proposer is not None \
                            and getattr(proposer, "needs_prefill", False):
                        ps["key"], sub = jax.random.split(ps["key"])
                        proposer.admit(
                            _pad_window(p["prompt"], self._max_prefill),
                            plen, slot, slots, sub)
                    histories[slot] = hist = list(p["prompt"])
                    rec = active[slot] = {
                        "rid": p["rid"], "toks": [], "cap": p["cap"],
                        "prio": p["prio"], "prompt": p["prompt"],
                        "hist": hist,   # histories[slot], while it holds it
                        "unread": 0}    # tokens queued for it and not read
                    slot_lens[slot] = plen
                    act_mask[slot] = 1
                    ps["pending"] = None
                    if proposer is not None:
                        first = int(np.asarray(tok)[0, 0])
                        rec["toks"].append(first)
                        hist.append(first)
                        self._req[rec["rid"]]["first"] = time.time()
                        retire()    # a first-token EOS / cap-1 request
                    else:
                        cur["firsts"].append((rec, tok))
                        rec["unread"] = 1
                        leave_due()     # a cap of one: it rides no step
        self._note_gauges()
        # --- (4) this tick's decode / verify step over the active slots
        can_spec = bool(active) and proposer is not None and k > 0 \
            and ps["pending"] is None \
            and max(slot_lens[s] for s in active) + k + 1 <= limit
        if self._eos_id is not None and ps["unread"] is not None \
                and not all(mgr.within_reserve(
                    s, int(slot_lens[s]), int(slot_lens[s]) + 1 + self._mtp)
                    for s in active):
            # a slot whose EOS is still unread would ride this step, and the
            # page its row needs is beyond what it reserved: find out first,
            # so that a dropped row can take nobody's page
            self._settle()
        if can_spec:
            sub = next_key()
            with _obs.span("serve.decode_dispatch", cat="serve"):
                hists = [histories.get(s) or [0] for s in range(slots)]
                draft_toks, draft_probs = proposer.propose(
                    hists, ps["state"], slot_lens, sub)
                sub = next_key()
                state, out, counts = pred.paged_verify(
                    ps["state"], slot_lens, draft_toks, draft_probs, sub,
                    act_mask)
                ps["state"] = state
            with _obs.span("serve.readback", cat="serve"):
                out_h = np.asarray(out)
                counts_h = np.asarray(counts).astype(np.int64)
            with _obs.span("serve.deliver", cat="serve"):
                self._note_step(spec=True)
                for slot, rec in active.items():
                    emitted = out_h[slot, :counts_h[slot]]
                    self._note_accept(k, int(counts_h[slot]) - 1)
                    deliver(rec, emitted)
                    histories[slot].extend(int(t) for t in emitted)
                slot_lens += counts_h
                retire()
        elif active:
            sub = next_key()
            with _obs.span("serve.decode_dispatch", cat="serve"):
                if self._mtp:
                    # verify the last draft and leave the next, in one
                    # program: one or two tokens a slot, how many the host
                    # learns one tick on.  Until then ``slot_lens`` holds
                    # the most a slot can have (two a tick), which
                    # :meth:`_settle` takes back down; the step's tokens
                    # and counts are results of its own, not donated on
                    behind = 2 if ps["unread"] and ps["unread"]["rows"] \
                        else 0
                    state, cur["toks"], cur["accepts"] = \
                        pred.paged_mtp_step(ps["state"], slot_lens, sub,
                                            act_mask, behind=behind)[:3]
                else:
                    state, _ = pred.paged_step(ps["state"], slot_lens, sub,
                                               act_mask)
                    # state.tok is donated into the next step, which is
                    # queued before the host reads this one: it reads a copy
                    cur["toks"] = pred._keep_fn(state.tok)
                ps["state"] = state
            if state.moe is not None:
                cur["moe"].append(("decode", state.moe))
            cur["ssm"] = state.ssm
            cur["counts"] = state.counts or {}
            cur["rows"] = list(active.items())
            for rec in active.values():
                rec["unread"] += 1      # at least: what leave_due counts on
            self._note_attn_blocks(slot_lens, act_mask, cur["note"])
            if self._latent_nodes:
                self._note_latent_rows(
                    1 + self._mtp, np.where(act_mask > 0, slot_lens + 1, 0),
                    cur["note"])
            self._note_step(spec=self._mtp)
            slot_lens += (1 + self._mtp) * act_mask.astype(np.int64)
            leave_due()
        # --- (5) read the PREVIOUS tick: its step is done or running, and
        # this tick's programs are queued behind it
        read = "behind" if ps["unread"] is not None else "first"
        self._settle()
        if cur["firsts"] or cur["moe"] or cur["rows"]:
            ps["unread"] = cur
        if proposer is not None:
            self._settle()      # the drafts of the next tick need these
        return read

    def _settle(self):
        """Read what the last tick left on the device (``ps["unread"]``:
        its step's tokens, the first token of a slot it committed, what its
        programs counted — one transfer, a wait for that tick's programs
        alone), deliver the tokens, and close what they end: a request that
        left its slot by count and now holds its last token; a slot whose
        token is the EOS, which retires here.  Nothing unread: nothing
        done."""
        ps = self._ps
        if ps is None or ps["unread"] is None:
            return
        import jax

        fl, ps["unread"] = ps["unread"], None
        note = fl["note"]       # filled below too, read as the span closes
        firsts, moe, rows = fl["firsts"], fl["moe"], fl["rows"]
        with _obs.span("serve.readback", cat="serve", args=note):
            names = sorted(fl["counts"])
            got = jax.device_get(
                [tok for _, tok in firsts] + [vec for _, vec in moe]
                + [a for a in (fl["toks"], fl["accepts"], fl["ssm"])
                   if a is not None]
                + [fl["counts"][name] for name in names])
            now = time.time()
            for name in reversed(names):
                note[name] = int(got.pop())
            self._note_counts(note)
            if fl["ssm"] is not None:
                note["ssm_rows"] = int(got.pop())
                self._m_state["ssm_rows"][0].inc(note["ssm_rows"])
            accepts = got.pop() if fl["accepts"] is not None else None
            toks = got.pop() if fl["toks"] is not None else None
            if accepts is not None:
                # a draft a live row, and what the device took and
                # committed of them (a cap or an EOS inside a pair may cut
                # what reaches the request: mx_serve_tokens counts that)
                live = [slot for slot, rec in rows if not rec.get("closed")]
                took = int(sum(accepts[slot] for slot in live)) - len(live)
                self._note_accept(len(live), took)
                note.update(spec_proposed=len(live), spec_accepted=took,
                            tokens_committed=len(live) + took)
            self._note_moe([(program, vec) for (program, _), vec
                            in zip(moe, got[len(firsts):])], note)
        with _obs.span("serve.deliver", cat="serve"):
            for (rec, _), tok in zip(firsts, got):
                first = int(tok[0, 0])
                rec["unread"] -= 1
                rec["toks"].append(first)
                rec["hist"].append(first)
                self._req[rec["rid"]]["first"] = now
            dropped = 0
            for slot, rec in rows:
                # one token a row; a self-drafting step's one or two
                n = 1 if accepts is None else int(accepts[slot])
                if accepts is not None and ps["active"].get(slot) is rec:
                    ps["slot_lens"][slot] -= 2 - n
                if rec.get("closed"):   # retired at its EOS, a step ago
                    dropped += 1
                    continue
                rec["unread"] -= 1
                held = len(rec["toks"])
                self._deliver(rec, toks[slot, :n])
                dropped += len(rec["toks"]) == held
                rec["hist"].extend(int(t) for t in toks[slot, :n])
            if dropped:
                self._m_dropped.inc(dropped)
            for rec in [r for r, _ in firsts] + [r for _, r in rows]:
                # out of its slot since its last step was queued
                if "counted" in rec and not rec.get("closed"):
                    if self._ended(rec):
                        self._close(rec, ps["results"])
                    else:
                        self._count_out(rec)
            self._retire_finished(ps["active"], ps["results"],
                                  self._on_retire_paged(ps))

    def _note_gauges(self):
        """Refresh the per-host queue-depth / free-page gauges (the
        router's load + headroom signals)."""
        self._m_queue_depth.set(len(self._queue))
        mgr = getattr(self._pred, "_manager", None)
        if mgr is not None:
            self._m_free_pages.set(mgr.allocator.free_pages)
            for g in mgr.groups:
                self._m_pages_in_use.labels(group=g.name).set(
                    g.allocator.used_pages)
