"""Megatron-style tensor-parallel planning over the 'model' mesh axis.

The round-3 executor column-sharded *every* parameter whose leading dim
divided the model axis — correct under GSPMD but communication-naive: the
partitioner inserts an all-gather after every layer to re-replicate
activations.  The Megatron pairing (column-parallel FC1, row-parallel FC2 —
Shoeybi et al., and the scaling-book "1D weight-stationary" recipe) leaves
the intermediate activation feature-sharded so one all-reduce per *pair*
replaces per-layer all-gathers.

This module derives that pairing from the graph rather than from user
annotations: a single topological walk tracks whether each activation is
feature-sharded ('feat': last/channel dim split over 'model') or replicated
('rep'), and assigns each FullyConnected / Convolution weight a column or
row role accordingly:

    input 'rep'  -> column parallel: W[out_dim] on 'model', bias sharded,
                    output becomes 'feat'          (no collective)
    input 'feat' -> row parallel: W[in_dim] on 'model', bias replicated,
                    output 'rep'                   (one psum, from GSPMD)

Elementwise ops (Activation, Dropout, Cast, adds) propagate 'feat';
BatchNorm on a 'feat' activation shards its per-channel params/aux the same
way (its statistics reductions are per-channel, so they stay local); any
other op conservatively resets to 'rep', which GSPMD realizes with an
all-gather exactly where the naive plan paid one per layer.

The result is a {param_name: partition-axes-tuple} plan consumed by
DataParallelExecutorGroup._param_sharding; communication is *measured* by
``parallel.hlo_stats`` (collective count/bytes from compiled HLO) — see
tests/test_tensor_parallel.py and tools/bandwidth.py.
"""
from __future__ import annotations

__all__ = ["plan_tensor_parallel", "kv_cache_pspec", "kv_pool_pspec",
           "ELEMENTWISE_OPS"]

# ops through which a feature-sharded activation stays feature-sharded
# (their compute is pointwise over the sharded dim, or reduces other dims)
ELEMENTWISE_OPS = {
    "Activation", "LeakyReLU", "Dropout", "Cast", "relu", "sigmoid", "tanh",
    "exp", "log", "negative", "abs", "_plus", "_minus", "_mul", "_div",
    "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div",
    "_plus_scalar", "_minus_scalar", "_mul_scalar", "_div_scalar",
    "_maximum", "_minimum", "clip", "identity", "BlockGrad", "stop_gradient",
}


def _kv_head_axis(sizes, head_axis, num_kv_heads, what, degrades=None):
    """The trailing-dim mesh axis for a K/V cache/pool, kv-head aware.

    MHA (``num_kv_heads`` None/0) keeps the unconditional E-split.  A
    grouped layout's trailing dim is H_kv head slices, so the E-split IS
    an H_kv-split: legal only when ``num_kv_heads % axis_size == 0``.
    Otherwise degrade VISIBLY to replicated-group sharding (every model
    shard holds all H_kv kv heads; q heads still split) with a warning —
    wrong-but-silent sharding of a grouped pool would interleave kv-head
    slices across shards and score q-heads against the wrong group.
    ``degrades``, when a list, also records the event as
    ``{"site", "reason"}`` for the artifact's ``replicated_degrades``
    meta, which the sharding-coverage lint pass surfaces.
    """
    size = sizes.get(head_axis, 1)
    if size <= 1:
        return None
    if num_kv_heads:
        kvh = int(num_kv_heads)
        if kvh % size:
            import warnings

            warnings.warn(
                "%s: num_kv_heads=%d not divisible by %r axis size %d — "
                "degrading to replicated-group sharding (each model shard "
                "holds the full grouped K/V)" % (what, kvh, head_axis,
                                                 size))
            if degrades is not None:
                degrades.append({
                    "site": what,
                    "reason": "num_kv_heads=%d %% %s=%d != 0"
                    % (kvh, head_axis, size)})
            return None
    return head_axis


def kv_cache_pspec(mesh_shape, batch_axis="data", head_axis="model",
                   num_kv_heads=None, degrades=None):
    """PartitionSpec for a (B, C, E_kv) decode KV cache on a mesh.

    The Megatron invariant this module's plan rests on — an E-split IS a
    head-group split (heads are contiguous hd-wide slices of E) — carries
    straight to the cache: shard the trailing E dim on ``head_axis`` and
    each model shard holds, appends to, and scores only its own head
    group's K/V slice, with zero collectives in the decode step (the Pope
    et al. inference sharding).  The ring-slot dim stays replicated
    (appends index it dynamically); the batch dim shards on ``batch_axis``
    so serving slots spread over the data axis.  Axes of size 1 drop out.

    ``num_kv_heads`` (grouped-query caches) gates the trailing split on
    ``num_kv_heads % axis == 0``; otherwise the kv dim degrades visibly
    to replicated (see :func:`_kv_head_axis`).
    """
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh_shape)
    return P(batch_axis if sizes.get(batch_axis, 1) > 1 else None, None,
             _kv_head_axis(sizes, head_axis, num_kv_heads,
                           "kv_cache_pspec", degrades=degrades))


def kv_pool_pspec(mesh_shape, head_axis="model", num_kv_heads=None,
                  degrades=None):
    """PartitionSpec for a (P, page_tokens, E_kv) paged KV pool on a mesh.

    Same Megatron invariant as :func:`kv_cache_pspec` — the trailing E dim
    shards on ``head_axis`` so each model shard holds and scores only its
    own head group's slice of every page.  The page dim replicates: pages
    are a GLOBAL id space shared by every serving slot (batch never enters
    the pool's shape — slots meet the pool through their page tables), so
    there is no batch axis to spread, and the page-id gathers/scatters
    stay local per shard.  Axes of size 1 drop out.  ``num_kv_heads``
    behaves as in :func:`kv_cache_pspec`.
    """
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh_shape)
    return P(None, None,
             _kv_head_axis(sizes, head_axis, num_kv_heads,
                           "kv_pool_pspec", degrades=degrades))


def plan_tensor_parallel(symbol):
    """One topological walk -> {param_name: partition axes tuple}.

    Axes tuples use the mesh axis name 'model' (e.g. ``('model', None)`` for
    a column-parallel FC weight); params absent from the plan replicate.
    Divisibility of the sharded dim is checked by the consumer at placement
    time, per param — an unshardable member of a pair degrades to
    replicated without breaking correctness (GSPMD re-derives).
    """
    plan = {}
    state = {}  # (id(node), out_idx) -> 'rep' | 'feat'

    def instate(entry):
        return state.get((id(entry[0]), entry[1]), "rep")

    for node in symbol._topo():
        if node.is_variable:
            state[(id(node), 0)] = "rep"
            continue
        attrs = node.parsed_attrs()
        n_args = node.op.n_inputs(attrs)
        ins = node.inputs[:n_args]
        aux_ins = node.inputs[n_args:]
        name = node.op.name
        out_state = "rep"

        if name == "FullyConnected":
            data_st = instate(ins[0])
            wnode = ins[1][0]
            bnode = ins[2][0] if len(ins) > 2 else None
            if wnode.is_variable:
                if data_st == "feat":
                    # row parallel: contract over the sharded feature dim,
                    # GSPMD inserts the pair's single psum here
                    plan[wnode.name] = (None, "model")
                    out_state = "rep"
                else:
                    plan[wnode.name] = ("model", None)
                    if bnode is not None and bnode.is_variable:
                        plan[bnode.name] = ("model",)
                    out_state = "feat"
        elif name == "Convolution":
            data_st = instate(ins[0])
            wnode = ins[1][0]
            bnode = ins[2][0] if len(ins) > 2 else None
            if wnode.is_variable and attrs.get("num_group", 1) == 1:
                if data_st == "feat":
                    # row parallel over input channels (OIHW dim 1)
                    plan[wnode.name] = (None, "model", None, None)
                    out_state = "rep"
                else:
                    plan[wnode.name] = ("model", None, None, None)
                    if bnode is not None and bnode.is_variable:
                        plan[bnode.name] = ("model",)
                    out_state = "feat"
        elif name == "BatchNorm":
            data_st = instate(ins[0])
            if data_st == "feat":
                for pnode, _ in ins[1:]:
                    if pnode.is_variable:
                        plan[pnode.name] = ("model",)
                for anode, _ in aux_ins:
                    plan[anode.name] = ("model",)
                out_state = "feat"
        elif name == "Pooling":
            # pooling reduces spatial dims only — the channel dim (NCHW or
            # NHWC alike) is untouched, so a feature-sharded activation
            # stays feature-sharded through it (round-4 verdict: the walk
            # reset here and an all-gather appeared after every pool)
            out_state = instate(ins[0])
        elif name == "Embedding":
            # Megatron vocab-dim sharding: each device holds a vocab slice,
            # GSPMD realizes the lookup as masked-local-gather + one psum,
            # and the REPLICATED output lets the following q/k/v
            # projections start column-parallel (feature-dim sharding here
            # would instead force them row-parallel: three psums where the
            # attention block needs one)
            wnode = ins[1][0]
            if wnode.is_variable:
                plan[wnode.name] = ("model", None)
                out_state = "rep"
        elif name == "dot_product_attention":
            # Megatron attention: with q/k/v all feature-sharded (their
            # projections column-parallel over heads), each device computes
            # attention for ITS head group locally — the op's (B,T,E) ->
            # (B,T,H,hd) reshape maps an E-split to an H-split — and the
            # output stays 'feat', so the out-projection becomes
            # row-parallel and the whole block costs ONE psum.  Head-count
            # divisibility by the mesh axis is GSPMD's to realize; a
            # non-divisible split degrades to resharding, never to wrong
            # numbers.
            sts = [instate(e) for e in ins]
            out_state = "feat" if sts and all(s == "feat" for s in sts) \
                else "rep"
        elif name in ELEMENTWISE_OPS:
            sts = [instate(e) for e in ins]
            out_state = "feat" if sts and all(s == "feat" for s in sts) \
                else "rep"

        for i in range(node.op.n_outputs(attrs)):
            state[(id(node), i)] = out_state
    return plan
