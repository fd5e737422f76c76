"""Mixture-of-Experts operator — the 'expert' mesh axis made real.

No reference analog (SURVEY §2.5: "Tensor/expert parallelism: not present
in any form") — this is a leapfrog op like attention.  ``MoEFFN`` is a
routed expert feed-forward layer:

    gate   = softmax(x @ Wg)                      # (N, E) router
    choice = top-k(gate)                          # k = num_experts_per_tok
    y      = sum_k gate_k * FFN_{choice_k}(x)     # gated expert mixture

Top-1 (the default) is switch routing with the raw chosen probability as
the gate; k > 1 renormalizes the chosen gates to sum to one (the
Mixtral/GShard convention), so k = 1 semantics are unchanged from the
original switch formulation.

Three dispatch shapes, one routing rule:

* **dense** (``capacity_factor == 0``): one-hot combine matmuls, every
  expert sees every token — static shapes, MXU-shaped einsums, E× the
  FFN compute.  The oracle the sparse paths are benchmarked against.
* **sparse reference** (``capacity_factor > 0``, no 'expert' mesh):
  capacity-slot dispatch — each expert owns ``C = ceil(cf*k*N_g/E)``
  slots per token group, tokens past capacity DROP (Switch semantics)
  unless ``overflow='dropless'`` stretches the capacity to the
  worst case with a padding mask.  ``num_groups`` splits the tokens into
  contiguous groups with independent capacity quotas — group g of the
  reference IS device g of the sharded path, so the two drop identical
  token sets by construction.
* **sharded** (``capacity_factor > 0`` under a mesh whose 'expert' axis
  is > 1): an explicit ``shard_map`` program — each device routes its
  local tokens, packs them into per-(destination-expert) capacity slots
  of static shape (E, C_loc, d), exchanges them with
  ``jax.lax.all_to_all``, runs only its own experts' FFNs (hidden dim
  optionally Megatron-split over 'model' with one psum), and
  all-to-alls the outputs back for the combine.  The backward pass —
  the op-level ``jax.custom_vjp`` below — differentiates through the
  region, so the two exchanges reappear reversed (an all-to-all's
  transpose is the opposite-direction all-to-all) instead of hoping
  GSPMD synthesizes them from sharding hints.  The mxlint collective
  pass budgets the resulting all-to-all count/bytes per program
  (benchmarks/budgets.json; docs/moe.md has the workflow).

Load balancing: the Switch auxiliary loss (E · Σ_e f_e·P_e) is folded
into the op's own gradient through ``jax.custom_vjp`` with weight
``aux_loss_coeff`` — backward computes the vjp of ``y + coeff * aux`` so
the router receives balancing pressure without any extra loss-head
plumbing (set ``aux_loss_coeff=0`` to disable).
"""
from __future__ import annotations

import math

import numpy as np

from ..attrs import Param, ParamSchema
from ..registry import OpDef, register_op

# which dispatch shape the last MoEFFN trace used ("dense" | "sparse" |
# "sparse_a2a") — path-selection tripwire, same pattern as
# ops.attention.PATH_TAKEN / parallel.ring.RING_PATH
MOE_PATH = {"last": None}

# which capacity-slot assignment algorithm the last sparse trace used
# ("sort" | "onehot") — the MXNET_MOE_DISPATCH tripwire; None until a
# capacity path traces
MOE_DISPATCH = {"last": None}


def _held(attrs):
    """How many of the layer's experts this chip holds (all, unless
    ``num_held`` says fewer)."""
    return int(attrs.get("num_held", 0) or 0) or int(attrs["num_experts"])


def _shared_width(attrs):
    """Width of the always-on MLP beside the routed experts (0 = none):
    ``shared_hidden_size`` where the node states it, else
    ``n_shared_experts`` experts of the routed experts' width, as one MLP."""
    if not int(attrs.get("n_shared_experts", 0) or 0):
        return 0
    return int(attrs.get("shared_hidden_size", 0) or 0) \
        or int(attrs["n_shared_experts"]) * int(attrs["hidden_size"])


def _silu(g):
    import jax

    return jax.nn.silu(g)


def _relu2(u):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(u, 0))


# an expert's body at a chip's share (``expert_act``): the names of its
# matrices, the last the way back; the activation of the first one's product
# (a further matrix in multiplies it: `_hidden`); and whether the matrices in
# are stored as the one back is, (held, h, d), a hidden unit a row.
# ``swiglu`` is ``(silu(x W_g) * (x W_u)) W_d``; ``relu2`` is ``relu(x
# W_u)^2 W_d``, two matrices, both a unit a row: then only the model's width
# is ever a minor dimension, and an expert width that is no whole number of
# lane tiles (1856 = 14.5 x 128) is stored without padding and read by the
# grouped product as it lies (as (held, d, 1856) the chip keeps it with d
# minor, and every call of the kernel pays a transposing copy of the stack)
BODIES = {"swiglu": (("gate", "up", "down"), _silu, False),
          "relu2": (("up", "down"), _relu2, True)}


def _hidden(act, products):
    """What the matrix back takes: ``act`` of the first product in, times
    each further one, drawn from ``products`` as it is needed."""
    products = iter(products)
    h = act(next(products))
    for p in products:
        h = h * p
    return h


def _body(attrs):
    """``(parts, act, unit_rows)`` of the node's ``expert_act``
    (:data:`BODIES`)."""
    act = attrs.get("expert_act") or "swiglu"
    if act not in BODIES:
        raise ValueError("MoEFFN: expert_act must be one of %s; got %r"
                         % (sorted(BODIES), act))
    return BODIES[act]


def _moe_shape(attrs, in_shapes, aux_shapes):
    x = in_shapes[0]
    e = attrs["num_experts"]
    h = attrs["hidden_size"]
    d = x[-1]
    if attrs.get("gated"):
        held = _held(attrs)
        parts, _, unit_rows = _body(attrs)
        ins = len(parts) - 1
        bias = [(e,)] if attrs.get("score_bias") else []
        want = [tuple(x), (d, e)] + bias \
            + [(held, h, d) if unit_rows else (held, d, h)] * ins \
            + [(held, h, d)]
        hs = _shared_width(attrs)
        if hs:
            want += [(hs, d) if unit_rows else (d, hs)] * ins + [(hs, d)]
        return want, [tuple(x)], []
    want = [tuple(x), (d, e), (e, d, h), (e, h), (e, h, d), (e, d)]
    return want, [tuple(x)], []


def _moe_arguments(attrs):
    if attrs.get("gated"):
        parts = _body(attrs)[0]
        return ["data", "gate_weight"] \
            + (["gate_bias"] if attrs.get("score_bias") else []) \
            + ["expert_%s_weight" % part for part in parts] \
            + (["shared_%s_weight" % part for part in parts]
               if _shared_width(attrs) else [])
    return ["data", "gate_weight", "expert1_weight", "expert1_bias",
            "expert2_weight", "expert2_bias"]


# ---------------------------------------------------------------------------
# the gated layer at one chip's share (serving): sigmoid or softmax scores
# over all E experts, top-k with a selection-only bias, experts without
# biases (SwiGLU, or the node's ``expert_act``), of which this chip holds
# [first, first + held)
# ---------------------------------------------------------------------------

# where a caller collects what the gated layers it traces counted: a list
# while `collecting()` is open, else None (the op then records nothing);
# `real` is the caller's mask of the rows that are tokens, or None for all
_STATS = {"sink": None, "real": None}


class collecting:
    """``with collecting(real) as rows:`` — every gated ``MoEFFN`` traced
    inside appends one int32 vector ``[rows held, rows elsewhere, held
    experts with a row]`` to ``rows``.  ``real`` (one 0/1 a row of the
    layer's flattened input, a function that returns it, or None) leaves
    idle slots and a chunk's padding out of all three.  The values are
    tracers of the enclosing trace: sum and return them from the same
    program."""

    def __init__(self, real=None):
        self._real = real

    def __enter__(self):
        self._before = dict(_STATS)
        _STATS["sink"] = rows = []
        _STATS["real"] = self._real
        return rows

    def __exit__(self, *exc):
        _STATS.update(self._before)


def _scores(xt, wr, bias, k, score_func, norm_topk):
    """Routing over all the layer's experts: ``(choice, weight)``, both
    (n, k).  The scores are taken in float32; ``bias`` (E,) moves the
    choice only and is not in the weight."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(xt, wr, preferred_element_type=jnp.float32)
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("MoEFFN: score_func must be 'softmax' or "
                         "'sigmoid'; got %r" % (score_func,))
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    _, choice = jax.lax.top_k(pick, k)
    weight = jnp.take_along_axis(scores, choice, axis=-1)
    if norm_topk:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return choice, weight


# The smallest call, in rows, whose routed product takes the grouped form
# (:func:`grouped_selected`), and the grouped product's tiles: rows of the
# buffer a step, and the most of the contraction and of the columns (a step
# takes the largest divisor of its width that is no larger, so no tile is
# partial: 6144 goes by 2048).  Measured kernel alone on the chip (TPU v5
# lite, jax 0.9.0, bfloat16, 16 held experts; benchmarks/probe_moe_grouped.py,
# PR 52; ms a layer, routing and sum back inside on both sides; uniform
# routing / every row to two held experts; reading the 16 experts once takes
# 0.98, exaone's 1.47):
#   rows             64           256          512          1024         2048
#   4096 x 2048, top 4 of 128 (mistral4)
#     dense          1.16         1.36         2.42         5.28         10.83
#     grouped        1.01 / 0.23  1.19 / 0.34  1.34 / 0.54  1.57 / 1.02  2.53 / 2.31
#   the same, top 8 of 256 (mimo)
#     dense          1.19         1.38         2.42         5.27         10.99
#     grouped        0.94 / 0.23  1.31 / 0.39  1.63 / 0.78  2.32 / 1.70  3.68 / 3.41
#   6144 x 2048, top 8 of 128 (exaone)
#     dense          1.76         2.02         4.06         7.85         16.26
#     grouped        1.79 / 0.30  2.01 / 0.62  2.47 / 1.25  3.92 / 3.04  6.90 / 6.57
# Under 512 rows the two forms lie within 0.25 ms at uniform routing (both
# read every held expert once and that read is the time); from 512 on the
# dense form is bound by its held x n rows of products and the grouped form
# is 1.5 x ahead at the least.  Tiles at 2048 rows of mistral4's shape,
# uniform / skewed: (128, 1024, 512) 2.80 / 3.48, (128, 2048, 512) 2.71 /
# 3.04, (128, 2048, 1024) 2.70 / 3.03, (128, 4096, 512) 2.53 / 2.30, (256,
# 2048, 512) 2.64 / 2.35 (but 1.48 at 512 rows, where 128 reads 1.34: a tile
# that straddles two small groups is multiplied once a group); 64 rows a tile
# 3.66 / 5.09 and (512, 1024, 512) 4.36 / 2.85 before that.  In megablox
# gmm's place jax.lax.ragged_dot read 2.80 / 0.60 at 512 rows and 4.53 / 2.97
# at 2048 (it visits only groups with rows, and loses where every group has
# some): not kept.
GROUPED_MIN_ROWS = 512
GROUPED_TILES = (128, 4096, 512)
LANES = 128
# rows of a bfloat16 matrix's sublane tile (a float32 one's is 8)
SUBLANES = 16
# under this the largest common divisor is no tile to work in: the width
# has few factors of two (2688 = 21 x 128, 1856 = 29 x 64) and `_tile` looks
# further
MIN_TILE = 512


def _tile(cap, width):
    """A step's tile of one ``width`` of a grouped product, ``cap`` the most
    it may be.  The largest divisor the two share where that is a tile worth
    having (every width of whole powers of two: 4096, 2048, 6144 = 3 x
    2048); else the width whole where the cap holds it (a block that is the
    whole dimension needs no alignment), else its largest divisor of whole
    lane tiles under the cap (2688 under 512: 384), else the cap itself, the
    last tile partial (1856 under 512: three tiles and 320 columns)."""
    t = math.gcd(cap, width)
    if t >= min(MIN_TILE, cap):
        return t
    if width <= cap:
        return width
    whole = [w for w in range(cap - cap % LANES, 0, -LANES)
             if width % w == 0]
    return whole[0] if whole else cap


def grouped_selected(n, k, held, d, h, mesh_active=False, unit_rows=False):
    """``(take, interpret)``: whether a gated layer's call of ``n`` rows,
    top ``k`` over ``held`` held experts of ``d`` x ``h``, takes the grouped
    form of the routed product, decided from what the call shows, as
    ``attention.flash_selected`` decides for a training node.

    All must hold: a backend that runs Pallas (``attention._kernel_backend``:
    the product is megablox's kernel); no mesh shards the executor (the
    kernel is opaque to GSPMD); widths of whole lane tiles (``unit_rows``,
    every stack (held, h, d): the expert's width is nowhere minor and needs
    whole sublane tiles only); and ``n`` has
    reached :data:`GROUPED_MIN_ROWS`.  Anything else takes the dense form.
    ``k`` and ``held`` size the grouped form's buffer and do not move the
    rule: at every top-k and share measured the forms cross between 256 and
    512 rows."""
    from .attention import _kernel_backend

    runs, interpret = _kernel_backend()
    if mesh_active or not runs or d % LANES \
            or h % (SUBLANES if unit_rows else LANES):
        return False, False
    return n >= GROUPED_MIN_ROWS, interpret


def _note_form(form):
    """Count a gated node traced under the form its routed product took,
    and leave it in :data:`MOE_PATH` for the program's record."""
    from .. import obs as _obs

    MOE_PATH["last"] = form
    _obs.registry.counter(
        "mx_moe_dispatch_total",
        "gated MoEFFN nodes traced, by the form their routed product took",
        labels=("form",)).labels(form=form).inc()


def _experts_dense(xt, ws, body, here, weight, layer):
    """Every held expert over every row; the unchosen pairs weigh zero.
    ``ws``: an expert's matrices in, then the one back; ``body`` the node's
    entry of :data:`BODIES`."""
    import jax.numpy as jnp

    from ..obs.scopes import scope as _scope

    _, act, unit_rows = body
    into = "nd,ehd->enh" if unit_rows else "nd,edh->enh"
    with _scope(layer, "experts"):
        ins = [jnp.einsum(into, xt, w) for w in ws[:-1]]
        y = jnp.einsum("enh,ehd->end", _hidden(act, ins), ws[-1],
                       preferred_element_type=jnp.float32)
    with _scope(layer, "combine"):
        w = (here * weight[:, :, None]).sum(1)            # (n, held)
        return jnp.einsum("end,ne->nd", y, w)


def _experts_grouped(xt, ws, body, here, weight, layer, interpret):
    """Only the held (row, expert) pairs: sorted by held expert, their rows
    gathered into a buffer of the worst case (every row choosing ``min(k,
    held)`` held experts), grouped products whose work follows the groups'
    sizes (``ws`` and ``body`` as :func:`_experts_dense` takes them), and each
    pair's output, times its weight, summed back into
    its row.  The sizes are data: one trace serves every routing and no pair
    is ever dropped.  Rows of the buffer past the last group are never
    computed; what lies there is selected away, not multiplied by zero.  The
    form has no gradient (what lies past the last group would reach it) and
    refuses one by name."""
    import jax
    import jax.numpy as jnp

    from ..obs.startup import pallas

    pallas("jax.experimental.pallas.ops.tpu.megablox.gmm")
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from ..obs.scopes import scope as _scope

    _, act, unit_rows = body
    n, k, held = here.shape
    tm, tk, tn = GROUPED_TILES
    pairs = n * k
    rows = -(-n * min(k, held) // tm) * tm

    @jax.custom_vjp
    def run(xt, ws, here, weight):
        with _scope(layer, "route"):
            # the pairs choice by choice, a choice's rows together: what is
            # gathered back for a choice is then (n, d) as it lies
            mine = here.any(-1).T                         # (k, n)
            # a pair's group is its held expert; pairs held elsewhere sort
            # behind the last group
            group = jnp.where(mine, jnp.argmax(here, -1).T, held)
            order = jnp.argsort(group.reshape(-1),
                                stable=True).astype(jnp.int32)
            sizes = here.sum((0, 1), dtype=jnp.int32)     # (held,)
            # the buffer's row of each pair, and the row each one holds
            slot = jnp.zeros((pairs,), jnp.int32).at[order].set(
                jnp.arange(pairs, dtype=jnp.int32))
            source = jnp.pad(order, (0, max(0, rows - pairs)))[:rows] % n

        def product(lhs, rhs, out_type, rows_out=False):
            # Mosaic has no 64-bit integers: traced with 32-bit defaults
            # whatever ``jax_enable_x64`` says (the tests set it)
            with jax.enable_x64(False):
                return gmm(lhs, rhs, sizes, preferred_element_type=out_type,
                           tiling=(tm, _tile(tk, lhs.shape[1]),
                                   _tile(tn, rhs.shape[1 if rows_out
                                                       else 2])),
                           transpose_rhs=rows_out, interpret=interpret)

        with _scope(layer, "experts"):
            xs = jnp.take(xt, source, axis=0, mode="clip")    # (rows, d)
            ins = [product(xs, w, xt.dtype, unit_rows) for w in ws[:-1]]
            y = product(_hidden(act, ins), ws[-1], jnp.float32)
        with _scope(layer, "combine"):
            picked = jnp.take(y, jnp.minimum(slot, rows - 1), axis=0,
                              mode="clip").reshape(k, n, -1)
            return jnp.where(mine[:, :, None],
                             picked * weight.T[:, :, None], 0.0).sum(0)

    def refuse(*_):
        raise NotImplementedError(
            "MoEFFN: the grouped form of the gated layer's routed product "
            "(held_grouped) has no gradient; differentiate the dense form "
            "(fewer than %d rows a call, or a backend that runs no kernel)"
            % GROUPED_MIN_ROWS)

    run.defvjp(refuse, refuse)
    return run(xt, tuple(ws), here, weight)


def _moe_share(x, wr, bias, ws, attrs, k, shared=None, mesh_active=False):
    """The gated layer at this chip's share: what the held experts add to
    each token's output, and nothing in place of the others.  ``ws`` are the
    held experts' weight stacks and ``shared`` (``n_shared_experts``) the
    matrices of an always-on MLP of the same body (``expert_act``,
    :data:`BODIES`) that
    every chip holds whole and adds to its own tokens: it is in every share,
    so where shares are added up it is counted in one of them.  The routed
    weights are scaled by ``routed_scaling_factor`` after normalising.

    The routed product has two forms with one result, and the call's shapes
    pick one (:func:`grouped_selected`; ``mesh_active``: a mesh shards the
    executor).  Dense (``held_dense``): every held expert runs over every
    token and the unchosen pairs weigh zero, held x n rows of work whatever
    the routing, no index traffic; an expert's product is bound by reading
    its weights until it has some 240 rows (TPU v5 lite: 197 TFLOP/s over 819
    GB/s), so in a call of few rows (a decode tick) the unchosen rows cost
    nothing.  Grouped (``held_grouped``, :func:`_experts_grouped`): only the
    held (row, expert) pairs, sorted by expert into a buffer of the worst
    case, through grouped products whose work follows the groups' sizes.  In
    both no token is ever dropped and nothing retraces as the routing
    changes.  The readings that put the crossover at 512 rows are beside
    :data:`GROUPED_MIN_ROWS` (a chunk of 2048 rows at 16 of 128 experts held,
    top 4: dense 10.83 ms a layer, grouped 2.53).  A body other than the
    gated one is counted under its own label (``held_dense_relu2``)."""
    import jax.numpy as jnp

    from ..obs.scopes import LAYER_ATTR, scope as _scope

    # the scope the node's time is filed under: "moe", unless the builder
    # names another (a block that is a layer of its own)
    layer = attrs.get(LAYER_ATTR) or "moe"
    first = int(attrs.get("first_held", 0) or 0)
    held = _held(attrs)
    e = int(attrs["num_experts"])
    body = _body(attrs)
    _, act, unit_rows = body
    if first < 0 or first + held > e or ws[-1].shape[0] != held:
        raise ValueError("MoEFFN: experts [%d, %d) of a stack of %d are "
                         "not among the layer's %d"
                         % (first, first + held, ws[-1].shape[0], e))
    xt = x.reshape(-1, x.shape[-1])
    with _scope(layer, "route"):
        choice, weight = _scores(xt, wr, bias, min(k, e),
                                 attrs.get("score_func", "softmax"),
                                 bool(attrs.get("norm_topk", True)))
        factor = float(attrs.get("routed_scaling_factor", 1.0) or 1.0)
        if factor != 1.0:
            weight = weight * factor
        # which held expert each (token, choice) pair goes to, if any
        here = choice[:, :, None] \
            == (first + jnp.arange(held))[None, None, :]  # (n, k, held)
    grouped, interpret = grouped_selected(
        xt.shape[0], choice.shape[1], held, ws[-1].shape[2],
        ws[-1].shape[1], mesh_active, unit_rows)
    # the gated body keeps the labels it had; another adds its name
    name = attrs.get("expert_act") or "swiglu"
    label = "" if name == "swiglu" else "_" + name
    if grouped:
        _note_form("held_grouped" + label)
        out = _experts_grouped(xt, ws, body, here, weight, layer, interpret)
    else:
        _note_form("held_dense" + label)
        out = _experts_dense(xt, ws, body, here, weight, layer)
    if shared is not None:
        with _scope(layer, "shared"):
            ins = (jnp.dot(xt, w.T if unit_rows else w) for w in shared[:-1])
            out = out + jnp.dot(_hidden(act, ins), shared[-1],
                                preferred_element_type=jnp.float32)
    if _STATS["sink"] is not None:
        with _scope(layer, "route"):
            pairs = here.any(-1)                          # (n, k)
            real = _STATS["real"]
            if callable(real):
                real = real()
            if real is None:
                total = choice.size
            else:
                real = jnp.asarray(real).reshape(-1).astype(bool)
                here = here & real[:, None, None]
                pairs = pairs & real[:, None]
                total = real.sum() * choice.shape[1]
            rows = pairs.sum()
            _STATS["sink"].append(jnp.stack(
                [rows, total - rows, here.any((0, 1)).sum()]
            ).astype(jnp.int32))
    return out.astype(x.dtype).reshape(x.shape)


# ---------------------------------------------------------------------------
# routing + slot assignment — ONE implementation shared by the sparse
# reference and the shard_map region, so drop sets cannot drift apart
# ---------------------------------------------------------------------------

def _route(probs, k):
    """Top-k routing: ``(choice, gate)`` both (n, k).

    k = 1 is switch routing (argmax; gate = the raw chosen probability).
    k > 1 takes the k highest-probability experts per token and
    renormalizes the chosen gates to sum to one.
    """
    import jax
    import jax.numpy as jnp

    if k == 1:
        choice = jnp.argmax(probs, axis=-1)[:, None]
        gate = jnp.take_along_axis(probs, choice, axis=-1)
        return choice, gate
    gate, choice = jax.lax.top_k(probs, k)
    gate = gate / gate.sum(-1, keepdims=True)
    return choice, gate


def _positions_onehot(choice, e):
    """Capacity positions via the one-hot cumsum pack (the historical
    algorithm, kept for A/B pricing): materializes a (k*n, E) int32
    one-hot and its running cumsum — E x the index traffic of the sort
    path.  Counting runs in int32: an activation-dtype cumsum loses
    exact integers past 256 and would silently collide slots on big
    batches."""
    import jax
    import jax.numpy as jnp

    n, k = choice.shape
    oh = jax.nn.one_hot(choice, e, dtype=jnp.int32)        # (n, k, E)
    oh_rank_major = oh.transpose(1, 0, 2).reshape(k * n, e)
    return ((jnp.cumsum(oh_rank_major, axis=0) - 1) * oh_rank_major) \
        .sum(-1).reshape(k, n).T                           # (n, k)


def _positions_sort(choice, e):
    """Capacity positions via sort-based dispatch (MegaBlocks, Gale et
    al. 2022): a STABLE argsort of the rank-major flattened choices is
    exactly an argsort over the composite (expert, priority) key — same-
    expert entries keep rank-major order — so each entry's position
    within its expert group is its sorted index minus the group start
    (an exclusive cumsum of the per-expert histogram).  No (k*n, E)
    one-hot ever materializes: the intermediates are O(k*n) sort keys
    and one length-E histogram, priced by the analysis sort/scatter
    accounting."""
    import jax.numpy as jnp

    n, k = choice.shape
    flat = choice.transpose(1, 0).reshape(-1)              # rank-major (k*n,)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=e).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts                   # exclusive
    pos_sorted = jnp.arange(k * n, dtype=jnp.int32) \
        - starts[jnp.take(flat, order)]
    return jnp.zeros((k * n,), jnp.int32).at[order].set(pos_sorted) \
        .reshape(k, n).T                                   # (n, k)


def _slot_assign(choice, e, cap):
    """Capacity-slot assignment for one token group.

    Positions are PRIORITY-MAJOR: every token's rank-0 choice is counted
    before any rank-1 choice (GShard order — a token's second expert can
    never evict another token's first).  Returns ``(pos, keep, slot)``,
    all (n, k); ``slot = choice*cap + pos`` clipped into [0, e*cap).

    ``MXNET_MOE_DISPATCH`` selects the position algorithm at trace time:
    'sort' (default — argsort over the composite (expert, priority) key)
    or 'onehot' (the one-hot cumsum pack).  Both are BIT-IDENTICAL in
    (pos, keep, slot) — and therefore in outputs, grads and drop sets —
    differing only in the dispatch intermediates they materialize
    (tier-1 asserts the identity; the sparse reference and the sharded
    all-to-all path share this one implementation so the knob can never
    split them).
    """
    import jax.numpy as jnp

    from .. import config as _config

    algo = (str(_config.get("MXNET_MOE_DISPATCH")) or "sort").lower()
    if algo not in ("sort", "onehot"):
        raise ValueError("MXNET_MOE_DISPATCH must be 'sort' or 'onehot'; "
                         "got %r" % algo)
    MOE_DISPATCH["last"] = algo
    pos = (_positions_sort if algo == "sort"
           else _positions_onehot)(choice, e)
    keep = pos < cap
    slot = choice * cap + jnp.minimum(pos, cap - 1)
    return pos, keep, slot


def _pack_slots(xt, slot, keep, e, cap):
    """Scatter kept tokens into the (E, cap, d) dispatch table (unfilled
    slots read a zero pad row; the sentinel index e*cap is dropped)."""
    import jax.numpy as jnp

    n, d = xt.shape
    k = slot.shape[1]
    tok = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                           (n, k)).reshape(-1)
    scatter_idx = jnp.where(keep.reshape(-1), slot.reshape(-1), e * cap)
    slot_tok = jnp.full((e * cap,), n, jnp.int32) \
        .at[scatter_idx].set(tok, mode="drop")
    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    return jnp.take(xpad, slot_tok, axis=0).reshape(e, cap, d)


def _combine_slots(flat_out, slot, keep, gate):
    """Gather each kept (token, rank) choice's slot output, weighted by
    its gate; dropped choices contribute zero."""
    import jax.numpy as jnp

    total = flat_out.shape[0]
    idx = jnp.minimum(slot, total - 1)                     # (n, k)
    picked = jnp.take(flat_out, idx.reshape(-1), axis=0) \
        .reshape(idx.shape + (flat_out.shape[-1],))        # (n, k, d)
    w = (keep.astype(flat_out.dtype) * gate.astype(flat_out.dtype))
    return (picked * w[..., None]).sum(axis=1)


def _expert_ffn(xd, w1, b1, w2, b2):
    """The relu expert FFN over an (E, C, d) slot table."""
    import jax.numpy as jnp

    h = jnp.einsum("ecd,edh->ech", xd, w1) + b1[:, None, :]
    h = jnp.maximum(h, 0.0)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


def _aux_terms(probs, choice, e):
    """Local (frac, imp) means for the Switch balance loss: f_e = mean
    routed fraction per choice rank, P_e = mean router probability."""
    import jax
    import jax.numpy as jnp

    k = choice.shape[1]
    oh = jax.nn.one_hot(choice, e, dtype=probs.dtype).sum(1)   # (n, E)
    return oh.mean(0) / k, probs.mean(0)


def _capacity(capacity_factor, k, group_tokens, e, dropless):
    if dropless:
        return group_tokens * k
    return max(1, int(np.ceil(capacity_factor * k * group_tokens / e)))


# ---------------------------------------------------------------------------
# the three dispatch shapes
# ---------------------------------------------------------------------------

def _moe_forward(x, wg, w1, b1, w2, b2, num_experts, num_experts_per_tok=1):
    """Dense one-hot dispatch -> (y, aux_loss): every expert sees the
    masked token batch (the E×-compute oracle the sparse paths beat)."""
    import jax
    import jax.numpy as jnp

    e = num_experts
    k = min(num_experts_per_tok, e)
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)                       # (N, d) tokens

    probs = jax.nn.softmax(xt @ wg, axis=-1)    # (N, E) router
    choice, gate = _route(probs, k)             # (N, k) each
    onehot_k = jax.nn.one_hot(choice, e, dtype=xt.dtype)   # (N, k, E)
    dispatch = onehot_k.sum(1)                  # (N, E) 0/1 mask
    combine = (onehot_k * gate[..., None].astype(xt.dtype)).sum(1)

    # dense dispatch: every expert sees the masked token batch; the
    # (E, ...) weight axis is what shards on the 'expert' mesh axis
    xe = jnp.einsum("nd,ne->end", xt, dispatch)  # (E, N, d)
    h = jnp.einsum("end,edh->enh", xe, w1) + b1[:, None, :]
    h = jnp.maximum(h, 0.0)                     # relu expert FFN
    ye = jnp.einsum("enh,ehd->end", h, w2) + b2[:, None, :]
    y = jnp.einsum("end,ne->nd", ye, combine)   # gated combine

    # Switch load-balance loss: E * sum_e f_e * P_e
    frac, imp = _aux_terms(probs, choice, e)
    aux_loss = (frac * imp).sum() * e
    return y.reshape(orig_shape), aux_loss


def _moe_forward_sparse(x, wg, w1, b1, w2, b2, num_experts,
                        capacity_factor, mesh=None, num_experts_per_tok=1,
                        num_groups=1, dropless=False):
    """Capacity-based sparse dispatch: per-step FLOPs FLAT in num_experts.

    Tokens split into ``num_groups`` contiguous groups; within each group
    every (token, rank-k choice) takes the next capacity slot of its
    chosen expert — C = ceil(cf*k*N_g/E) slots per (group, expert) — and
    choices past capacity are DROPPED (Switch semantics; the residual
    connection around the MoE layer carries them) unless ``dropless``
    stretches C to the group's worst case with a padding mask.  Dispatch
    and combine are gathers over static slot tables — no (N, E) one-hot
    matmuls, so the expert FFN compute is ~2*cf*k*N*(dh+hd) regardless
    of E, where the dense oracle pays E times that.

    ``num_groups`` exists because group g IS device g of the sharded
    all-to-all path (`_moe_forward_sparse_sharded`): called with
    ``num_groups = data_par * expert_par`` this single-device reference
    reproduces the sharded program's drop set token for token — the
    parity the tier-1 suite asserts.  The default (1) is the historical
    global-cumsum semantics.

    Under a mesh with an 'expert' axis (but taken only when the explicit
    shard_map path's divisibility guards fail) the expert-major tensors
    carry sharding constraints so GSPMD may synthesize the exchange.
    """
    import jax
    import jax.numpy as jnp

    e = num_experts
    k = min(num_experts_per_tok, e)
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    g = num_groups
    assert n % g == 0, "token count %d not divisible into %d groups" % (n, g)
    ng = n // g
    cap = _capacity(capacity_factor, k, ng, e, dropless)

    probs = jax.nn.softmax(xt @ wg, axis=-1)
    choice_all, gate_all = _route(probs, k)

    def pack_group(xtg, choiceg, gateg):
        _, keep, slot = _slot_assign(choiceg, e, cap)
        xd = _pack_slots(xtg, slot, keep, e, cap)
        return xd, keep, slot

    xd_g, keep_g, slot_g = jax.vmap(pack_group)(
        xt.reshape(g, ng, d), choice_all.reshape(g, ng, k),
        gate_all.reshape(g, ng, k))             # (g, E, cap, d), ...

    if mesh is not None and dict(mesh.shape).get("expert", 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        espec = NamedSharding(mesh, P(None, "expert"))
        xd_g = jax.lax.with_sharding_constraint(xd_g, espec)
    ye_g = jax.vmap(_expert_ffn, in_axes=(0, None, None, None, None))(
        xd_g, w1, b1, w2, b2)
    if mesh is not None and dict(mesh.shape).get("expert", 1) > 1:
        ye_g = jax.lax.with_sharding_constraint(ye_g, espec)

    # combine: each kept (token, rank) reads back its slot; drops emit 0
    yt = jax.vmap(_combine_slots)(
        ye_g.reshape(g, e * cap, d), slot_g, keep_g,
        gate_all.reshape(g, ng, k)).reshape(n, d)

    frac, imp = _aux_terms(probs, choice_all, e)
    aux_loss = (frac * imp).sum() * e
    return yt.reshape(orig_shape), aux_loss


def _moe_forward_sparse_sharded(x, wg, w1, b1, w2, b2, num_experts,
                                capacity_factor, mesh,
                                num_experts_per_tok=1, dropless=False):
    """Explicit expert-parallel dispatch: a ``shard_map`` program over the
    mesh in which the token exchange is two ``jax.lax.all_to_all`` calls.

    Per device (tokens sharded over ('data', 'expert'), weights over
    'expert' with the hidden dim Megatron-split over 'model' when it
    divides):

    1. route the n_loc local tokens (top-k, renormalized gates) and pack
       them into per-(destination-expert) capacity slots (E, C_loc, d),
       C_loc = ceil(cf*k*n_loc/E);
    2. ``all_to_all`` over 'expert' (split the expert dim, concat the
       capacity dim): each device now holds its OWN experts' full slot
       tables (E/ep, ep*C_loc, d), source-device-major in the capacity
       dim;
    3. run the local experts' FFNs — hidden dim sharded over 'model'
       with one psum, the Megatron pair;
    4. ``all_to_all`` back (split capacity, concat experts) and combine
       each kept token's k slots with its gates.

    Gradients differentiate through the region (the op-level custom_vjp
    below), so the backward program contains the same two exchanges
    reversed — d(combine) all-to-alls out to the experts, the FFN
    backward runs local, and d(dispatch) all-to-alls home — which is
    what the collective-budget pass pins in benchmarks/budgets.json.

    Token-identical (outputs, grads, drop set) to
    ``_moe_forward_sparse(..., num_groups=data_par*expert_par)``: group
    ordering, slot layout and capacity quotas match by construction
    (shared `_slot_assign`/`_pack_slots`/`_combine_slots` helpers).
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.compat import shard_map

    e = num_experts
    k = min(num_experts_per_tok, e)
    orig_shape = x.shape
    d = x.shape[-1]
    axes = dict(mesh.shape)
    dp = axes.get("data", 1)
    ep = axes["expert"]
    mp = axes.get("model", 1)
    h_dim = w1.shape[-1]

    xt = x.reshape(-1, d)
    n = xt.shape[0]
    n_loc = n // (dp * ep)
    cap = _capacity(capacity_factor, k, n_loc, e, dropless)
    model_ax = "model" if (mp > 1 and h_dim % mp == 0) else None
    # tokens shard over every axis that exists of (data, expert) —
    # hand-built meshes without a 'data' name still dispatch
    tok_axes = tuple(a for a in ("data", "expert") if a in axes)

    def local_moe(xt, wg, w1, b1, w2, b2):
        import jax.numpy as jnp

        probs = jax.nn.softmax(xt @ wg, axis=-1)
        choice, gate = _route(probs, k)
        _, keep, slot = _slot_assign(choice, e, cap)
        xd = _pack_slots(xt, slot, keep, e, cap)       # (E, C_loc, d)

        # dispatch: expert dim splits across the axis, capacity dims
        # concat source-device-major -> (E/ep, ep*C_loc, d) local tables
        xs = lax.all_to_all(xd, "expert", split_axis=0, concat_axis=1,
                            tiled=True)
        h = jnp.einsum("ecd,edh->ech", xs, w1) + b1[:, None, :]
        h = jnp.maximum(h, 0.0)
        ye = jnp.einsum("ech,ehd->ecd", h, w2)
        if model_ax is not None:
            ye = lax.psum(ye, model_ax)                # Megatron row-psum
        ye = ye + b2[:, None, :]
        # combine exchange: capacity splits back to source devices,
        # expert dim concats home -> (E, C_loc, d)
        ys = lax.all_to_all(ye, "expert", split_axis=1, concat_axis=0,
                            tiled=True)
        yt = _combine_slots(ys.reshape(e * cap, d), slot, keep, gate)

        frac, imp = _aux_terms(probs, choice, e)
        frac = lax.pmean(frac, tok_axes)
        imp = lax.pmean(imp, tok_axes)
        aux = (frac * imp).sum() * e
        return yt, aux

    tok_spec = tok_axes
    fn = shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(tok_spec, None), P(None, None),
                  P("expert", None, model_ax), P("expert", model_ax),
                  P("expert", model_ax, None), P("expert", None)),
        out_specs=(P(tok_spec, None), P()),
        check_vma=False)
    yt, aux = fn(xt, wg, w1, b1, w2, b2)
    return yt.reshape(orig_shape), aux


def _sharded_ok(x, num_experts, mesh):
    """The explicit all-to-all path's static divisibility guards: an
    'expert' axis > 1, experts divisible over it, and the flattened
    token count divisible over (data x expert).  Indivisible configs
    degrade to the GSPMD-hint sparse path, never to wrong numbers."""
    if mesh is None:
        return False
    axes = dict(mesh.shape)
    ep = axes.get("expert", 1)
    if ep <= 1 or num_experts % ep != 0:
        return False
    n = 1
    for dim in x.shape[:-1]:
        n *= dim
    return n % (axes.get("data", 1) * ep) == 0


def register_all():
    import jax

    _wrapped = {}

    def _moe_with_aux_grad(num_experts, coeff, capacity_factor, mesh,
                           num_experts_per_tok, dropless):
        """custom_vjp wrapper: forward value is y alone; backward is the
        vjp of (y + coeff * aux_loss), i.e. training minimizes
        task_loss + coeff * balance_loss with exact gradients.  For the
        sharded sparse path the vjp differentiates through the shard_map
        region, so the backward program carries the two all-to-all
        exchanges in reverse."""
        # key by the mesh's VALUE (axes + device ids), not id(): id-keying
        # grows the cache (and pins a Mesh) for every rebind in a
        # long-running job; equal meshes share one traced closure
        mesh_key = None if mesh is None else (
            tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat))
        key = (num_experts, coeff, capacity_factor, mesh_key,
               num_experts_per_tok, dropless)
        fn = _wrapped.get(key)
        if fn is not None:
            return fn

        def fwd_impl(x, wg, w1, b1, w2, b2):
            if capacity_factor > 0 or dropless:
                if _sharded_ok(x, num_experts, mesh):
                    MOE_PATH["last"] = "sparse_a2a"
                    return _moe_forward_sparse_sharded(
                        x, wg, w1, b1, w2, b2, num_experts,
                        capacity_factor, mesh,
                        num_experts_per_tok=num_experts_per_tok,
                        dropless=dropless)
                MOE_PATH["last"] = "sparse"
                return _moe_forward_sparse(
                    x, wg, w1, b1, w2, b2, num_experts, capacity_factor,
                    mesh, num_experts_per_tok=num_experts_per_tok,
                    dropless=dropless)
            MOE_PATH["last"] = "dense"
            return _moe_forward(x, wg, w1, b1, w2, b2, num_experts,
                                num_experts_per_tok=num_experts_per_tok)

        @jax.custom_vjp
        def moe(x, wg, w1, b1, w2, b2):
            y, _ = fwd_impl(x, wg, w1, b1, w2, b2)
            return y

        def fwd(x, wg, w1, b1, w2, b2):
            y, _ = fwd_impl(x, wg, w1, b1, w2, b2)
            return y, (x, wg, w1, b1, w2, b2)

        def bwd(res, dy):
            import jax.numpy as jnp

            def total(x, wg, w1, b1, w2, b2):
                y, aux = fwd_impl(x, wg, w1, b1, w2, b2)
                return y, aux

            (_, aux), vjp = jax.vjp(total, *res)
            # cotangents must match the primal dtypes (aux follows inputs)
            return vjp((dy, jnp.asarray(coeff, dtype=aux.dtype)))

        moe.defvjp(fwd, bwd)
        _wrapped[key] = moe
        return moe

    def fcompute(attrs, inputs, aux, octx):
        from .. import config as _config

        # runtime knobs override the symbol's attributes at trace time
        # (flip routing/capacity/overflow without editing the model)
        k = int(_config.get("MXNET_MOE_TOPK")) \
            or int(attrs.get("num_experts_per_tok", 1))
        if attrs.get("gated"):
            x, wr, *rest = inputs
            bias = rest.pop(0) if attrs.get("score_bias") else None
            n = len(_body(attrs)[0])
            shared = tuple(rest[n:]) if _shared_width(attrs) else None
            return [_moe_share(x, wr, bias, tuple(rest[:n]), attrs, k,
                               shared=shared,
                               mesh_active=octx.mesh_active)], []
        if attrs.get("score_bias") or attrs.get("num_held") \
                or attrs.get("score_func", "softmax") != "softmax":
            raise ValueError("MoEFFN: score_func, score_bias and num_held "
                             "belong to the gated layer (gated=True)")
        cf = float(_config.get("MXNET_MOE_CAPACITY")) \
            or float(attrs["capacity_factor"])
        dropless = bool(_config.get("MXNET_MOE_DROPLESS")) \
            or attrs.get("overflow", "drop") == "dropless"
        fn = _moe_with_aux_grad(attrs["num_experts"],
                                float(attrs["aux_loss_coeff"]),
                                cf, octx.mesh, k, dropless)
        return [fn(*inputs)], []

    register_op(OpDef(
        "MoEFFN", fcompute,
        schema=ParamSchema(
            Param("num_experts", int, required=True),
            Param("hidden_size", int, required=True),
            Param("aux_loss_coeff", float, default=0.01,
                  doc="weight of the Switch load-balancing loss folded "
                      "into the backward pass; 0 disables"),
            Param("capacity_factor", float, default=0.0,
                  doc="> 0 enables SPARSE capacity-based dispatch: each "
                      "expert processes at most ceil(cf*k*N_g/E) tokens "
                      "per token group (overflow drops, Switch "
                      "semantics, unless overflow='dropless') and the "
                      "per-step FLOPs are flat in num_experts; under an "
                      "'expert' mesh the dispatch is an explicit "
                      "all-to-all shard_map program (docs/moe.md); 0 "
                      "keeps the dense all-expert oracle"),
            Param("num_experts_per_tok", int, default=1,
                  doc="top-k routing: experts per token (gates "
                      "renormalized over the chosen k when k > 1; 1 = "
                      "classic switch top-1 with the raw probability "
                      "gate).  MXNET_MOE_TOPK overrides at trace time"),
            Param("overflow", str, default="drop",
                  doc="sparse-path overflow policy: 'drop' (Switch "
                      "semantics — past-capacity tokens emit zero and "
                      "ride the residual) or 'dropless' (capacity "
                      "stretches to the per-device worst case with a "
                      "padding mask, no drops ever).  "
                      "MXNET_MOE_DROPLESS=1 forces 'dropless'.  The gated "
                      "layer has no capacity and never drops"),
            Param("gated", bool, default=False,
                  doc="the layer at one chip's share: experts without "
                      "biases, routed without capacity or drops; the "
                      "attributes below belong to it"),
            Param("expert_act", str, default="swiglu",
                  doc="an expert's body (and the shared MLP's): 'swiglu' = "
                      "silu(x W_g) * (x W_u) then W_d, three weight stacks "
                      "gate/up/down of (held, d, h), (held, d, h), (held, "
                      "h, d); 'relu2' = relu(x W_u)^2 then W_d, two stacks "
                      "up/down, both (held, h, d), a hidden unit a row"),
            Param("score_func", str, default="softmax",
                  doc="'softmax' or 'sigmoid' over the router's outputs"),
            Param("score_bias", bool, default=False,
                  doc="a gate_bias input (E,) added to the scores for the "
                      "top-k choice only, never in the weight"),
            Param("norm_topk", bool, default=True,
                  doc="the chosen scores renormalised to sum to one"),
            Param("num_held", int, default=0,
                  doc="experts this chip holds: the weight stacks are "
                      "(num_held, ...), the router stays (d, num_experts) "
                      "and the output is the held experts' part; 0 = all"),
            Param("first_held", int, default=0,
                  doc="the first expert held"),
            Param("n_shared_experts", int, default=0,
                  doc="always-on experts beside the routed ones, "
                      "held as one MLP of n_shared_experts x hidden_size "
                      "(shared_gate/up/down_weight, (d, w) and (w, d); "
                      "under 'relu2' shared_up/down_weight, both (w, d)) "
                      "and added in every share"),
            Param("shared_hidden_size", int, default=0,
                  doc="the shared MLP's width where it is not "
                      "n_shared_experts x hidden_size; 0 = that"),
            Param("routed_scaling_factor", float, default=1.0,
                  doc="the routed weights x this, after norm_topk"),
        ),
        num_inputs=lambda a: len(_moe_arguments(a)),
        arguments=_moe_arguments,
        infer_shape=_moe_shape,
        mesh_axes={"expert1_weight": "expert", "expert1_bias": "expert",
                   "expert2_weight": "expert", "expert2_bias": "expert"},
        doc="Top-k-routed mixture-of-experts feed-forward.  "
            "Leapfrog op (SURVEY §2.5: expert parallelism 'not present'): "
            "expert-stacked weights (E, ...) shard on the 'expert' mesh "
            "axis; capacity_factor > 0 under an 'expert' mesh dispatches "
            "through the explicit all-to-all shard_map program; the "
            "Switch balance loss rides the backward pass "
            "(aux_loss_coeff)."),
        aliases=("_contrib_MoEFFN",))
