"""Fused multi-tensor Pallas optimizer update — the train step's HBM diet.

The compiled train step's optimizer phase today is a per-parameter chain
of XLA ops: cast the bf16 gradient up (``grads[i].astype``), scale by
``rescale_grad``, clip, run the SGD-momentum/Adam moment update, and
recast the weight for the next forward — every link reading and writing
every param, grad and slot tensor.  At engine-op granularity (the
reference's per-op kernel semantics, and the worst case XLA is allowed
to emit for a chain of separately-rooted elementwise fusions) that is
five HBM round trips per parameter per step on tensors that together
rival the activation traffic of the whole backward pass.

This module is the Apex-style *multi-tensor apply* answer (FusedAdam /
``multi_tensor_applier``): the donated param/grad/slot trees flatten
into dtype-homogeneous flat **slabs** — each parameter padded to a
whole number of (16, 128) blocks, concatenated, viewed as (rows, 128) —
and ONE Pallas pass per slab performs the entire chain:

    g32 = promote(g)                      # bf16 grad -> f32, in VMEM
    g32 = rescale/clip(g32)
    w', slots' = opt(w32, g32, slots32)   # SGD-mom or Adam, f32 math
    store w' (master dtype), slots', and w'.astype(compute_dtype)

The slabs are the step's PERSISTENT donated state (train_step.py):
masters and slots enter as slabs and leave as the kernel's aliased
outputs, so nothing re-packs per step.  The compute-dtype recast
output means the next step's program-entry cast pass disappears too:
the forward reads views sliced from the persistent compute slab and
differentiates against them, and the gradient slab's pack (the one
per-step assembly) fuses into the backward's own output writes — the
f32 convert sits directly on each backward dot (see ``grad_dtype``).

Per-parameter hyperparameters (lr — Adam's bias correction already
folded host-side at the TRUE update count t, matching the elastic
sidecar's resume semantics — and wd) ride in as scalar-prefetch arrays
indexed by grid block; ``rescale``/``clip`` and the optimizer extras
(momentum / betas / epsilon) ride in one scalar-prefetch hyper vector,
so post-compile hyper mutation is honored exactly like the XLA path.

Numerics: f32 math in the exact op order of the per-parameter XLA
``fused_kernel`` apply chain — SGD-momentum is BIT-identical; Adam's
sqrt/div parity is tolerance-documented at <= 1e-6 f32
(docs/performance.md).  Slot and master storage dtypes are preserved
(``s_new.astype(s_old.dtype)`` semantics).

Scope and fallback: SGD (with or without momentum) and Adam; float32 /
bfloat16 params; single-device masters (a mesh-sharded master store
keeps the per-param XLA path — slabs would force replication).
Anything else, and the eager ``opt_owner``, falls back unchanged; the
train step stamps ``meta['pallas_update']`` only when the kernel
actually lowered, and the mxlint flop-dtype pass's ``pallas-fallback``
tripwire errors if a stamped program quietly lost its ``pallas_call``.

``priced_update_cost`` prices both paths' optimizer-phase HBM bytes
through the PR-9/11 roofline machinery (``analysis.cost.program_cost``
on one program per phase): the per-parameter path at engine-op
granularity (each chain link one materialized round trip), the fused
path as its single pass — ``bench.py`` publishes both and the
``opt_update`` mfu_table row carries whichever path is armed.
"""
from __future__ import annotations

import functools

import numpy as np

# one grid block: (16, 128) = 2048 elements — the bf16 minimum tile,
# a multiple of the f32 (8, 128) tile, and small enough that per-param
# padding waste is negligible beside the slab it buys
BLOCK_ROWS = 16
LANES = 128
BLOCK = BLOCK_ROWS * LANES

# which update path the last fused-step build took ("pallas" | "xla") —
# path-selection tripwire, same pattern as ops.attention.PATH_TAKEN /
# DECODE_PATH
UPDATE_PATH = {"last": None}

_SUPPORTED_DTYPES = ("float32", "bfloat16")


def enabled():
    """``(armed, interpret)``: the kernel engages on TPU natively, or
    anywhere under ``MXNET_PALLAS_INTERPRET`` (the tier-1 CPU harness):
    ``ops.attention._kernel_backend``'s rule."""
    import jax

    from .. import config as _config

    if not _config.get("MXNET_PALLAS_UPDATE"):
        return False, False
    if jax.default_backend() == "tpu":
        return True, False
    if _config.get("MXNET_PALLAS_INTERPRET"):
        return True, True
    return False, False


def kind_of(optimizer):
    """``("sgd", nslots)`` / ``("adam", 2)`` for optimizers the kernel
    implements, else None.  Exact-type checks: NAG subclasses SGD with
    different math and must fall back."""
    from ..optimizer import SGD, Adam, ccSGD

    if type(optimizer) in (SGD, ccSGD):
        return ("sgd", 1 if optimizer.momentum != 0.0 else 0)
    if type(optimizer) is Adam:
        return ("adam", 2)
    return None


# ---------------------------------------------------------------------------
# slab plan
# ---------------------------------------------------------------------------

class _Segment:
    __slots__ = ("name", "shape", "size", "row0", "nblocks")

    def __init__(self, name, shape, size, row0, nblocks):
        self.name = name
        self.shape = shape
        self.size = size
        self.row0 = row0
        self.nblocks = nblocks


class UpdatePlan:
    """The static flattening plan: which parameter lives where in which
    slab.  Built once per step compile; all methods are traceable."""

    def __init__(self, kind, nslots, segments_by_bucket, compute_dtype,
                 interpret, block_rows=BLOCK_ROWS):
        self.kind = kind
        self.nslots = nslots
        self.buckets = segments_by_bucket  # {dtype_name: [_Segment...]}
        self.cdtype = compute_dtype        # jnp dtype or None
        self.interpret = interpret
        # grid-block height: the tuning cache's winner for this param
        # population (plan_for resolves it); segments_by_bucket must have
        # been laid out with the SAME value
        self.block_rows = int(block_rows)
        self.block = self.block_rows * LANES

    # -- layout ---------------------------------------------------------
    def names(self):
        """Every parameter name the plan covers (== the trainable set)."""
        return frozenset(s.name for segs in self.buckets.values()
                         for s in segs)

    def rows(self, bucket):
        segs = self.buckets[bucket]
        last = segs[-1]
        return last.row0 + last.nblocks * self.block_rows

    def grad_dtype(self, bucket):
        """The dtype gradients cross the kernel boundary in: always
        float32.  The per-parameter XLA chain never actually rounds the
        backward dot to the compute dtype — XLA's excess-precision
        folding elides the ``convert(convert(dot_f32 -> bf16) -> f32)``
        pair, so the update there sees the raw f32 dot result.  A
        custom-call boundary can't be folded through, so a bf16 grad
        slab would quantize grads once per step (~1 bf16 ulp drift per
        step vs the XLA path); packing the grad slab in f32 lets the
        same folding fire on our side and keeps SGD-momentum
        bit-identical.  Costs 2x the grad slab's kernel-boundary bytes
        under bf16 compute — still one pass, still far under the
        per-parameter chain."""
        import jax.numpy as jnp

        del bucket
        return jnp.dtype(jnp.float32)

    def has_wc(self, bucket):
        """Whether this bucket keeps a separate compute-dtype slab (the
        in-kernel recast output the next forward reads)."""
        import jax.numpy as jnp

        return self.cdtype is not None and jnp.dtype(bucket) != self.cdtype

    # -- pack / unpack --------------------------------------------------
    def _pack_bucket(self, bk, tree, dt):
        """The names of ONE bucket -> its (rows, 128) slab (traceable)."""
        import jax.numpy as jnp

        parts = []
        for seg in self.buckets[bk]:
            # cast BEFORE reshape: the f32 convert then sits directly on
            # the producer (the backward dot, for grads), where XLA's
            # excess-precision folding elides the bf16 materialization —
            # the same fold the per-parameter chain's ``astype(master)``
            # gets, and the reason bf16-compute parity is bit-exact
            v = tree[seg.name].astype(dt).reshape(-1)
            pad = seg.nblocks * self.block - seg.size
            if pad:
                v = jnp.concatenate([v, jnp.zeros((pad,), dt)])
            parts.append(v)
        return jnp.concatenate(parts).reshape(-1, LANES)

    def pack(self, tree, dtype_of_bucket=None):
        """{name: array} -> {bucket: (rows, 128) slab} (traceable)."""
        import jax.numpy as jnp

        return {bk: self._pack_bucket(
            bk, tree, jnp.dtype(bk) if dtype_of_bucket is None
            else dtype_of_bucket(bk)) for bk in self.buckets}

    def pack_slots(self, slots):
        """{name: tuple} -> {bucket: tuple of slabs} (slot storage keeps
        the master dtype, ``jnp.zeros_like`` semantics)."""
        import jax.numpy as jnp

        return {bk: tuple(
            self._pack_bucket(bk, {s.name: slots[s.name][i]
                                   for s in self.buckets[bk]},
                              jnp.dtype(bk))
            for i in range(self.nslots)) for bk in self.buckets}

    def cast_slabs(self, w_slabs):
        """The compute-dtype slabs the forward reads (only for buckets
        whose master dtype differs from the compute dtype)."""
        return {bk: w_slabs[bk].astype(self.cdtype)
                for bk in self.buckets if self.has_wc(bk)}

    def unpack(self, bucket, slab):
        """One slab -> {name: array} views (traceable slices)."""
        flat = slab.reshape(-1)
        out = {}
        for seg in self.buckets[bucket]:
            start = seg.row0 * LANES
            out[seg.name] = flat[start:start + seg.size].reshape(seg.shape)
        return out

    def unpack_all(self, slabs):
        out = {}
        for bk in self.buckets:
            out.update(self.unpack(bk, slabs[bk]))
        return out

    def unpack_slots(self, slot_slabs):
        """{bucket: tuple of slabs} -> {name: tuple of arrays}."""
        out = {}
        for bk in self.buckets:
            per_slot = [self.unpack(bk, s) for s in slot_slabs[bk]]
            for seg in self.buckets[bk]:
                out[seg.name] = tuple(p[seg.name] for p in per_slot)
        return out

    # -- per-block hyperparameters --------------------------------------
    def lr_wd_blocks(self, lrs, wds):
        """Per-name lr/wd -> per-bucket per-block numpy arrays (host
        side; cached across steps by the step's hyper cache)."""
        lrb, wdb = {}, {}
        for bk, segs in self.buckets.items():
            lr = np.empty(self.rows(bk) // self.block_rows, np.float32)
            wd = np.empty_like(lr)
            for seg in segs:
                b0 = seg.row0 // self.block_rows
                lr[b0:b0 + seg.nblocks] = lrs[seg.name]
                wd[b0:b0 + seg.nblocks] = wds[seg.name]
            lrb[bk], wdb[bk] = lr, wd
        return lrb, wdb

    # -- the kernel -----------------------------------------------------
    def apply(self, w_slabs, g_slabs, slot_slabs, wc_slabs, lrb, wdb, hyp):
        """One fused Pallas pass per bucket; returns
        ``(new_w, new_slots, new_wc)`` slab dicts.

        ``wc_slabs`` may omit a has_wc bucket (the pricing path): the
        recast output is then allocated fresh instead of aliasing the
        old compute slab's buffer — the old slab is a never-READ operand
        either way, so the priced traffic is the same as the real
        kernel's; the alias only saves an allocation on the hot path."""
        new_w, new_slots, new_wc = {}, {}, {}
        for bk in self.buckets:
            has_wc = self.has_wc(bk)
            outs = _bucket_call(
                self.kind, self.nslots, has_wc,
                w_slabs[bk], g_slabs[bk], slot_slabs[bk],
                wc_slabs.get(bk) if has_wc else None, self.cdtype,
                lrb[bk], wdb[bk], hyp, self.interpret,
                block_rows=self.block_rows)
            new_w[bk] = outs[0]
            new_slots[bk] = tuple(outs[1:1 + self.nslots])
            if has_wc:
                new_wc[bk] = outs[-1]
        return new_w, new_slots, new_wc


def plan_for(optimizer, params, grad_names, compute_dtype, mesh=None,
             interpret=False):
    """Build an :class:`UpdatePlan`, or None when this configuration must
    stay on the per-parameter XLA path: unsupported optimizer, a
    non-f32/bf16 trainable param, or a mesh-sharded master store."""
    import jax.numpy as jnp

    if mesh is not None:
        return None
    kind = kind_of(optimizer)
    if kind is None or not grad_names:
        return None
    for name in grad_names:
        if jnp.dtype(params[name].dtype).name not in _SUPPORTED_DTYPES:
            return None
    # one layout rule: the pricing path (_segments_for) and the live
    # plan share it, so the priced slabs are the kernel's slabs
    total = sum(int(np.prod(params[n].shape)) or 1 for n in grad_names)
    br = _tuned_block_rows(total)
    segs = _segments_for({n: params[n] for n in grad_names},
                         block_rows=br)
    cdtype = None
    if compute_dtype is not None and \
            jnp.dtype(compute_dtype) != jnp.float32:
        cdtype = jnp.dtype(compute_dtype)
    return UpdatePlan(kind[0], kind[1], segs, cdtype, interpret,
                      block_rows=br)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _update_math(kind, nslots, w, g, slots, lr, wd, hyp):
    """The f32 update chain, in the exact op order of the per-parameter
    XLA ``fused_kernel`` applies (optimizer.py) — shared by the Pallas
    kernel body and the pricing reference."""
    import jax.numpy as jnp

    rescale, clip = hyp[0], hyp[1]
    g = g * rescale
    g = jnp.where(clip > 0, jnp.clip(g, -clip, clip), g)
    if kind == "sgd":
        if nslots:
            momentum = hyp[2]
            (m,) = slots
            m = momentum * m - lr * (g + wd * w)
            return w + m, (m,)
        return w - lr * (g + wd * w), ()
    beta1, beta2, eps = hyp[2], hyp[3], hyp[4]
    mean, var = slots
    g = g + wd * w
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * jnp.square(g)
    return w - lr * mean / (jnp.sqrt(var) + eps), (mean, var)


def _kernel(lrb_ref, wdb_ref, hyp_ref, w_ref, g_ref, *refs, kind, nslots,
            has_wc, wc_dummy):
    """One grid block: the whole cast+rescale+clip+update+recast chain
    over 2048 elements of one parameter's segment, f32 math in VMEM."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    lr = lrb_ref[i]
    wd = wdb_ref[i]
    slot_in = refs[:nslots]
    out_at = nslots + (1 if wc_dummy else 0)  # skip the wc alias dummy
    w_out = refs[out_at]
    slot_out = refs[out_at + 1:out_at + 1 + nslots]

    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    slots = tuple(s[...].astype(jnp.float32) for s in slot_in)
    hyp = tuple(hyp_ref[j] for j in range(5 if kind == "adam" else 3))
    new_w, new_slots = _update_math(kind, nslots, w, g, slots, lr, wd, hyp)
    w_out[...] = new_w.astype(w_out.dtype)
    for ref, s in zip(slot_out, new_slots):
        ref[...] = s.astype(ref.dtype)
    if has_wc:
        refs[-1][...] = new_w.astype(refs[-1].dtype)


def _bucket_call(kind, nslots, has_wc, w, g, slots, wc, cdtype, lrb, wdb,
                 hyp, interpret, block_rows=BLOCK_ROWS):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = w.shape[0]
    nb = rows // block_rows
    blk = lambda *_: (_[0], 0)          # block i of every slab operand
    bspec = lambda: pl.BlockSpec((block_rows, LANES), blk)

    in_specs = [bspec(), bspec()] + [bspec()] * nslots
    args = [w, g] + list(slots)
    out_specs = [bspec()] + [bspec()] * nslots
    out_shape = [jax.ShapeDtypeStruct(w.shape, w.dtype)] + [
        jax.ShapeDtypeStruct(s.shape, s.dtype) for s in slots]
    # input index of a slab operand = 3 scalar-prefetch args + position;
    # the slabs update in place (multi-tensor apply over donated buffers)
    aliases = {3: 0}
    for i in range(nslots):
        aliases[3 + 2 + i] = 1 + i
    if has_wc:
        out_specs.append(bspec())
        out_shape.append(jax.ShapeDtypeStruct((rows, LANES), cdtype))
        if wc is not None:
            # the old compute slab rides along as a never-read operand so
            # its buffer can host the recast output in place; wc=None
            # (the pricing path) allocates the output fresh instead —
            # identical traffic, one extra allocation
            in_specs.append(bspec())
            args.append(wc)
            aliases[3 + 2 + nslots] = 1 + nslots

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return pl.pallas_call(
        functools.partial(_kernel, kind=kind, nslots=nslots, has_wc=has_wc,
                          wc_dummy=wc is not None),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # every (16, 128) block is an independent segment of the update
        # — no cross-block reduction — so the grid axis fans out across
        # megacores ('arbitrary' would serialize the whole slab)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(lrb), jnp.asarray(wdb), jnp.asarray(hyp), *args)


# ---------------------------------------------------------------------------
# priced HBM bytes per update path (the roofline machinery)
# ---------------------------------------------------------------------------

def priced_update_cost(param_specs, kind, nslots, compute_dtype,
                       interpret=True):
    """Optimizer-phase HBM bytes per path, priced with
    :func:`~mxnet_tpu.analysis.cost.program_cost`.

    ``param_specs`` maps trainable param name -> an object with
    ``.shape``/``.dtype`` (arrays or ShapeDtypeStructs).  The
    **per-parameter path** is priced at engine-op granularity — one
    program per chain link (grad cast, rescale, clip, the optimizer
    update, the compute-dtype recast), each link's operands and results
    a full HBM round trip, which is both the reference engine's per-op
    dispatch semantics and the materialization worst case for a chain
    of separately-rooted elementwise fusions.  The **fused path** is
    one program: the per-bucket Pallas pass over the slabs.  Returns
    ``{"per_param_bytes", "fused_bytes", "ratio", "phases"}``.
    """
    import jax
    import jax.numpy as jnp

    from ..analysis.cost import program_cost

    sds = {n: jax.ShapeDtypeStruct(tuple(v.shape), jnp.dtype(v.dtype))
           for n, v in param_specs.items()}
    cdtype = None
    if compute_dtype is not None and \
            jnp.dtype(compute_dtype) != jnp.float32:
        cdtype = jnp.dtype(compute_dtype)

    def tree(dtype_of=None):
        return {n: jax.ShapeDtypeStruct(
            v.shape, v.dtype if dtype_of is None else dtype_of(v))
            for n, v in sds.items()}

    def jmap(f):
        import jax.tree_util as jtu

        return jax.jit(lambda t, *s: jtu.tree_map(f, t, *s))

    phases = {}
    grads_in = tree(lambda v: cdtype or v.dtype)
    # 1. grad cast up to the master dtype (skipped where it is a no-op)
    cast_set = {n: v for n, v in grads_in.items()
                if v.dtype != sds[n].dtype}
    if cast_set:
        fn = jax.jit(lambda t: {n: t[n].astype(sds[n].dtype)
                                for n in t})
        phases["cast"] = program_cost(fn, (cast_set,))["bytes"]
    # 2. rescale  3. clip — runtime scalars, always-traced ops
    gtree = tree()
    scal = jax.ShapeDtypeStruct((), jnp.float32)
    phases["rescale"] = program_cost(
        jmap(lambda g: g * 1.5), (gtree,))["bytes"]
    fn = jax.jit(lambda t, c: {n: jnp.where(c > 0, jnp.clip(v, -c, c), v)
                               for n, v in t.items()})
    phases["clip"] = program_cost(fn, (gtree, scal))["bytes"]
    # 4. the optimizer update proper (per-param XLA apply chain)
    slots_t = tuple(tree() for _ in range(nslots))
    hyp = jax.ShapeDtypeStruct((5,), jnp.float32)

    def upd(w, g, slots, hyp):
        out_w, out_s = {}, [dict() for _ in range(nslots)]
        for n in w:
            nw, ns = _update_math(kind, nslots, w[n], g[n],
                                  tuple(s[n] for s in slots),
                                  jnp.float32(0.1), jnp.float32(1e-4),
                                  tuple(hyp[i] for i in range(5)))
            out_w[n] = nw.astype(w[n].dtype)
            for i, s in enumerate(ns):
                out_s[i][n] = s.astype(slots[i][n].dtype)
        return out_w, out_s

    phases["update"] = program_cost(
        jax.jit(upd), (tree(), tree(), slots_t, hyp))["bytes"]
    # 5. the next forward's program-entry compute cast
    recast_set = {n: v for n, v in sds.items()
                  if cdtype is not None and v.dtype != cdtype}
    if recast_set:
        fn = jax.jit(lambda t: {n: v.astype(cdtype)
                                for n, v in t.items()})
        phases["recast"] = program_cost(fn, (recast_set,))["bytes"]
    per_param = sum(phases.values())

    # fused: ONE pass (per bucket) over the slabs
    plan = UpdatePlan(kind, nslots, _segments_for(sds), cdtype, interpret)

    def slab_sds(dtype):
        return {bk: jax.ShapeDtypeStruct((plan.rows(bk), LANES),
                                         jnp.dtype(dtype or bk))
                for bk in plan.buckets}

    w_s = slab_sds(None)
    g_s = {bk: jax.ShapeDtypeStruct((plan.rows(bk), LANES),
                                    plan.grad_dtype(bk))
           for bk in plan.buckets}
    slots_s = {bk: tuple(
        jax.ShapeDtypeStruct((plan.rows(bk), LANES), jnp.dtype(bk))
        for _ in range(nslots)) for bk in plan.buckets}
    lrb_s = {bk: jax.ShapeDtypeStruct((plan.rows(bk) // plan.block_rows,),
                                      jnp.float32) for bk in plan.buckets}
    hyp_s = jax.ShapeDtypeStruct((5,), jnp.float32)
    # no wc input operand: the real kernel's old compute slab is an
    # aliased NEVER-READ dummy (its bytes are not traffic), so the
    # honest price allocates the recast output fresh (plan.apply with
    # wc_slabs={})
    fn = jax.jit(lambda w, g, s, lrb, wdb, hyp:
                 plan.apply(w, g, s, {}, lrb, wdb, hyp))
    fused = program_cost(
        fn, (w_s, g_s, slots_s, lrb_s, lrb_s, hyp_s))["bytes"]
    return {"per_param_bytes": int(per_param), "fused_bytes": int(fused),
            "ratio": round(fused / per_param, 4) if per_param else None,
            "phases": {k: int(v) for k, v in phases.items()}}


def _segments_for(sds, block_rows=BLOCK_ROWS):
    segs = {}
    import jax.numpy as jnp

    block = block_rows * LANES
    buckets = {}
    for name, v in sds.items():
        buckets.setdefault(jnp.dtype(v.dtype).name, []).append(
            (name, tuple(v.shape)))
    for bk, entries in buckets.items():
        row = 0
        out = []
        for name, shape in entries:
            size = int(np.prod(shape)) if shape else 1
            nblocks = max(1, -(-size // block))
            out.append(_Segment(name, shape, size, row, nblocks))
            row += nblocks * block_rows
        segs[bk] = out
    return segs


def priced_update_cost_for_step(step):
    """Convenience wrapper: price both update paths at a live
    :class:`~mxnet_tpu.train_step.CompiledTrainStep`'s shapes (None when
    the step's optimizer is outside the kernel's scope)."""
    kind = kind_of(step._optimizer)
    if kind is None or not step._grad_names:
        return None
    params = step.params   # one slab unpack, not one per name
    specs = {n: params[n] for n in step._grad_names}
    return priced_update_cost(specs, kind[0], kind[1],
                              step._cdtype, interpret=True)


# ---------------------------------------------------------------------------
# tunable space (ops/tuning.py): grid-block height per param-count class
# ---------------------------------------------------------------------------

def _tuned_block_rows(total):
    """The tuning cache's grid-block height for a trainable population of
    ``total`` elements (:data:`BLOCK_ROWS` when cold and no sweep armed),
    clamped to the bf16 minimum sublane tile."""
    from . import tuning

    br = int(tuning.resolve("pallas_update",
                            tuning.shape_class_for(n=max(int(total), 1)),
                            "any").get("block_rows", BLOCK_ROWS))
    return max(16, (br // 16) * 16)


def _tuning_candidates(shape_class, interpret):
    if interpret:
        # 2-candidate toy space for the tier-1 CPU sweep
        return [{"block_rows": 16}, {"block_rows": 32}]
    return [{"block_rows": br} for br in (16, 32, 64, 128)]


def _tuning_runner(params, shape_class, dtype, interpret):
    import jax
    import jax.numpy as jnp

    from . import tuning

    n = tuning.parse_shape_class(shape_class).get("n", 1 << 16)
    br = params["block_rows"]
    if br <= 0 or br % 16:
        raise tuning.SpaceError("block_rows %r not a multiple of the "
                                "bf16 sublane tile" % (br,))
    block = br * LANES
    nb = max(1, -(-n // block))
    rows = nb * br
    w = jnp.zeros((rows, LANES), jnp.float32)
    g = jnp.ones((rows, LANES), jnp.float32)
    m = jnp.zeros((rows, LANES), jnp.float32)
    lrb = np.full((nb,), 0.1, np.float32)
    wdb = np.zeros((nb,), np.float32)
    hyp = np.array([1.0, 0.0, 0.9], np.float32)

    @jax.jit
    def probe(w, g, m):
        return _bucket_call("sgd", 1, False, w, g, (m,), None, None,
                            lrb, wdb, hyp, interpret, block_rows=br)

    def run():
        jax.block_until_ready(probe(w, g, m))

    return run


def _register_space():
    from . import tuning

    tuning.register_space(
        "pallas_update", version=1,
        defaults={"block_rows": BLOCK_ROWS},
        constants=("BLOCK_ROWS", "BLOCK"),
        candidates=_tuning_candidates, runner=_tuning_runner)


_register_space()
