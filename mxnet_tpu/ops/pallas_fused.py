"""Fused affine + ReLU + matmul Pallas kernels (TPU) — forward AND backward.

The BN-ResNet traffic lever identified by ``benchmarks/ROOFLINE.md``: on a
bandwidth-bound model, every elementwise pass over an activation tensor is
~1 full HBM round trip.  XLA's graph runs, per BN -> ReLU -> 1x1-conv link:

    stats(x): R(x) | apply: R(x), W(a) | conv: R(a), W(y) | next stats: R(y)

The fused kernel here collapses the whole link into ONE pass:

    y = relu(x * scale + shift) @ W (+ bias) (+ residual)   [matmul prologue]
    ysum, ysumsq = per-channel sums of y                    [matmul epilogue]

reading x once and writing y once — scale/shift application and ReLU ride
the MXU matmul's operand load, the *output's* BN statistics ride its result
store, and the bias/residual adds ride the epilogue.  The next link receives
(ysum, ysumsq) as tensors, so its BatchNorm is per-channel scalar math.

Backward is one combined kernel per link (plus a small XLA prologue that
folds the stats outputs' cotangents into an effective dy): it reads x and
dy once and emits dx, dW, dscale, dshift together, recomputing the ReLU
mask from x instead of storing the activation — the activation tensor `a`
never exists in HBM in either pass.

This is the TPU-shaped analog of the reference's fused-kernel perf work
(its conv/BN go through cuDNN fused paths and hand-written epilogues —
``docs/how_to/perf.md:107-190``); a 1x1 conv over NHWC is exactly a matmul,
so the kernel is a tiled MXU matmul with a custom prologue/epilogue.

**ResNet outcome (round 4, benchmarks/ROOFLINE.md)**: on the bench chip
the traffic saved does NOT beat XLA at ResNet's conv shapes — its conv
emitters are ~1.7x faster than this kernel's matmul there, so the full
fused trunk runs 0.63x the XLA step (`benchmarks/rn50_raw.py FUSED=1`
reproduces it).  **The LM training path is the shape where it pays**:
``models/attention_lm.py``'s pre-norm blocks dispatch their LN->QKV and
LN->MLP segments here under ``MXNET_PALLAS_FUSED`` (ops/fused_lm.py) —
``bias`` rides the epilogue, ``wt=True`` takes FullyConnected's
(num_hidden, K) weight layout without materializing a transpose, and the
residual add rides along; :func:`priced_fused_cost` prices the HBM diet
against the engine-op einsum chain for the mfu_table.

Block shapes resolve through the persistent tuning cache
(:mod:`~mxnet_tpu.ops.tuning`): the module constants below are the
interpret/CPU defaults; an ``MXNET_PALLAS_TUNE`` sweep on the live
device persists per-(generation, shape-class, dtype) winners that later
processes read with zero probes.

Numerics: matmul accumulates f32; y is cast to the compute dtype and the
statistics are computed from the *cast* values, so (ysum, ysumsq) equal
what a separate pass over the stored y would produce.

``interpret=True`` runs the same kernels on CPU for tests.
"""
from __future__ import annotations

import functools

import numpy as np

# interpret/CPU-mode defaults (swept on the TPU v5 lite bench chip; see
# benchmarks/proto_fused.py).  On the live device the tuning cache
# (ops/tuning.py) overrides them per (generation, shape-class, dtype);
# block_m = 0 means "derive from the VMEM budget" (_auto_block_m).
BLOCK_M = 512
BLOCK_N = 256
BLOCK_M_BWD = 256
MIN_BLOCK_M = 8


def supported(m, k, n, dtype):
    """Shapes the kernel handles without padding: all dims tile-aligned."""
    import jax.numpy as jnp

    if dtype not in (jnp.bfloat16, np.dtype("bfloat16"), jnp.float32,
                     np.dtype("float32")):
        return False
    # whole-K/whole-N VMEM budget (weights + one x/dx/dy block each way,
    # double-buffered) — stay well under the ~16MB/core budget
    itemsize = 2 if dtype in (jnp.bfloat16, np.dtype("bfloat16")) else 4
    if k * n * itemsize > 4 * 1024 * 1024:
        return False
    if m % 256 or k % 8 or n % 64:
        return False
    return True


def _auto_block_m(k, n):
    """Row block as large as a ~2.5MB/operand VMEM budget allows (fewer
    grid steps = less per-step overhead; double-buffered x and y
    dominate usage)."""
    return max(256, min(8192, (2560 * 1024 // (2 * max(k, n))) // 256 * 256))


def _tuned(m, k, n, dtype):
    """The tuning-cache resolution for this shape class — {"block_m",
    "block_m_bwd"}, defaults when the cache is cold and no sweep armed."""
    import jax.numpy as jnp

    from . import tuning

    return tuning.resolve(
        "pallas_fused", tuning.shape_class_for(m=m, k=k, n=n),
        jnp.dtype(dtype).name)


def _fit_block(bm, m):
    """Clamp a block preference onto a power-of-two divisor of m; the grid
    drops whole rows otherwise.  The power-of-two floor comes first: the
    halving walk from a preference like 1280 (``_auto_block_m`` at
    k = n = 1024) ended on 5 rows, which Mosaic refuses (row blocks must be
    a multiple of 8)."""
    bm = max(MIN_BLOCK_M, min(int(bm), m))
    bm = 1 << (bm.bit_length() - 1)
    while m % bm and bm > MIN_BLOCK_M:
        bm //= 2
    return bm


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(x_ref, scale_ref, shift_ref, w_ref, *rest, relu, has_res,
                has_bias, wt):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rest = list(rest)
    b_ref = rest.pop(0) if has_bias else None
    r_ref = rest.pop(0) if has_res else None
    y_ref, s1_ref, s2_ref = rest

    i = pl.program_id(0)

    a = x_ref[...].astype(jnp.float32) * scale_ref[...] + shift_ref[...]
    if relu:
        a = jnp.maximum(a, 0.0)
    # wt: the weight arrives in FullyConnected's (N, K) layout and the
    # contraction runs over its trailing axis — no transpose materializes
    dims = (((1,), (1,)), ((), ())) if wt else (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        a.astype(x_ref.dtype), w_ref[...],
        dimension_numbers=dims,
        preferred_element_type=jnp.float32)
    if b_ref is not None:
        acc = acc + b_ref[...]
    if r_ref is not None:
        acc = acc + r_ref[...].astype(jnp.float32)
    y = acc.astype(y_ref.dtype)
    y_ref[...] = y

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    y32 = y.astype(jnp.float32)  # stats of the *stored* values
    s1_ref[...] += jnp.sum(y32, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(jnp.square(y32), axis=0, keepdims=True)


def _fwd_call(x, scale, shift, w, residual, bias, relu, wt, interpret,
              block_m=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = w.shape[0] if wt else w.shape[1]
    # 1-D grid over row blocks, whole K and N per step: x is read exactly
    # once, the weight stays VMEM-resident (supported() bounds k*n), y is
    # written exactly once, and the stats accumulators live in VMEM across
    # the whole grid — minimum possible HBM traffic for this op.
    if block_m is None:
        block_m = _tuned(m, k, n, x.dtype).get("block_m", 0)
    bm = _fit_block(block_m or _auto_block_m(k, n), m)
    grid = (m // bm,)

    wshape = (n, k) if wt else (k, n)
    in_specs = [
        pl.BlockSpec((bm, k), lambda i: (i, 0)),
        pl.BlockSpec((1, k), lambda i: (0, 0)),
        pl.BlockSpec((1, k), lambda i: (0, 0)),
        pl.BlockSpec(wshape, lambda i: (0, 0)),
    ]
    args = [x, scale.reshape(1, k), shift.reshape(1, k), w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, n), lambda i: (0, 0)))
        args.append(bias.astype(jnp.float32).reshape(1, n))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, n), lambda i: (i, 0)))
        args.append(residual)

    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, relu=relu,
                          has_res=residual is not None,
                          has_bias=bias is not None, wt=wt),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return y, s1[0], s2[0]


# ---------------------------------------------------------------------------
# backward: one combined kernel -> dx, dW, dscale, dshift
# ---------------------------------------------------------------------------
def _bwd_kernel(x_ref, dy_ref, scale_ref, shift_ref, w_ref,
                dx_ref, dw_ref, dscale_ref, dshift_ref, *, relu, wt):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dshift_ref[...] = jnp.zeros_like(dshift_ref)

    x = x_ref[...].astype(jnp.float32)
    u = x * scale_ref[...] + shift_ref[...]
    a = jnp.maximum(u, 0.0) if relu else u
    dy = dy_ref[...]

    # dW += a^T @ dy (K, N) — or dy^T @ a for the (N, K) wt layout —
    # (contraction over the row block either way)
    if wt:
        dw_ref[...] += jax.lax.dot_general(
            dy, a.astype(dy.dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        dw_ref[...] += jax.lax.dot_general(
            a.astype(dy.dtype), dy,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # du = (dy @ W^T) * relu'(u)
    dims = (((1,), (0,)), ((), ())) if wt else (((1,), (1,)), ((), ()))
    dz = jax.lax.dot_general(
        dy, w_ref[...],
        dimension_numbers=dims,
        preferred_element_type=jnp.float32)
    du = jnp.where(u > 0.0, dz, 0.0) if relu else dz

    dx_ref[...] = (du * scale_ref[...]).astype(dx_ref.dtype)
    dscale_ref[...] += jnp.sum(du * x, axis=0, keepdims=True)
    dshift_ref[...] += jnp.sum(du, axis=0, keepdims=True)


def _bwd_call(x, dy, scale, shift, w, relu, wt, interpret, block_m=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = w.shape[0] if wt else w.shape[1]
    if block_m is None:
        block_m = _tuned(m, k, n, x.dtype).get("block_m_bwd", BLOCK_M_BWD)
    bm = _fit_block(block_m or BLOCK_M_BWD, m)

    wshape = (n, k) if wt else (k, n)
    dx, dw, ds, db = pl.pallas_call(
        functools.partial(_bwd_kernel, relu=relu, wt=wt),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec(wshape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec(wshape, lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), x.dtype),
            jax.ShapeDtypeStruct(wshape, jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(x, dy, scale.reshape(1, k), shift.reshape(1, k), w)
    return dx, dw, ds[0], db[0]


# ---------------------------------------------------------------------------
# public op: custom_vjp (built lazily, cached per variant)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _build(relu, has_res, has_bias, wt, interpret):
    import jax
    import jax.numpy as jnp

    def unpack(extra):
        extra = list(extra)
        bias = extra.pop(0) if has_bias else None
        res = extra.pop(0) if has_res else None
        return bias, res

    @jax.custom_vjp
    def fused(x, scale, shift, w, *extra):
        bias, res = unpack(extra)
        return _fwd_call(x, scale, shift, w, res, bias, relu, wt,
                         interpret)

    def fwd(x, scale, shift, w, *extra):
        bias, res = unpack(extra)
        out = _fwd_call(x, scale, shift, w, res, bias, relu, wt,
                        interpret)
        return out, (x, scale, shift, w, out[0])

    def bwd(saved, cts):
        x, scale, shift, w, y = saved
        dy, dysum, dysumsq = cts
        # fold the stats outputs' cotangents into an effective dy:
        #   d/dy [ sum(y).dysum + sum(y^2).dysumsq ] = dysum + 2 y dysumsq
        dy_eff32 = (dy.astype(jnp.float32) + dysum[None, :]
                    + 2.0 * y.astype(jnp.float32) * dysumsq[None, :])
        dy_eff = dy_eff32.astype(x.dtype)
        dx, dw, dscale, dshift = _bwd_call(x, dy_eff, scale, shift, w,
                                           relu, wt, interpret)
        grads = (dx, dscale, dshift, dw.astype(w.dtype))
        if has_bias:
            # column sums of the effective dy; XLA fuses this into the
            # dy_eff fold above (one elementwise producer, one reduce)
            grads = grads + (jnp.sum(dy_eff32, axis=0),)
        if has_res:
            grads = grads + (dy_eff,)
        return grads

    fused.defvjp(fwd, bwd)
    return fused


def fused_scale_relu_matmul(x, scale, shift, w, residual=None, relu=True,
                            bias=None, wt=False, interpret=False):
    """y = relu(x*scale + shift) @ w (+ bias) (+ residual); returns
    (y, ysum, ysumsq).

    x: (M, K); scale, shift: (K,) f32; w: (K, N) — or (N, K) under
    ``wt=True`` (FullyConnected's weight layout, contracted in place);
    bias: (N,) or None; residual: (M, N) or None.  ysum/ysumsq are
    per-output-channel sums over M of the stored y — the next
    BatchNorm's sufficient statistics, produced in the epilogue so no
    later pass re-reads y.  Differentiable (custom_vjp); the stats
    outputs' cotangents are folded into the backward, so BN's backward-
    through-statistics terms arrive through ordinary autodiff
    composition.
    """
    fn = _build(bool(relu), residual is not None, bias is not None,
                bool(wt), bool(interpret))
    extra = ()
    if bias is not None:
        extra = extra + (bias,)
    if residual is not None:
        extra = extra + (residual,)
    return fn(x, scale, shift, w, *extra)


def reference_impl(x, scale, shift, w, residual=None, relu=True, bias=None,
                   wt=False):
    """Plain-XLA composition with identical semantics, for tests/fallback."""
    import jax
    import jax.numpy as jnp

    a = x.astype(jnp.float32) * scale + shift
    if relu:
        a = jnp.maximum(a, 0.0)
    dims = (((1,), (1,)), ((), ())) if wt else (((1,), (0,)), ((), ()))
    y = jax.lax.dot_general(
        a.astype(x.dtype), w, dimension_numbers=dims,
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    y = y.astype(x.dtype)
    y32 = y.astype(jnp.float32)
    return y, jnp.sum(y32, axis=0), jnp.sum(jnp.square(y32), axis=0)


# ---------------------------------------------------------------------------
# priced HBM bytes per path (the roofline machinery)
# ---------------------------------------------------------------------------

def priced_fused_cost(m, k, n, dtype, relu=False, has_res=False,
                      has_bias=True, interpret=True):
    """HBM bytes of one LN->linear segment per path, priced with
    :func:`~mxnet_tpu.analysis.cost.program_cost`.

    The **einsum path** is priced at engine-op granularity — one program
    per graph op of the fallback composition (the affine scale, the
    affine shift, the ReLU prologue when present, the matmul+bias, the
    residual add), each op's operands and results a full HBM round trip
    — which is both the reference engine's per-op dispatch semantics
    and the materialization worst case for separately-rooted
    elementwise fusions.  The **fused path** is ONE program: the Pallas
    kernel's operands in, y (+ the two (N,) stats rows) out.  Returns
    ``{"einsum_bytes", "fused_bytes", "ratio", "phases"}``.
    """
    import jax
    import jax.numpy as jnp

    from ..analysis.cost import program_cost

    dt = jnp.dtype(dtype)
    x_s = jax.ShapeDtypeStruct((m, k), dt)
    g_s = jax.ShapeDtypeStruct((k,), jnp.float32)
    w_s = jax.ShapeDtypeStruct((n, k), dt)
    b_s = jax.ShapeDtypeStruct((n,), dt)
    y_s = jax.ShapeDtypeStruct((m, n), dt)

    phases = {}
    # 1./2. the affine scale and shift (two broadcast ops in the graph)
    phases["affine_mul"] = program_cost(
        jax.jit(lambda x, g: x * g), (x_s, g_s))["bytes"]
    phases["affine_add"] = program_cost(
        jax.jit(lambda x, g: x + g), (x_s, g_s))["bytes"]
    # 3. the ReLU prologue (its own Activation op when present)
    if relu:
        phases["relu"] = program_cost(
            jax.jit(lambda x: jnp.maximum(x, 0)), (x_s,))["bytes"]
    # 4. the matmul (+bias — one FullyConnected op)
    if has_bias:
        fn = jax.jit(lambda x, w, b: jnp.dot(x, w.T) + b)
        phases["matmul"] = program_cost(fn, (x_s, w_s, b_s))["bytes"]
    else:
        phases["matmul"] = program_cost(
            jax.jit(lambda x, w: jnp.dot(x, w.T)), (x_s, w_s))["bytes"]
    # 5. the residual add (its own elemwise op)
    if has_res:
        phases["residual"] = program_cost(
            jax.jit(lambda y, r: y + r), (y_s, y_s))["bytes"]
    einsum = sum(phases.values())

    # fused: ONE pass — kernel operands in, y + two (N,) stat rows out
    scale_s = jax.ShapeDtypeStruct((k,), jnp.float32)
    args = [x_s, scale_s, scale_s, w_s]
    kw = {"relu": relu, "wt": True, "interpret": interpret}
    if has_bias:
        args.append(b_s)
    if has_res:
        args.append(y_s)

    def fused_fn(x, scale, shift, w, *extra):
        extra = list(extra)
        bias = extra.pop(0) if has_bias else None
        res = extra.pop(0) if has_res else None
        return fused_scale_relu_matmul(x, scale, shift, w, residual=res,
                                       bias=bias, **kw)

    fused = program_cost(jax.jit(fused_fn), tuple(args))["bytes"]
    return {"einsum_bytes": int(einsum), "fused_bytes": int(fused),
            "ratio": round(fused / einsum, 4) if einsum else None,
            "phases": {p: int(v) for p, v in phases.items()}}


# ---------------------------------------------------------------------------
# tunable space (ops/tuning.py): block_m / block_m_bwd per shape class
# ---------------------------------------------------------------------------

def _tuning_candidates(shape_class, interpret):
    if interpret:
        # a toy 2-candidate space: tier-1 sweeps run the real machinery
        # on CPU without paying for a grid search
        return [{"block_m": 256, "block_m_bwd": 256},
                {"block_m": 512, "block_m_bwd": 128}]
    out = []
    for bm in (256, 512, 1024, 2048, 4096):
        for bmb in (128, 256, 512):
            out.append({"block_m": bm, "block_m_bwd": bmb})
    return out


def _tuning_runner(params, shape_class, dtype, interpret):
    import jax
    import jax.numpy as jnp

    from . import tuning

    dims = tuning.parse_shape_class(shape_class)
    m, k, n = dims["m"], dims["k"], dims["n"]
    if params["block_m"] and m % min(params["block_m"], m):
        raise tuning.SpaceError("block_m %d does not tile m=%d"
                                % (params["block_m"], m))
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), dt)
    w = jax.random.normal(key, (n, k), dt) * 0.05
    scale = jnp.ones((k,), jnp.float32)
    shift = jnp.zeros((k,), jnp.float32)
    bias = jnp.zeros((n,), dt)
    dy = jnp.ones((m, n), dt)

    bm, bmb = params["block_m"], params["block_m_bwd"]

    @jax.jit
    def probe(x, scale, shift, w, bias, dy):
        y, s1, s2 = _fwd_call(x, scale, shift, w, None, bias, False, True,
                              interpret, block_m=bm or None)
        dx, dw, ds, db = _bwd_call(x, dy, scale, shift, w, False, True,
                                   interpret, block_m=bmb)
        return y, dx, dw

    def run():
        outs = probe(x, scale, shift, w, bias, dy)
        jax.block_until_ready(outs)

    return run


def _register_space():
    from . import tuning

    tuning.register_space(
        "pallas_fused", version=1,
        defaults={"block_m": 0, "block_m_bwd": BLOCK_M_BWD},
        constants=("BLOCK_M", "BLOCK_N", "BLOCK_M_BWD"),
        candidates=_tuning_candidates, runner=_tuning_runner)


_register_space()
