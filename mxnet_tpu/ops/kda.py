"""Kimi delta attention (Kimi Linear, arXiv:2510.26692; the mixer of three
layers in four of ``solar_open2``): what lies between a layer's input
projections and its output projection.

``KimiDeltaAttention`` takes six projected streams of a (B, T, ...) batch,
``H`` heads of ``D`` dims for keys and values alike: ``query``, ``key``,
``value`` and ``gate`` of H * D, the decay's pre-activation ``decay`` of
H * D and ``beta`` of H; token t, head h:

    q, k, v = silu(conv(query)), silu(conv(key)), silu(conv(value))
              (causal, depthwise, a kernel of K a channel, no bias)
    q, k    = q / ||q||_2, k / ||k||_2             (a head; 1e-6 under the root)
    g_t     = -exp(A_log_h) softplus(decay_t + dt_bias)   (H * D log-decays, <= 0)
    beta_t  = 2 sigmoid(beta_t)             (:data:`BETA_SCALE`: in (0, 2))
    S_t     = (I - beta k_t k_t^T) Diag(exp g_t) S_t-1 + beta k_t v_t^T
              (D x D a head, float32)
    o_t     = S_t^T q_t / sqrt(D)
    out     = RMSNorm_D(o_t; one gain of D) * sigmoid(gate_t)

a delta rule (the state is corrected by what it already answers for k_t)
under a decay a CHANNEL of the key dim.  Two leaves of state a sequence: the
last ``K - 1`` rows of [query | key | value] before the convolution
((K - 1, 3 H D), the stream's type) and the matrices ((H, D, D) float32).
:func:`mix` is the one mathematics in the three forms ``ops.linattn.mix`` has:

* a whole sequence from zero state (``state=None``);
* a chunk of ``T`` tokens from a carried state (``nvalid`` given), by blocks
  of :data:`BLOCK` tokens (:func:`_chunked`).  With G the running sum of g
  inside a block, ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` (j < i),
  ``N = (I + A)^-1 [beta V - (beta K exp G) S_0]`` (a triangular solve a
  block), ``o_i = S_0^T (q_i exp G_i) + sum_{j<=i} N_j sum_c q_ic k_jc
  exp(G_ic - G_jc)`` and ``S_C = Diag(exp G_C) S_0 + sum_j (k_j exp(G_C -
  G_j)) N_j^T``.  No ``exp(G_i)`` ever meets an ``exp(-G_j)``: the decayed
  products are taken elementwise inside sub-blocks of :data:`SUB` tokens and
  against a sub-block's first row between them, so every exponent is <= 0.
  Positions past ``nvalid`` are the identity (g = 0, beta = 0, the tail not
  advanced), and a chunk at ``pos0 == 0`` starts from a zero state and a
  zero tail whatever the carried arrays hold;
* one token a row (``T == 1`` over a carried state): the decode step
  (:func:`step`: one Pallas kernel that moves a matrix through HBM once each
  way, ``ops.pallas_delta``, where the backend runs one; elementwise,
  :func:`_step`, elsewhere).  A row whose ``active`` is 0 comes out
  bit-for-bit as it went in.

The recurrence is computed in float32 whatever the streams' type.
"""
from __future__ import annotations

from ..attrs import Param, ParamSchema
from ..obs.scopes import scope as _scope
from ..registry import OpDef, register_op
from .ssm import _conv

OP_NAME = "KimiDeltaAttention"
L2_EPS = 1e-6   # under the root of q's and k's L2 norms
BETA_SCALE = 2.0    # ``kda_allow_neg_eigval``: beta in (0, 2), a step's
                    # transition may have eigenvalues down to -1
BLOCK = 64  # tokens of a block of the chunked form: one triangular solve
SUB = 16    # tokens of a sub-block, BLOCK a whole number of them: the decayed
            # products inside one are elementwise over (SUB, SUB, D), between
            # two a matrix product


def dims(attrs):
    """``(H, D, K)`` of a node."""
    return (int(attrs["num_heads"]), int(attrs["head_dim"]),
            int(attrs.get("conv_kernel", 4)))


def _unit(x, eps):
    """``x / ||x||_2`` over the last axis, ``eps`` under the root."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _decayed_products(x, y, cum):
    """``P[..., m, i, j] = sum_c x[m, i, c] y[j, c] exp(cum[i, c] - cum[j,
    c])`` for j <= i and 0 above the diagonal: ``x`` (..., M, C, D), ``y``
    and ``cum`` (..., C, D), ``cum`` non-increasing along C.  Every exponent
    taken is <= 0."""
    import jax
    import jax.numpy as jnp

    c, d = y.shape[-2:]
    m, ns = x.shape[-3], c // SUB
    hi = jax.lax.Precision.HIGHEST
    lead = y.shape[:-2]
    xs = x.reshape(lead + (m, ns, SUB, d))
    ys = y.reshape(lead + (ns, SUB, d))
    gs = cum.reshape(lead + (ns, SUB, d))
    # inside a sub-block: elementwise over (i, j, c), reduced over c; the M
    # sets of rows side by side as one set of M * SUB rows, so that the
    # product is one reduction and its (i, j, c) terms are never stored
    side = lambda v: jnp.moveaxis(v, -4, -3).reshape(
        lead + (ns, m * SUB, d))
    seg = jnp.concatenate([gs] * m, axis=-2)[..., :, None, :] \
        - gs[..., None, :, :]                           # (.., ns, mi, j, d)
    low = jnp.tile(jnp.tril(jnp.ones((SUB, SUB), bool)), (m, 1))[..., None]
    inner = jnp.sum(side(xs)[..., :, None, :] * ys[..., None, :, :]
                    * jnp.exp(jnp.where(low, seg, -jnp.inf)), axis=-1)
    inner = jnp.moveaxis(inner.reshape(lead + (ns, m, SUB, SUB)), -3, -4)
    # between sub-blocks: rows against their sub-block's first row, columns
    # from that row back (both differences <= 0), then a matrix product
    first = gs[..., :, :1, :]                           # (.., ns, 1, d)
    rows = xs * jnp.exp(gs - first)[..., None, :, :, :]
    back = first - cum[..., None, :, :]                 # (.., ns, C, d)
    cols = y[..., None, :, :] * jnp.exp(jnp.minimum(back, 0.0))
    outer = jnp.einsum("...msid,...sjd->...msij", rows, cols, precision=hi)
    before = (jnp.arange(c) // SUB)[None, :] < jnp.arange(ns)[:, None]
    outer = jnp.where(before[:, None, :], outer, 0.0)   # (.., M, ns, i, C)
    own = jnp.eye(ns, dtype=bool)[:, None, :, None]     # (ns, 1, ns, 1)
    p = outer.reshape(outer.shape[:-1] + (ns, SUB)) \
        + jnp.where(own, inner[..., :, :, None, :], 0.0)
    return p.reshape(lead + (x.shape[-3], c, c))


def _chunked(q, k, v, g, beta, s0, layer="kda"):
    """The recurrence over (B, T) by blocks of :data:`BLOCK` tokens from ``s0``
    (B, H, D, D): ``(o (B, T, H, D), S_T)``.  ``q`` (scaled), ``k``, ``v``,
    ``g`` (B, T, H, D) float32, ``beta`` (B, T, H); ``g`` and ``beta`` 0
    where the step is the identity."""
    import jax
    import jax.numpy as jnp

    b, t, h, d = q.shape
    pad = -t % BLOCK
    if pad:
        grow = lambda x: jnp.pad(x, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (x.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    nc = (t + pad) // BLOCK
    hi = jax.lax.Precision.HIGHEST
    # heads before a block's tokens: the products' two minor dims are a
    # block's tokens and the head's dims
    qc, kc, vc, gc = (jnp.swapaxes(x.reshape(b, nc, BLOCK, h, d), 2, 3)
                      for x in (q, k, v, g))            # (b, nc, h, i, d)
    bc = jnp.swapaxes(beta.reshape(b, nc, BLOCK, h), 2, 3)[..., None]
    cum = jnp.cumsum(gc, axis=3)
    kb = kc * bc
    p = _decayed_products(jnp.stack([kb, qc], axis=3), kc, cum)
    # (b, nc, h, 2, i, j)
    strict = jnp.tril(jnp.ones((BLOCK, BLOCK), bool), -1)
    a = jnp.where(strict, p[..., 0, :, :], 0.0)
    qk = p[..., 1, :, :]
    # N = U - W S_0: the two right-hand sides that do not wait for S_0
    with _scope(layer, "solve"):
        uw = jax.lax.linalg.triangular_solve(
            a + jnp.eye(BLOCK, dtype=a.dtype),
            jnp.concatenate([vc * bc, kb * jnp.exp(cum)], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
    u, w = uw[..., :d], uw[..., d:]
    q_in = qc * jnp.exp(cum)
    to_end = kc * jnp.exp(cum[..., -1:, :] - cum)
    whole = jnp.exp(cum[..., -1, :])                    # (b, nc, h, d)

    def carry(s, blk):
        u_c, w_c, qk_c, q_c, k_c, whole_c = blk
        n = u_c - jnp.einsum("bhid,bhde->bhie", w_c, s, precision=hi)
        o = jnp.einsum("bhid,bhde->bhie", q_c, s, precision=hi) \
            + jnp.einsum("bhij,bhje->bhie", qk_c, n, precision=hi)
        s = s * whole_c[..., None] \
            + jnp.einsum("bhjd,bhje->bhde", k_c, n, precision=hi)
        return s, o

    s_end, o = jax.lax.scan(
        carry, s0, tuple(jnp.moveaxis(x, 1, 0)
                         for x in (u, w, qk, q_in, to_end, whole)))
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3)       # (b, nc, i, h, d)
    return o.reshape(b, t + pad, h, d)[:, :t], s_end


def _step(q, k, v, g, beta, s):
    """One token a row: ``q`` (scaled), ``k``, ``v``, ``g`` (B, H, D),
    ``beta`` (B, H), ``s`` (B, H, D, D); elementwise, no matrix unit.  Both
    sums over the key dim read the decayed state, so that the state is
    passed over twice and not three times: ``S^T q = S'^T q + (q . k) nu``
    with S' = Diag(exp g) S."""
    import jax.numpy as jnp

    s = s * jnp.exp(g)[..., :, None]
    nu = beta[..., None] * (v - jnp.sum(k[..., :, None] * s, axis=-2))
    o = jnp.sum(q[..., :, None] * s, axis=-2) \
        + jnp.sum(q * k, axis=-1, keepdims=True) * nu
    return o, s + k[..., :, None] * nu[..., None, :]


# Which form the last decode step traced took, "kernel" or "elementwise":
# written at trace time as ``ops.attention.DECODE_PATH`` is.  Each such
# dispatch also counts in mx_delta_step_dispatch_total{op, path}, and
# mxnet_tpu.decode records it per program, so that an artifact's meta says
# which step it holds.
STEP_PATH = {"last": None}


def step_kernel_selected(s_shape, mesh_active=False):
    """``(take, interpret)``: whether a decode step over a (B, H, Dk, Dv)
    state runs ``ops.pallas_delta``'s kernel, decided from what the call
    shows, as ``attention.decode_kernel_selected`` decides for a decode row.

    All must hold: a backend that runs Pallas (``attention._kernel_backend``);
    no mesh shards the executor (the kernel is opaque to GSPMD; ``mix`` never
    says one does: ``DecodePredictor`` serves a graph with a delta node on one
    device); and a state
    ``pallas_delta.supported`` tiles, stored as the kernel reads it.  Anything
    else takes :func:`_step`."""
    from . import pallas_delta as _pd
    from .attention import _kernel_backend

    runs, interpret = _kernel_backend()
    if mesh_active or not runs \
            or not _pd.supported(*s_shape[1:], rows=s_shape[0]):
        return False, False
    return True, interpret


def step(q, k, v, g, beta, s, active=None, op=OP_NAME, mesh_active=False):
    """The decode step of the delta rule over B rows, the write masked:
    ``(o (B, H, Dv) float32, S_new)``, a row whose ``active`` (B,) is 0 keeping
    its ``s`` bit for bit.  ``q`` (scaled), ``k`` (B, H, Dk), ``v`` (B, H,
    Dv), ``g`` (B, H, Dk), a log-decay a channel, or (B, H, 1), one a head,
    ``beta`` (B, H), ``s`` (B, H, Dk, Dv) float32.  One algorithm in two
    forms (:func:`step_kernel_selected`); ``op`` names the caller in the
    counter."""
    import jax.numpy as jnp

    from .. import obs as _obs

    take, interpret = step_kernel_selected(s.shape, mesh_active)
    STEP_PATH["last"] = path = "kernel" if take else "elementwise"
    _obs.registry.counter(
        "mx_delta_step_dispatch_total",
        "decode steps of the delta rule traced, by the op that asked and "
        "the form the step took",
        labels=("op", "path")).labels(op=op, path=path).inc()
    if take:
        from . import pallas_delta as _pd

        return _pd.delta_step(q, k, v, g, beta, s, active,
                              interpret=interpret)
    o, new_s = _step(q, k, v, g, beta, s)
    if active is not None:
        on = jnp.asarray(active).reshape(-1).astype(bool)
        new_s = jnp.where(on[:, None, None, None], new_s, s)
    return o, new_s


def mix(attrs, q, k, v, decay, beta, gate, conv_w, a_log, dt_bias,
        out_gamma, state=None, pos0=None, nvalid=None, active=None):
    """``(out (B, T, H * D), (conv tail, S), rows)``: the mixer over the
    projected streams in one of the module's three forms.  ``state`` is the
    two-leaf tuple the B rows carry, ``pos0`` (B,) the first position (0
    where None), ``nvalid`` (B,) a chunk's real tokens, ``active`` (B,) the
    decode step's 0/1 mask; ``rows`` counts the rows whose state advanced."""
    import jax
    import jax.numpy as jnp

    h, d, kernel = dims(attrs)
    layer = attrs.get("__layer__") or "kda"
    b, t, width = q.shape
    if width != h * d or k.shape != q.shape or v.shape != q.shape \
            or decay.shape != q.shape or gate.shape != q.shape \
            or beta.shape != (b, t, h):
        raise ValueError(
            "%s: q %s, k %s, v %s, decay %s, gate %s are not (B, T, %d x "
            "%d) or beta %s not (B, T, %d)"
            % (OP_NAME, q.shape, k.shape, v.shape, decay.shape, gate.shape,
               h, d, beta.shape, h))
    if state is None:
        tail = jnp.zeros((b, kernel - 1, 3 * width), q.dtype)
        s = jnp.zeros((b, h, d, d), jnp.float32)
    else:
        tail, s = state[0], state[1].astype(jnp.float32)
    one = t == 1 and nvalid is None and state is not None
    if nvalid is not None:
        nvalid = jnp.asarray(nvalid, jnp.int32).reshape(-1)
        if pos0 is not None:
            # a slot's first chunk: whatever the last request left is void
            fresh = jnp.asarray(pos0, jnp.int32).reshape(-1) == 0
            tail = jnp.where(fresh[:, None, None], 0, tail)
            s = jnp.where(fresh[:, None, None, None], 0.0, s)
    with _scope(layer, "conv"):
        mixed, tails = zip(*(
            _conv(x, tail[..., i * width:(i + 1) * width],
                  conv_w[i * width:(i + 1) * width], None, nvalid)
            for i, x in enumerate((q, k, v))))
        new_tail = jnp.concatenate(tails, axis=-1)
        heads = lambda x: x.reshape(b, t, h, d)
        qh, kh, vh = (heads(x) for x in mixed)
        qh, kh = _unit(qh, L2_EPS) * d ** -0.5, _unit(kh, L2_EPS)
        g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * heads(
            jax.nn.softplus(decay.astype(jnp.float32)
                            + dt_bias.astype(jnp.float32)))
        bt = jax.nn.sigmoid(beta.astype(jnp.float32)) * BETA_SCALE
    if state is not None:
        # kept in the type it is carried in, whatever the streams' type
        new_tail = new_tail.astype(state[0].dtype)
    rows = jnp.int32(b)
    if one:
        with _scope(layer, "step"):
            o, new_s = step(qh[:, 0], kh[:, 0], vh[:, 0], g[:, 0], bt[:, 0],
                            s, active, OP_NAME)
            o = o[:, None]
            if active is not None:
                on = jnp.asarray(active).reshape(-1).astype(bool)
                new_tail = jnp.where(on[:, None, None], new_tail, state[0])
                rows = jnp.sum(on, dtype=jnp.int32)
    else:
        with _scope(layer, "chunk"):
            if nvalid is not None:
                real = (jnp.arange(t)[None, :] < nvalid[:, None])[..., None]
                g = jnp.where(real[..., None], g, 0.0)
                bt = jnp.where(real, bt, 0.0)
            o, new_s = _chunked(qh, kh, vh, g, bt, s, layer)
    with _scope(layer, "gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + float(attrs.get("eps", 1e-5))) \
            * out_gamma.astype(jnp.float32)
        o = o.reshape(b, t, width) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(q.dtype), (new_tail, new_s), rows


ARGUMENTS = ["query", "key", "value", "decay", "beta", "gate", "conv_weight",
             "A_log", "dt_bias", "out_norm_gamma"]


def _shape(attrs, in_shapes, aux_shapes):
    h, d, kernel = dims(attrs)
    lead = tuple(in_shapes[0][:-1])
    x = lead + (h * d,)
    want = [x, x, x, x, lead + (h,), x, (3 * h * d, kernel), (h,), (h * d,),
            (d,)]
    return want, [x], []


def register_all():
    def fcompute(attrs, inputs, aux, octx):
        return [mix(attrs, *inputs)[0]], list(aux)

    register_op(OpDef(
        OP_NAME, fcompute,
        schema=ParamSchema(
            Param("num_heads", int, required=True),
            Param("head_dim", int, required=True,
                  doc="dims of a head's keys and of its values (D)"),
            Param("conv_kernel", int, default=4,
                  doc="width of the causal depthwise convolution over the "
                      "query, key and value streams"),
            Param("eps", float, default=1e-5, doc="of the output RMSNorm"),
        ),
        num_inputs=len(ARGUMENTS),
        arguments=ARGUMENTS,
        infer_shape=_shape,
        doc="Kimi delta attention over already projected (B, T, H * D) "
            "query, key, value, decay and gate streams and a (B, T, H) beta "
            "stream: a causal depthwise convolution and silu on q, k, v, L2 "
            "norms on q and k, beta = 2 sigmoid(.), the delta rule S_t = (I - beta k k^T) "
            "Diag(exp g_t) S_t-1 + beta k v^T with a decay a channel, o_t = "
            "S_t^T q_t / sqrt(D), an RMSNorm a head and a sigmoid gate; "
            "returns (B, T, H * D).  Stateful in serving: DecodePredictor "
            "carries the convolution's tail and one (H, D, D) float32 matrix "
            "state a slot."))
