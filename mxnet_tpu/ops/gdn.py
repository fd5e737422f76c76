"""Gated DeltaNet (Yang, Kautz, Hatamizadeh, arXiv:2412.06464; the mixer of
three layers in four of ``olmo_hybrid``): what lies between a layer's input
projections and its output projection.

``GatedDeltaNet`` takes six projected streams of a (B, T, ...) batch, ``H``
heads whose keys have ``Dk`` dims and whose values ``Dv``: ``query`` and
``key`` of H * Dk, ``value`` and ``gate`` of H * Dv, the decay's
pre-activation ``decay`` of H and ``beta`` of H; token t, head h:

    q, k, v = silu(conv(query)), silu(conv(key)), silu(conv(value))
              (causal, depthwise, a kernel of K a channel, no bias)
    q, k    = q / ||q||_2, k / ||k||_2      (a head; 1e-6 under the root)
    g_t     = -exp(A_log_h) softplus(decay_t + dt_bias_h)
              (ONE log-decay a head)
    beta_t  = 2 sigmoid(beta_t)         (``ops.kda.BETA_SCALE``: in (0, 2))
    S_t     = (I - beta k_t k_t^T) exp(g_t) S_t-1 + beta k_t v_t^T
              (Dk x Dv a head, float32)
    o_t     = S_t^T q_t / sqrt(Dk)
    out     = RMSNorm_Dv(o_t; one gain of Dv) * silu(gate_t)

the delta rule of ``ops.kda`` under a decay a HEAD, which is a scalar and so
leaves every product over the key dim: ``k_i . k_j exp(G_i - G_j)`` is the
plain ``K K^T`` times a (C, C) matrix of decays.  Two leaves of state a
sequence: the last ``K - 1`` rows of [query | key | value] before the
convolution ((K - 1, 2 H Dk + H Dv), the stream's type) and the matrices
((H, Dk, Dv) float32).  :func:`mix` is the one mathematics in the three forms
``ops.kda.mix`` has, over that module's own ``_unit`` and ``step`` (a step
is blind to Dk != Dv and takes the head's decay broadcast over the key dim)
and ``ops.ssm._conv``:

* a whole sequence from zero state (``state=None``);
* a chunk of ``T`` tokens from a carried state (``nvalid`` given), by blocks
  of :data:`BLOCK` tokens (:func:`_chunked`): with G the running sum of g
  inside a block and ``Gamma_ij = exp(G_i - G_j)`` for j <= i (every exponent
  <= 0, taken on the (C, C) matrix), ``A = strict_lower(Diag(beta) (K K^T) o
  Gamma)``, ``N = (I + A)^-1 [Diag(beta) V - Diag(beta exp G) K S_0]`` (a
  triangular solve a block), ``o_i = exp(G_i) q_i S_0 + sum_{j<=i} ((Q K^T)
  o Gamma)_ij N_j`` and ``S_C = exp(G_C) S_0 + sum_j exp(G_C - G_j) k_j
  N_j^T``: matrix products, one mask, one solve.  Positions past ``nvalid``
  are the identity (g = 0, beta = 0, the tail not advanced), and a chunk at
  ``pos0 == 0`` starts from a zero state and a zero tail whatever the carried
  arrays hold;
* one token a row (``T == 1`` over a carried state): the decode step,
  ``ops.kda.step`` (one Pallas kernel where the backend runs one,
  elementwise elsewhere).  A row whose ``active`` is 0 comes out bit-for-bit
  as it went in.

The recurrence is computed in float32 whatever the streams' type, and the
chunk form's products at :data:`PRECISION` (highest): on the TPU a float32
product at the default precision is ONE bfloat16 pass, whose 2^-9 on ``K
K^T`` the solve carries through 63 rows of forward substitution and the
carried state through every later block; the step form it has to agree with
multiplies in float32 on the vector unit.
"""
from __future__ import annotations

from ..attrs import Param, ParamSchema
from ..obs.scopes import scope as _scope
from ..registry import OpDef, register_op
from .kda import BETA_SCALE, L2_EPS, _unit, step as _delta_step
from .ssm import _conv

OP_NAME = "GatedDeltaNet"
BLOCK = 64  # tokens of a block of the chunked form: one triangular solve
PRECISION = "highest"   # of the chunked form's products (the docstring's
                        # last paragraph)


def dims(attrs):
    """``(H, Dk, Dv, K)`` of a node."""
    return (int(attrs["num_heads"]), int(attrs["key_head_dim"]),
            int(attrs["value_head_dim"]), int(attrs.get("conv_kernel", 4)))


def _chunked(q, k, v, g, beta, s0, layer="gdn"):
    """The recurrence over (B, T) by blocks of :data:`BLOCK` tokens from ``s0``
    (B, H, Dk, Dv): ``(o (B, T, H, Dv), S_T)``.  ``q`` (scaled), ``k`` (B, T,
    H, Dk), ``v`` (B, T, H, Dv), ``g`` and ``beta`` (B, T, H) float32; ``g``
    and ``beta`` 0 where the step is the identity."""
    import jax
    import jax.numpy as jnp

    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % BLOCK
    if pad:
        grow = lambda x: jnp.pad(x, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (x.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    nc = (t + pad) // BLOCK
    hi = PRECISION
    # heads before a block's tokens: the products' two minor dims are a
    # block's tokens and the head's dims
    blocks = lambda x: jnp.swapaxes(
        x.reshape((b, nc, BLOCK, h) + x.shape[3:]), 2, 3)
    qc, kc, vc = blocks(q), blocks(k), blocks(v)        # (b, nc, h, i, d)
    cum = jnp.cumsum(blocks(g), axis=3)                 # (b, nc, h, i)
    bc = blocks(beta)[..., None]
    low = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))
    gamma = jnp.exp(jnp.where(low, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                # (b, nc, h, i, j)
    kb = kc * bc
    a = jnp.einsum("...id,...jd->...ij", kb, kc, precision=hi) \
        * jnp.where(jnp.eye(BLOCK, dtype=bool), 0.0, gamma)
    qk = jnp.einsum("...id,...jd->...ij", qc, kc, precision=hi) * gamma
    into = jnp.exp(cum)[..., None]
    # N = U - W S_0: the two right-hand sides that do not wait for S_0
    with _scope(layer, "solve"):
        uw = jax.lax.linalg.triangular_solve(
            a + jnp.eye(BLOCK, dtype=a.dtype),
            jnp.concatenate([vc * bc, kb * into], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    q_in = qc * into
    to_end = kc * jnp.exp(cum[..., -1:] - cum)[..., None]
    whole = jnp.exp(cum[..., -1])                       # (b, nc, h)

    def carry(s, blk):
        u_c, w_c, qk_c, q_c, k_c, whole_c = blk
        n = u_c - jnp.einsum("bhid,bhde->bhie", w_c, s, precision=hi)
        o = jnp.einsum("bhid,bhde->bhie", q_c, s, precision=hi) \
            + jnp.einsum("bhij,bhje->bhie", qk_c, n, precision=hi)
        s = s * whole_c[..., None, None] \
            + jnp.einsum("bhjd,bhje->bhde", k_c, n, precision=hi)
        return s, o

    s_end, o = jax.lax.scan(
        carry, s0, tuple(jnp.moveaxis(x, 1, 0)
                         for x in (u, w, qk, q_in, to_end, whole)))
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3)       # (b, nc, i, h, dv)
    return o.reshape(b, t + pad, h, dv)[:, :t], s_end


def mix(attrs, q, k, v, decay, beta, gate, conv_w, a_log, dt_bias,
        out_gamma, state=None, pos0=None, nvalid=None, active=None):
    """``(out (B, T, H * Dv), (conv tail, S), rows)``: the mixer over the
    projected streams in one of the module's three forms.  ``state`` is the
    two-leaf tuple the B rows carry, ``pos0`` (B,) the first position (0
    where None), ``nvalid`` (B,) a chunk's real tokens, ``active`` (B,) the
    decode step's 0/1 mask; ``rows`` counts the rows whose state advanced."""
    import jax
    import jax.numpy as jnp

    h, dk, dv, kernel = dims(attrs)
    layer = attrs.get("__layer__") or "gdn"
    b, t, _ = q.shape
    kw, vw = h * dk, h * dv
    if q.shape != (b, t, kw) or k.shape != q.shape \
            or v.shape != (b, t, vw) or gate.shape != v.shape \
            or decay.shape != (b, t, h) or beta.shape != decay.shape:
        raise ValueError(
            "%s: q %s, k %s are not (B, T, %d x %d), v %s, gate %s not (B, "
            "T, %d x %d) or decay %s, beta %s not (B, T, %d)"
            % (OP_NAME, q.shape, k.shape, h, dk, v.shape, gate.shape, h, dv,
               decay.shape, beta.shape, h))
    cuts = (0, kw, 2 * kw, 2 * kw + vw)
    if state is None:
        tail = jnp.zeros((b, kernel - 1, cuts[-1]), q.dtype)
        s = jnp.zeros((b, h, dk, dv), jnp.float32)
    else:
        tail, s = state[0], state[1].astype(jnp.float32)
    step = t == 1 and nvalid is None and state is not None
    if nvalid is not None:
        nvalid = jnp.asarray(nvalid, jnp.int32).reshape(-1)
        if pos0 is not None:
            # a slot's first chunk: whatever the last request left is void
            fresh = jnp.asarray(pos0, jnp.int32).reshape(-1) == 0
            tail = jnp.where(fresh[:, None, None], 0, tail)
            s = jnp.where(fresh[:, None, None, None], 0.0, s)
    with _scope(layer, "conv"):
        mixed, tails = zip(*(
            _conv(x, tail[..., lo:up], conv_w[lo:up], None, nvalid)
            for x, lo, up in zip((q, k, v), cuts, cuts[1:])))
        new_tail = jnp.concatenate(tails, axis=-1)
        qh, kh = (_unit(x.reshape(b, t, h, dk), L2_EPS) for x in mixed[:2])
        qh = qh * dk ** -0.5
        vh = mixed[2].reshape(b, t, h, dv)
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            decay.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        bt = jax.nn.sigmoid(beta.astype(jnp.float32)) * BETA_SCALE
    if state is not None:
        # kept in the type it is carried in, whatever the streams' type
        new_tail = new_tail.astype(state[0].dtype)
    rows = jnp.int32(b)
    if step:
        with _scope(layer, "step"):
            # the head's one decay over its key dims: (B, H, 1) broadcasts
            o, new_s = _delta_step(
                qh[:, 0], kh[:, 0], vh[:, 0], g[:, 0, :, None], bt[:, 0], s,
                active, OP_NAME)
            o = o[:, None]
            if active is not None:
                on = jnp.asarray(active).reshape(-1).astype(bool)
                new_tail = jnp.where(on[:, None, None], new_tail, state[0])
                rows = jnp.sum(on, dtype=jnp.int32)
    else:
        with _scope(layer, "chunk"):
            if nvalid is not None:
                real = jnp.arange(t)[None, :, None] < nvalid[:, None, None]
                g = jnp.where(real, g, 0.0)
                bt = jnp.where(real, bt, 0.0)
            o, new_s = _chunked(qh, kh, vh, g, bt, s, layer)
    with _scope(layer, "gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + float(attrs.get("eps", 1e-6))) \
            * out_gamma.astype(jnp.float32)
        o = o.reshape(b, t, vw) * jax.nn.silu(gate.astype(jnp.float32))
    return o.astype(q.dtype), (new_tail, new_s), rows


ARGUMENTS = ["query", "key", "value", "decay", "beta", "gate", "conv_weight",
             "A_log", "dt_bias", "out_norm_gamma"]


def _shape(attrs, in_shapes, aux_shapes):
    h, dk, dv, kernel = dims(attrs)
    lead = tuple(in_shapes[0][:-1])
    xk, xv, xh = lead + (h * dk,), lead + (h * dv,), lead + (h,)
    want = [xk, xk, xv, xh, xh, xv, (2 * h * dk + h * dv, kernel), (h,),
            (h,), (dv,)]
    return want, [xv], []


def register_all():
    def fcompute(attrs, inputs, aux, octx):
        return [mix(attrs, *inputs)[0]], list(aux)

    register_op(OpDef(
        OP_NAME, fcompute,
        schema=ParamSchema(
            Param("num_heads", int, required=True),
            Param("key_head_dim", int, required=True,
                  doc="dims of a head's queries and keys (Dk)"),
            Param("value_head_dim", int, required=True,
                  doc="dims of a head's values and outputs (Dv)"),
            Param("conv_kernel", int, default=4,
                  doc="width of the causal depthwise convolution over the "
                      "query, key and value streams"),
            Param("eps", float, default=1e-6, doc="of the output RMSNorm"),
        ),
        num_inputs=len(ARGUMENTS),
        arguments=ARGUMENTS,
        infer_shape=_shape,
        doc="Gated DeltaNet over already projected (B, T, H * Dk) query and "
            "key, (B, T, H * Dv) value and gate and (B, T, H) decay and beta "
            "streams: a causal depthwise convolution and silu on q, k, v, L2 "
            "norms on q and k, beta = 2 sigmoid(.), the delta rule S_t = (I - "
            "beta k k^T) exp(g_t) S_t-1 + beta k v^T with ONE decay a head, "
            "o_t = S_t^T q_t / sqrt(Dk), an RMSNorm a head and a silu gate; "
            "returns (B, T, H * Dv).  Stateful in serving: DecodePredictor "
            "carries the convolution's tail and one (H, Dk, Dv) float32 "
            "matrix state a slot."))
