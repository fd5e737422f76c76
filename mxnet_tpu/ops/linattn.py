"""Lightning linear attention (Qin et al. 2024, Lightning Attention-2; the
mixer of MiniMax-01 and of ``minicpm_sala``'s ``lightning-attn`` layers):
what lies between a layer's four input projections and its output
projection.

``LightningAttention`` takes the projected ``q``, ``k``, ``v`` and gate
streams of a (B, T, H * D) batch, ``H`` heads of ``D`` dims, and keeps one
float32 matrix a head:

    q, k  = rope(RMSNorm_D(q)), rope(RMSNorm_D(k))      (a gain of D each)
    S_t,h = lambda_h S_t-1,h + k_t,h^T v_t,h            (D x D a head)
    o_t,h = q_t,h S_t,h / sqrt(D)
    out   = RMSNorm(o_t; gain of H * D) * sigmoid(gate_t)

with a fixed decay a head, ``lambda_h = exp(-s_h * slope_scale)``, ``s_h =
2^(-8 (h + 1) / H)`` (:func:`slopes`).  No convolution, no groups, no
input-dependent step: one leaf of state a sequence, (H, D, D) float32, and
no positions but those the rotation reads.  :func:`mix` is the one
mathematics in the three forms ``ops.ssm.mix`` has:

* a whole sequence from zero state (``state=None``);
* a chunk of ``T`` tokens from a carried state (``nvalid`` given), by
  blocks of ``chunk_size``: inside a block the outputs are a masked matrix
  product with the decays laid out as a matrix (never as ``lambda^i`` and
  ``lambda^-j``, which leave float32 at a few hundred positions), and only
  the blocks' end states go from one to the next.  Positions past
  ``nvalid`` are the identity (decay 1, nothing added), and a chunk at
  ``pos0 == 0`` starts from zero whatever the carried array holds;
* one token a row (``T == 1`` over a carried state): the decode step,
  elementwise, the state read once and written once.  A row whose
  ``active`` is 0 comes out bit-for-bit as it went in.

The recurrence is computed in float32 whatever the stream's type.
"""
from __future__ import annotations

import numpy as np

from ..attrs import Param, ParamSchema
from ..obs.scopes import scope as _scope
from ..registry import OpDef, register_op

OP_NAME = "LightningAttention"


def dims(attrs):
    """``(H, D)`` of a node."""
    return int(attrs["num_heads"]), int(attrs["head_dim"])


def slopes(num_heads, slope_scale=1.0):
    """The heads' decay rates ``s_h * slope_scale`` (float64 numpy, (H,)):
    ``s_h = 2^(-8 (h + 1) / H)``, the geometric sequence of Lightning
    Attention-2; a step multiplies head ``h``'s state by ``exp(-s_h *
    slope_scale)``."""
    h = int(num_heads)
    return 2.0 ** (-8.0 * np.arange(1, h + 1) / h) * float(slope_scale)


def _head_norm(x, gamma, eps):
    """RMSNorm over the last axis of ``x`` (..., D) float32."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


def _chunked(q, k, v, log_decay, s0, block):
    """The recurrence over (B, T) by blocks of ``block`` tokens from ``s0``
    (B, H, D, D): ``(o (B, T, H, D), S_T)``.  ``q`` (scaled), ``k``, ``v``
    (B, T, H, D) float32, ``k`` zero where the step is the identity;
    ``log_decay`` (B, T, H) <= 0, 0 there."""
    import jax
    import jax.numpy as jnp

    b, t, h, d = q.shape
    pad = -t % block
    if pad:
        grow = lambda x: jnp.pad(x, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (x.ndim - 2))
        q, k, v, log_decay = grow(q), grow(k), grow(v), grow(log_decay)
    nc = (t + pad) // block
    hi = jax.lax.Precision.HIGHEST
    # heads before a block's tokens: the products' two minor dims are a
    # block's tokens and the head's dims
    qc, kc, vc = (jnp.swapaxes(x.reshape(b, nc, block, h, d), 2, 3)
                  for x in (q, k, v))                   # (b, nc, h, i, d)
    cum = jnp.cumsum(jnp.swapaxes(
        log_decay.reshape(b, nc, block, h), 2, 3), axis=3)  # (b, nc, h, i)
    # inside a block: o_i += sum_{j<=i} exp(cum_i - cum_j) (q_i . k_j) v_j
    seg = cum[..., :, None] - cum[..., None, :]         # (b, nc, h, i, j)
    causal = jnp.tril(jnp.ones((block, block), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    qk = jnp.einsum("bchid,bchjd->bchij", qc, kc, precision=hi)
    o = jnp.einsum("bchij,bchje->bchie", qk * decay, vc, precision=hi)
    # what each block adds to the state by its end, and its whole decay
    to_end = jnp.exp(cum[..., -1:] - cum)               # (b, nc, h, j)
    add = jnp.einsum("bchjd,bchje->bchde", kc * to_end[..., None], vc,
                     precision=hi)
    whole = jnp.exp(cum[..., -1])                       # (b, nc, h)

    def carry(s, blk):
        add_c, whole_c = blk
        return s * whole_c[..., None, None] + add_c, s

    s_end, starts = jax.lax.scan(
        carry, s0, (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                 # (b, nc, h, d, e)
    # across blocks: o_i += exp(cum_i) q_i . S_(block start)
    o = o + jnp.einsum("bchid,bchde->bchie", qc * jnp.exp(cum)[..., None],
                       starts, precision=hi)
    return jnp.swapaxes(o, 2, 3).reshape(b, t + pad, h, d)[:, :t], s_end


def _step(q, k, v, decay, s):
    """One token a row: ``q`` (scaled), ``k``, ``v`` (B, H, D), ``decay``
    (H,), ``s`` (B, H, D, D); elementwise, no matrix unit."""
    import jax.numpy as jnp

    s = s * decay[None, :, None, None] + k[..., :, None] * v[..., None, :]
    return jnp.sum(q[..., :, None] * s, axis=-2), s


def mix(attrs, q, k, v, *rest, state=None, pos0=None, nvalid=None,
        active=None):
    """``(out (B, T, H * D), (S,), rows)``: the mixer over the projected
    streams in one of the module's three forms.  ``rest`` holds, in this
    order and each only where its flag is set: the gate stream
    (``output_gate``), the gains of the q and k norms (``qk_norm``, (D,)
    each) and of the output norm (``output_norm``, (H * D,)).  ``state`` is
    the one-leaf tuple the B rows carry, ``pos0`` (B,) the first position
    (0 where None), ``nvalid`` (B,) a chunk's real tokens, ``active`` (B,)
    the decode step's 0/1 mask; ``rows`` counts the rows whose state
    advanced."""
    import jax
    import jax.numpy as jnp

    from .attention import rope

    h, d = dims(attrs)
    layer = attrs.get("__layer__") or "linattn"
    eps = float(attrs.get("eps", 1e-6))
    rest = list(rest)
    gate = rest.pop(0) if attrs.get("output_gate", True) else None
    q_gamma, k_gamma = (rest.pop(0), rest.pop(0)) \
        if attrs.get("qk_norm", True) else (None, None)
    out_gamma = rest.pop(0) if attrs.get("output_norm", True) else None
    b, t, width = q.shape
    if width != h * d or k.shape != q.shape or v.shape != q.shape:
        raise ValueError("%s: q %s, k %s, v %s are not (B, T, %d x %d)"
                         % (OP_NAME, q.shape, k.shape, v.shape, h, d))
    s = jnp.zeros((b, h, d, d), jnp.float32) if state is None \
        else state[0].astype(jnp.float32)
    step = t == 1 and nvalid is None and state is not None
    start = jnp.zeros((b,), jnp.int32) if pos0 is None else \
        jnp.broadcast_to(jnp.asarray(pos0, jnp.int32).reshape(-1), (b,))
    if nvalid is not None:
        nvalid = jnp.asarray(nvalid, jnp.int32).reshape(-1)
        if pos0 is not None:
            # a slot's first chunk: whatever the last request left is void
            s = jnp.where((start == 0)[:, None, None, None], 0.0, s)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    heads = lambda x: x.astype(jnp.float32).reshape(b, t, h, d)
    qh, kh, vh = heads(q), heads(k), heads(v)
    if q_gamma is not None:
        with _scope(layer, "qk_norm"):
            qh = _head_norm(qh, q_gamma, eps)
            kh = _head_norm(kh, k_gamma, eps)
    if attrs.get("rotary", True):
        turn = lambda x: rope(x.reshape(b, t, h * d), positions, h, d,
                              float(attrs.get("rope_theta", 10000.0)),
                              layer=layer).reshape(b, t, h, d)
        qh, kh = turn(qh), turn(kh)
    qh = qh * (1.0 / np.sqrt(d))
    rate = jnp.asarray(slopes(h, attrs.get("slope_scale", 1.0)),
                       jnp.float32)
    rows = jnp.int32(b)
    if step:
        with _scope(layer, "step"):
            o, new_s = _step(qh[:, 0], kh[:, 0], vh[:, 0], jnp.exp(-rate), s)
            o = o[:, None]
            if active is not None:
                on = jnp.asarray(active).reshape(-1).astype(bool)
                new_s = jnp.where(on[:, None, None, None], new_s, state[0])
                rows = jnp.sum(on, dtype=jnp.int32)
    else:
        with _scope(layer, "chunk"):
            log_decay = jnp.broadcast_to(-rate, (b, t, h))
            if nvalid is not None:
                real = jnp.arange(t)[None, :] < nvalid[:, None]
                log_decay = jnp.where(real[..., None], log_decay, 0.0)
                kh = jnp.where(real[..., None, None], kh, 0.0)
            o, new_s = _chunked(qh, kh, vh, log_decay, s,
                                int(attrs.get("chunk_size", 256)))
    with _scope(layer, "gate_norm"):
        o = o.reshape(b, t, h * d)
        if out_gamma is not None:
            o = _head_norm(o, out_gamma, eps)
        if gate is not None:
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(q.dtype), (new_s,), rows


def _flags(attrs):
    return (bool(attrs.get("output_gate", True)),
            bool(attrs.get("qk_norm", True)),
            bool(attrs.get("output_norm", True)))


def _arguments(attrs):
    gate, qk, out = _flags(attrs)
    return ["query", "key", "value"] + (["gate"] if gate else []) \
        + (["q_norm_gamma", "k_norm_gamma"] if qk else []) \
        + (["out_norm_gamma"] if out else [])


def _shape(attrs, in_shapes, aux_shapes):
    h, d = dims(attrs)
    gate, qk, out = _flags(attrs)
    x = tuple(in_shapes[0][:-1]) + (h * d,)
    want = [x] * (3 + gate) + [(d,), (d,)] * qk + [(h * d,)] * out
    return want, [x], []


def register_all():
    def fcompute(attrs, inputs, aux, octx):
        return [mix(attrs, *inputs)[0]], list(aux)

    register_op(OpDef(
        OP_NAME, fcompute,
        schema=ParamSchema(
            Param("num_heads", int, required=True),
            Param("head_dim", int, required=True),
            Param("slope_scale", float, default=1.0,
                  doc="multiplies every head's decay rate 2^(-8(h+1)/H) "
                      "(MiniMax-01 scales them by the layer's depth)"),
            Param("rotary", bool, default=True,
                  doc="rotate q and k (the whole head, half-split pairing) "
                      "at the token's position"),
            Param("rope_theta", float, default=10000.0),
            Param("qk_norm", bool, default=True,
                  doc="RMSNorm over each q and k head, two gains of D"),
            Param("output_norm", bool, default=True,
                  doc="RMSNorm over the H * D outputs before the gate"),
            Param("output_gate", bool, default=True,
                  doc="a fourth stream: the output is multiplied by its "
                      "sigmoid"),
            Param("chunk_size", int, default=256,
                  doc="block of the chunked form (a sequence or a chunk)"),
            Param("eps", float, default=1e-6, doc="of the RMSNorms"),
        ),
        num_inputs=lambda a: len(_arguments(a)),
        arguments=_arguments,
        infer_shape=_shape,
        doc="Lightning linear attention over already projected (B, T, "
            "H * D) q, k, v and gate streams: per-head RMSNorm and rotary "
            "on q and k, the recurrence S_t = lambda_h S_t-1 + k_t^T v_t "
            "with a fixed decay a head, o_t = q_t S_t / sqrt(D), an "
            "RMSNorm and a sigmoid gate; returns (B, T, H * D).  Stateful "
            "in serving: DecodePredictor carries one (H, D, D) float32 "
            "matrix state a slot."))
