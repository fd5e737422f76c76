"""The selective state-space sequence mixer (Mamba-2 / SSD, Dao & Gu 2024):
what lies between a block's input projection and its output projection.

``SelectiveSSM`` takes the projected stream ``[z | x | B | C | dt]`` of a
(B, T, ...) batch, ``H`` heads of ``P`` channels, a state of ``N`` a channel
and ``G`` groups that share B and C (head ``k`` reads group ``k // (H/G)``):

    xBC   = silu(conv1d_causal_depthwise([x | B | C]; width K, bias))
    dt_k  = softplus(dt_k + dt_bias_k);   A_k = -exp(A_log_k)
    S_t,k = exp(dt_t,k A_k) S_t-1,k + dt_t,k x_t,k (x) B_t,g    (P x N a head)
    y_t,k = S_t,k C_t,g + D_k x_t,k
    out   = GroupRMSNorm(y * silu(z); G groups, gamma)

The mixer is stateful as attention is, but what it carries a sequence has no
positions: the last ``K - 1`` rows of ``[x | B | C]`` before the convolution
(the conv tail) and the state ``S``.  :func:`mix` is the one mathematics in
the three forms the callers need:

* a whole sequence from zero state (``state=None``): ``Module`` forward, and
  the shape probe of the serving path;
* a chunk of ``T`` tokens from a carried state (``nvalid`` given): chunked
  prefill.  The recurrence runs by the chunked algorithm of the paper at
  ``chunk_size``: inside a block of ``chunk_size`` tokens every output is a
  masked matrix product, and only the blocks' end states are carried one
  to the next.  Positions past ``nvalid`` are padding and must not advance
  the state: there the step is the identity (``dt = 0``: decay 1, input 0)
  and the conv tail is taken at the last real token.  A chunk at ``pos0 ==
  0`` starts from zero state whatever the carried arrays hold, so a serving
  slot is reused with no clearing program;
* one token a row (``T == 1``, ``active`` given): the decode step.  A row
  whose ``active`` is 0 (a slot that is empty or mid-prefill) comes out
  bit-for-bit as it went in: there is no scratch row to send a junk write to.

The recurrence is computed in float32 whatever the stream's type; ``S`` is
kept in ``state_dtype`` and the conv tail in the stream's type.
"""
from __future__ import annotations

from ..attrs import Param, ParamSchema
from ..obs.scopes import scope as _scope
from ..registry import OpDef, register_op

OP_NAME = "SelectiveSSM"


def dims(attrs):
    """``(H, P, N, G, K)`` and the widths ``(d_ssm, conv_dim, in_dim)`` of
    a node: ``in_dim = d_ssm + conv_dim + H`` is the projected stream's."""
    h, p = int(attrs["num_heads"]), int(attrs["head_dim"])
    n, g = int(attrs["state_size"]), int(attrs.get("n_groups", 1))
    k = int(attrs.get("conv_kernel", 4))
    if h % g:
        raise ValueError("%s: num_heads=%d not divisible by n_groups=%d"
                         % (OP_NAME, h, g))
    d_ssm, conv_dim = h * p, h * p + 2 * g * n
    return (h, p, n, g, k), (d_ssm, conv_dim, d_ssm + conv_dim + h)


def state_avals(attrs, rows, dtype):
    """Shapes and types of what ``rows`` sequences carry: ``((rows, K - 1,
    conv_dim), stream dtype), ((rows, H, P, N), state_dtype)``."""
    (h, p, n, _, k), (_, conv_dim, _) = dims(attrs)
    return (((rows, k - 1, conv_dim), dtype),
            ((rows, h, p, n), attrs.get("state_dtype", "float32")))


def _conv(xbc, tail, w, bias, nvalid):
    """Causal depthwise convolution of ``xbc`` (B, T, C) behind ``tail``
    (B, K - 1, C) (``bias`` None: without one), then silu; and the new tail:
    the ``K - 1`` rows that end at each row's last real token."""
    import jax
    import jax.numpy as jnp

    k, t = w.shape[1], xbc.shape[1]
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w32 = w.astype(jnp.float32)
    out = sum(window[:, i:i + t].astype(jnp.float32) * w32[:, i]
              for i in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if nvalid is None:
        new_tail = window[:, t:]
    else:
        new_tail = jax.vmap(lambda win, n: jax.lax.dynamic_slice_in_dim(
            win, n, k - 1, axis=0))(window, nvalid)
    return jax.nn.silu(out), new_tail


def _scan_chunked(x, dt, a, bm, cm, s0, q):
    """The recurrence over (B, T) by blocks of ``q`` tokens, from ``s0``
    (B, H, P, N): ``(y (B, T, H, P), S_T)``.  ``x`` (B, T, H, P), ``dt``
    (B, T, H) (0 where the step is the identity), ``a`` (H,), ``bm`` /
    ``cm`` (B, T, G, N); all float32."""
    import jax
    import jax.numpy as jnp

    b, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    pad = -t % q
    if pad:
        grow = lambda v: jnp.pad(v, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (v.ndim - 2))
        x, dt, bm, cm = grow(x), grow(dt), grow(bm), grow(cm)
    nc, r = (t + pad) // q, h // g
    hi = jax.lax.Precision.HIGHEST
    # blocks first; heads as (group, head of the group) beside B and C
    xc = x.reshape(b, nc, q, g, r, p)
    dtc = dt.reshape(b, nc, q, g, r)
    bc, cc = bm.reshape(b, nc, q, g, n), cm.reshape(b, nc, q, g, n)
    cum = jnp.cumsum(dtc * a.reshape(g, r), axis=2)     # log decay, <= 0
    xdt = xc * dtc[..., None]
    # inside a block: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
    seg = cum[:, :, :, None] - cum[:, :, None, :]       # (b, nc, t, s, g, r)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", cc, bc, precision=hi)
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", decay * cb[..., None], xdt,
                   precision=hi)
    # what each block adds to the state by its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum)              # (b, nc, q, g, r)
    add = jnp.einsum("bcsgr,bcsgrp,bcsgn->bcgrpn", to_end, xdt, bc,
                     precision=hi)
    whole = jnp.exp(cum[:, :, -1])                      # (b, nc, g, r)

    def carry(s, blk):
        add_c, whole_c = blk
        return s * whole_c[..., None, None] + add_c, s

    s_end, starts = jax.lax.scan(
        carry, s0.reshape(b, g, r, p, n),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                 # (b, nc, g, r, p, n)
    # across blocks: y_t += exp(cum_t) C_t . S_(block start)
    y = y + jnp.einsum("bctgn,bcgrpn,bctgr->bctgrp", cc, starts,
                       jnp.exp(cum), precision=hi)
    return y.reshape(b, t + pad, h, p)[:, :t], s_end.reshape(b, h, p, n)


def _step(x, dt, a, bm, cm, s):
    """One token a row: ``x`` (B, H, P), ``dt`` (B, H), ``bm`` / ``cm``
    (B, G, N), ``s`` (B, H, P, N); elementwise, no matrix unit: the state is
    read once and written once."""
    import jax.numpy as jnp

    b, h, p = x.shape
    g, n = bm.shape[1], bm.shape[2]
    r = h // g
    s = s.reshape(b, g, r, p, n)
    decay = jnp.exp(dt * a).reshape(b, g, r, 1, 1)
    xdt = (x * dt[..., None]).reshape(b, g, r, p, 1)
    s = s * decay + xdt * bm.reshape(b, g, 1, 1, n)
    y = jnp.sum(s * cm.reshape(b, g, 1, 1, n), axis=-1)
    return y.reshape(b, h, p), s.reshape(b, h, p, n)


def _gate_norm(y, z, gamma, groups, eps):
    """``GroupRMSNorm(y * silu(z))`` over ``groups`` equal groups of the
    last axis (the gate before the norm)."""
    import jax
    import jax.numpy as jnp

    v = y * jax.nn.silu(z.astype(jnp.float32))
    shape = v.shape
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(shape) * gamma.astype(jnp.float32)


def mix(attrs, data, conv_w, conv_b, dt_bias, a_log, d_skip, gamma,
        state=None, pos0=None, nvalid=None, active=None):
    """``(out (B, T, d_ssm), (conv tail, S), rows)``: the mixer over
    ``data`` (B, T, in_dim) in one of the module's three forms.  ``state``
    is what the B rows carry, ``pos0`` / ``nvalid`` (B,) the chunk's first
    position and number of real tokens, ``active`` (B,) the decode step's
    0/1 mask (one token a row over a carried state is the decode step, with
    or without a mask); ``rows`` counts the rows whose state advanced."""
    import jax
    import jax.numpy as jnp

    (h, p, n, g, k), (d_ssm, conv_dim, in_dim) = dims(attrs)
    layer = attrs.get("__layer__") or "ssm"
    b, t, width = data.shape
    if width != in_dim:
        raise ValueError(
            "%s: input width %d != d_ssm %d + conv_dim %d + num_heads %d"
            % (OP_NAME, width, d_ssm, conv_dim, h))
    tail_aval, s_aval = state_avals(attrs, b, data.dtype)
    if state is None:
        tail = jnp.zeros(*tail_aval)
        s = jnp.zeros(s_aval[0], jnp.float32)
    else:
        tail, s = state[0], state[1].astype(jnp.float32)
    step = t == 1 and nvalid is None and state is not None
    if nvalid is not None:
        nvalid = jnp.asarray(nvalid, jnp.int32).reshape(-1)
        if pos0 is not None:
            # a slot's first chunk: whatever the last request left is void
            fresh = (jnp.asarray(pos0, jnp.int32).reshape(-1) == 0)
            tail = jnp.where(fresh[:, None, None], 0, tail)
            s = jnp.where(fresh[:, None, None, None], 0.0, s)
    z = data[..., :d_ssm]
    xbc = data[..., d_ssm:d_ssm + conv_dim]
    dt = data[..., d_ssm + conv_dim:].astype(jnp.float32)
    with _scope(layer, "conv"):
        xbc, new_tail = _conv(xbc, tail, conv_w, conv_b, nvalid)
    x = xbc[..., :d_ssm].reshape(b, t, h, p)
    bm = xbc[..., d_ssm:d_ssm + g * n].reshape(b, t, g, n)
    cm = xbc[..., d_ssm + g * n:].reshape(b, t, g, n)
    a = -jnp.exp(a_log.astype(jnp.float32))
    dt = jax.nn.softplus(dt + dt_bias.astype(jnp.float32))
    rows = jnp.int32(b)
    if step:
        with _scope(layer, "step"):
            y, new_s = _step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], s)
            y, new_s = y[:, None], new_s.astype(s_aval[1])
            if active is not None:
                on = jnp.asarray(active).reshape(-1).astype(bool)
                new_tail = jnp.where(on[:, None, None], new_tail, state[0])
                new_s = jnp.where(on[:, None, None, None], new_s, state[1])
                rows = jnp.sum(on, dtype=jnp.int32)
    else:
        with _scope(layer, "scan"):
            if nvalid is not None:
                real = jnp.arange(t)[None, :] < nvalid[:, None]
                dt = jnp.where(real[..., None], dt, 0.0)
            y, new_s = _scan_chunked(x, dt, a, bm, cm, s,
                                     int(attrs.get("chunk_size", 128)))
            new_s = new_s.astype(s_aval[1])
    y = y + x * d_skip.astype(jnp.float32)[:, None]
    with _scope(layer, "gate_norm"):
        out = _gate_norm(y.reshape(b, t, d_ssm), z, gamma, g,
                         float(attrs.get("eps", 1e-5)))
    return out.astype(data.dtype), (new_tail, new_s), rows


def _shape(attrs, in_shapes, aux_shapes):
    (h, _, _, _, k), (d_ssm, conv_dim, in_dim) = dims(attrs)
    x = tuple(in_shapes[0])
    want = [x[:-1] + (in_dim,), (conv_dim, k), (conv_dim,), (h,), (h,),
            (h,), (d_ssm,)]
    return want, [x[:-1] + (d_ssm,)], []


def register_all():
    def fcompute(attrs, inputs, aux, octx):
        return [mix(attrs, *inputs)[0]], list(aux)

    register_op(OpDef(
        OP_NAME, fcompute,
        schema=ParamSchema(
            Param("num_heads", int, required=True),
            Param("head_dim", int, required=True,
                  doc="channels a head (P)"),
            Param("state_size", int, required=True,
                  doc="state a channel (N)"),
            Param("n_groups", int, default=1,
                  doc="groups that share B and C; divides num_heads"),
            Param("conv_kernel", int, default=4,
                  doc="width of the causal depthwise convolution"),
            Param("chunk_size", int, default=128,
                  doc="block of the chunked scan (a sequence or a chunk)"),
            Param("eps", float, default=1e-5,
                  doc="of the grouped RMSNorm"),
            Param("state_dtype", str, default="float32",
                  doc="the type the carried state S is kept in"),
        ),
        num_inputs=7,
        arguments=["data", "conv_weight", "conv_bias", "dt_bias", "A_log",
                   "D", "norm_gamma"],
        infer_shape=_shape,
        doc="Mamba-2 selective state-space mixer over an already "
            "projected (B, T, d_ssm + conv_dim + H) stream [z | x | B | C "
            "| dt]: causal depthwise convolution, the selective "
            "recurrence, the skip D, the gate and a grouped RMSNorm; "
            "returns (B, T, d_ssm).  Stateful in serving: "
            "DecodePredictor carries its conv tail and state a slot."))
