"""Attention operators — the long-context leapfrog.

The reference (2017-era MXNet) has no attention op; its long-sequence story
is bucketing + fused RNN (SURVEY §2.5 "Sequence-length scaling").  The TPU
build upgrades that niche with first-class attention that composes with the
mesh axes:

* ``dot_product_attention`` — multi-head scaled-dot-product attention over
  already-projected (B, T, E) tensors (compose MHA from FullyConnected +
  this op, the framework's op-granularity convention).  Pure jnp einsum:
  under the mesh executor, GSPMD partitions it over the ``seq`` axis from
  the input shardings (all-gather/all-to-all sequence parallelism — the
  Ulysses-style path) and over ``model`` for the head dimension.
* For the explicit-collective path (memory-optimal long context), see
  ``mxnet_tpu.parallel.ring.ring_attention`` — blockwise ring attention
  with K/V rotating via ``lax.ppermute`` under ``shard_map``.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from ..attrs import Param, ParamSchema
from ..obs.scopes import scope as _scope
from ..registry import OpDef, register_op


def check_head_groups(num_heads, num_kv_heads, e, ev=None, kv_dim=None,
                      where="dot_product_attention"):
    """Validate a (possibly grouped) head configuration, raising
    ``ValueError``s that NAME the offending dims — the silent-fallthrough
    guards (``e % heads``, ``heads % kv_heads``) all route through here
    so every call path fails with the same loud message.

    Returns ``(kv_heads, group)`` with ``kv_heads`` resolved (0 ->
    ``num_heads``, the MHA default) and ``group = num_heads //
    kv_heads`` — the GQA/MQA group factor G (Ainslie et al. 2023;
    Shazeer 2019 at kv_heads == 1)."""
    heads = int(num_heads)
    kvh = int(num_kv_heads) or heads
    if heads <= 0:
        raise ValueError("%s: num_heads=%d must be positive"
                         % (where, heads))
    if kvh <= 0:
        raise ValueError("%s: num_kv_heads=%d must be positive"
                         % (where, kvh))
    if heads % kvh != 0:
        raise ValueError("%s: num_heads=%d not divisible by "
                         "num_kv_heads=%d" % (where, heads, kvh))
    if e % heads != 0:
        raise ValueError("%s: query embed dim %d not divisible by "
                         "num_heads=%d" % (where, e, heads))
    if ev is not None and ev % kvh != 0:
        raise ValueError("%s: value embed dim %d not divisible by "
                         "num_kv_heads=%d" % (where, ev, kvh))
    if kv_dim is not None and kv_dim != kvh * (e // heads):
        raise ValueError(
            "%s: key embed dim %d != num_kv_heads=%d * head_dim=%d"
            % (where, kv_dim, kvh, e // heads))
    return kvh, heads // kvh


def rope(x, positions, num_heads, rotary_dim, theta, layer="attn"):
    """Rotary position embedding on the first ``rotary_dim`` dims of each
    of ``x``'s (B, t, H*hd) heads, in the half-split (NeoX) pairing: dim
    ``i`` pairs with ``i + rotary_dim/2`` and turns by ``position *
    theta**(-2i/rotary_dim)``; the dims past ``rotary_dim`` pass as they
    are.  ``positions`` is (t,) or (B, t) absolute indices.  Computed in
    float32, returned in ``x``'s dtype."""
    import jax.numpy as jnp

    b, t, e = x.shape
    hd = e // num_heads
    half = int(rotary_dim) // 2
    if half <= 0 or 2 * half > hd:
        raise ValueError("rope: rotary_dim=%d does not fit heads of %d"
                         % (rotary_dim, hd))
    with _scope(layer, "rope"):
        inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
        pos = jnp.broadcast_to(jnp.asarray(positions, jnp.float32), (b, t)) \
            if jnp.ndim(positions) == 2 \
            else jnp.asarray(positions, jnp.float32)[None, :]
        ang = pos[:, :, None, None] * inv            # (B|1, t, 1, half)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        xh = x.reshape(b, t, num_heads, hd).astype(jnp.float32)
        x1, x2 = xh[..., :half], xh[..., half:2 * half]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                               xh[..., 2 * half:]], axis=-1)
        return out.reshape(b, t, e).astype(x.dtype)


def _softmax_with_sink(logits, allowed, sink):
    """Float32 softmax over the last axis of ``logits`` where ``allowed``;
    ``sink`` (shaped to broadcast against the head axes, or None) is one
    more logit in the maximum and the denominator that carries no value:
    the probabilities then sum to less than one."""
    import jax.numpy as jnp

    logits = jnp.where(allowed, logits, jnp.finfo(jnp.float32).min)
    m = jnp.max(logits, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink)
    p = jnp.exp(logits - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink - m)
    return p / den


def _sdpa_extra(q, k, v, num_heads, causal, scale, num_kv_heads, window,
                sink, value_scale, layer):
    """:func:`sdpa` for a node with a window, a sink or a value scale: the
    grouped einsums with the mask built from positions (query ``i`` of
    ``tq`` sits at ``tk - tq + i``) and the sink in the softmax."""
    import jax.numpy as jnp

    b, tq, e = q.shape
    tk, ev = k.shape[1], v.shape[2]
    kvh, g = check_head_groups(num_heads, num_kv_heads, e, ev, k.shape[2],
                               where="sdpa")
    hd = e // num_heads
    scale = scale or 1.0 / np.sqrt(hd)
    qh = q.reshape(b, tq, kvh, g, hd)
    kh = k.reshape(b, tk, kvh, hd)
    vh = v.reshape(b, tk, kvh, ev // kvh)
    with _scope(layer, "scores"):
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qh,
                            kh).astype(jnp.float32) * scale
        qpos = jnp.arange(tq, dtype=jnp.int32)[:, None] + (tk - tq)
        kpos = jnp.arange(tk, dtype=jnp.int32)[None, :]
        allowed = jnp.ones((tq, tk), bool)
        if causal:
            allowed &= kpos <= qpos
        if window:
            allowed &= kpos > qpos - int(window)
        p = _softmax_with_sink(
            logits, allowed[None, None, None],
            None if sink is None else jnp.asarray(sink, jnp.float32)
            .reshape(1, kvh, g, 1, 1))
    out = jnp.einsum("bhgqk,bkhe->bqhge", p.astype(vh.dtype), vh)
    if value_scale != 1.0:
        out = out * jnp.asarray(value_scale, out.dtype)
    return out.reshape(b, tq, num_heads * (ev // kvh))


def sdpa(q, k, v, num_heads=1, causal=False, scale=None, num_kv_heads=0,
         window=0, sink=None, value_scale=1.0, layer="attn"):
    """Multi-head scaled-dot-product attention kernel.

    (B, Tq, E), (B, Tk, Ek), (B, Tk, Ev) -> (B, Tq, H*hdv).  The softmax
    runs in float32 regardless of the input dtype (bf16-safe
    accumulation); the output is cast back to the value dtype.  Shared by
    the registered op and ``parallel.ring.dense_attention`` (one copy of
    the numerics).

    ``num_kv_heads`` (0 = ``num_heads``, plain MHA) enables grouped-query
    attention: K/V carry only ``H_kv`` heads (``Ek == H_kv * hd``) and
    q-head ``h`` attends kv-head ``h // G`` with ``G = H / H_kv`` —
    mapped INSIDE the einsum by reshaping q to (B, Tq, H_kv, G, hd), so
    the G× smaller K/V are never broadcast into a materialized copy.

    ``window`` (0 = none) lets query ``i`` see keys ``i - window + 1 ..
    i`` only; ``sink`` (H,) is a learned per-head logit in the softmax's
    denominator that carries no value; ``value_scale`` multiplies the
    output.  A call with none of the three traces what it traced before
    them; ``layer`` names the scope the sub-scopes sit under.
    """
    import jax.numpy as jnp

    if window or sink is not None or value_scale != 1.0:
        return _sdpa_extra(q, k, v, num_heads, causal, scale, num_kv_heads,
                           window, sink, value_scale, layer)
    b, tq, e = q.shape
    tk = k.shape[1]
    ev = v.shape[2]
    kvh, g = check_head_groups(num_heads, num_kv_heads, e, ev, k.shape[2],
                               where="sdpa")
    hd = e // num_heads
    scale = scale or 1.0 / np.sqrt(hd)
    if g == 1:
        # ungrouped path kept verbatim: G=1 stays bit-identical to the
        # pre-GQA kernel (same einsums in the same order)
        qh = q.reshape(b, tq, num_heads, hd)
        kh = k.reshape(b, tk, num_heads, hd)
        vh = v.reshape(b, tk, num_heads, ev // num_heads)
        with _scope("attn", "scores"):
            logits = jnp.einsum("bqhd,bkhd->bhqk", qh,
                                kh).astype(jnp.float32) * scale
            if causal:
                mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
                logits = jnp.where(mask[None, None], logits,
                                   jnp.finfo(jnp.float32).min)
            m = jnp.max(logits, axis=-1, keepdims=True)
            p = jnp.exp(logits - m)
            p = p / jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhqk,bkhe->bqhe", p.astype(vh.dtype), vh)
        return out.reshape(b, tq, ev)
    qh = q.reshape(b, tq, kvh, g, hd)
    kh = k.reshape(b, tk, kvh, hd)
    vh = v.reshape(b, tk, kvh, ev // kvh)
    with _scope("attn", "scores"):
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qh,
                            kh).astype(jnp.float32) * scale
        if causal:
            mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
            logits = jnp.where(mask[None, None, None], logits,
                               jnp.finfo(jnp.float32).min)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhe->bqhge", p.astype(vh.dtype), vh)
    return out.reshape(b, tq, num_heads * (ev // kvh))


# ---------------------------------------------------------------------------
# Decode mode — incremental attention against a preallocated ring-buffer KV
# cache (the Pope et al. "Efficiently Scaling Transformer Inference" decode
# plan).  The full-sequence op above re-scores the whole prefix for every
# generated token (O(T^2) per sequence); these kernels make decode O(T):
# append the new K/V at the next ring slot, attend the query position(s)
# against the cache with a length mask.  ``mxnet_tpu.decode`` drives them —
# it splits an attention_lm-style symbol into a prefill program and a
# donated decode-step program that calls cache_append + sdpa_decode at every
# dot_product_attention node.  Under a mesh, the cache's E (head) dim is
# sharded on 'model' (an E-split IS a head-group split — see
# parallel/tp_rules.py) so each model shard holds and scores only its own
# head group's cache slice.
# ---------------------------------------------------------------------------

class QuantKV(NamedTuple):
    """A quantized ring-buffer cache: narrow ``data`` plus per-(token,
    head) fp32 ``scale``.

    ``data`` is the (B, C, E) K or V buffer in the narrow storage dtype
    (int8 / fp8); ``scale`` is (B, C, H) float32 — one scale per cache
    slot per head, chosen at append time so each head's hd-wide slice
    fills the storage dtype's representable range.  A jax pytree (both
    leaves donate/shard independently: ``data`` follows
    ``tp_rules.kv_cache_pspec``; ``scale``'s trailing head dim shards the
    same way, an H-split IS the same head-group split).

    The two PAGED pools of a node (``data`` (P, page_tokens, E)) keep
    their scales in ONE plane, the K pool's ``scale``, (P, page_tokens *
    2 * H): a page is one row, token-major, a token's 2 * H floats K's H
    then V's H; the V pool's ``scale`` is None.  A plane (P, page_tokens,
    H) has a minor dimension of 4-32 heads, which fills no lane tile:
    XLA:TPU gave it one layout for the append's scatter, another for the
    products and a third as a parameter, and converted the whole plane
    between them in every serving program, since layout assignment
    carries a consumer's preference back through a gather to its
    operand.  A row of 128-1024 lanes is taken as it lies by the append
    and by the gather (:func:`paged_append_kv`, :func:`paged_gather_kv`),
    which read and write whole rows, and one gather serves both pools.
    The dense ring's plane stays (B, C, H).
    """

    data: object
    scale: object


# quantization range per storage dtype: int8 is symmetric round-to-nearest
# in [-127, 127]; the fp8 variants scale into their finite max so the cast
# never saturates (values are <= qmax by construction)
_KV_QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0, "float8_e5m2": 57344.0}


def kv_qmax(dtype):
    """Quantization range of a KV storage dtype (KeyError = unsupported —
    the MXNET_KV_DTYPE consumer turns that into a config error)."""
    return _KV_QMAX[np.dtype(dtype).name]


def quantize_kv(x, dtype, num_heads=1):
    """(B, t, E) float K/V -> :class:`QuantKV` with per-(token, head)
    scales: ``scale = amax_head / qmax``, ``data = round(x / scale)``
    (int8) or a saturating-range fp8 cast.  All-zero heads (pad slots)
    quantize to zeros under a floor scale instead of dividing by zero."""
    import jax.numpy as jnp

    b, t, e = x.shape
    if e % num_heads != 0:
        raise ValueError("quantize_kv: embed dim %d not divisible by "
                         "num_heads=%d" % (e, num_heads))
    qmax = kv_qmax(dtype)
    xh = x.astype(jnp.float32).reshape(b, t, num_heads, e // num_heads)
    amax = jnp.max(jnp.abs(xh), axis=-1)                      # (B, t, H)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = xh / scale[..., None]
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    data = q.astype(dtype).reshape(b, t, e)
    return QuantKV(data, scale)


def dequantize_kv(cache, num_heads=None, out_dtype=None):
    """:class:`QuantKV` -> the float (B, C, E) buffer the kernels attend
    against (``data * scale`` per head).  Plain arrays pass through, so
    callers handle both cache layouts with one code path.  The head
    count is authoritative in the scale plane's trailing dim;
    ``num_heads``, when given, must agree (a cache built under a
    different head config must fail loudly, not descale wrongly)."""
    import jax.numpy as jnp

    if not isinstance(cache, QuantKV):
        return cache
    b, c, e = cache.data.shape
    h = cache.scale.shape[-1]
    assert num_heads is None or num_heads == h, \
        "cache quantized with %d heads, caller expects %d" % (h, num_heads)
    x = cache.data.astype(jnp.float32).reshape(b, c, h, e // h) \
        * cache.scale[..., None]
    x = x.reshape(b, c, e)
    return x.astype(out_dtype) if out_dtype is not None else x


def cache_append(cache, new, start_pos, num_heads=1, layer="attn"):
    """Write ``new`` (B, t, E) into ring-buffer slots [start_pos,
    start_pos+t) mod C of ``cache`` (B, C, E).

    ``start_pos`` is the number of tokens already in the cache — a scalar
    or a per-sequence (B,) vector (batched serving: each slot at its own
    length).  The t == 1 decode hot path is a per-row
    ``jax.lax.dynamic_update_slice`` (never wraps: one slot always fits);
    multi-position appends (the speculative verify pass's fixed-width
    k+1-token append) scatter, wrapping modulo C so the cache keeps the
    latest C tokens (sliding-window semantics — attention over a set of
    keys is order-agnostic, positions having been added at the input
    embedding).  Rejected speculative entries are not un-written: the
    caller rolls back ``lens`` instead, the length mask hides them, and
    the next append overwrites them in place.

    A :class:`QuantKV` cache quantizes ``new`` on the way in
    (per-(token, head) scales — pass ``num_heads``); both its leaves
    update at the same slots.  Traceable; donated-safe (pure functional
    update).
    """
    import jax
    import jax.numpy as jnp

    with _scope(layer, "kv_append"):
        if isinstance(cache, QuantKV):
            qnew = quantize_kv(new, cache.data.dtype, num_heads)
            return QuantKV(
                cache_append(cache.data, qnew.data, start_pos, layer=layer),
                cache_append(cache.scale, qnew.scale, start_pos,
                             layer=layer))
        b, t = new.shape[0], new.shape[1]
        c = cache.shape[1]
        start = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32).reshape(-1),
                                 (b,))
        new = new.astype(cache.dtype)
        if t == 1:
            slot = start % c
            zero = (jnp.int32(0),) * (new.ndim - 2)
            return jax.vmap(
                lambda buf, row, s: jax.lax.dynamic_update_slice(
                    buf, row, (s,) + zero))(cache, new, slot)
        if t > c:
            # only the latest C tokens can land; trimming BEFORE the scatter
            # keeps the slot indices unique per row (scatter order with
            # duplicate indices is backend-unspecified)
            new = new[:, -c:]
            start = start + (t - c)
            t = c
        pos = (start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]) % c
        return cache.at[jnp.arange(b)[:, None], pos].set(new)


def _sdpa_cache(q, k_cache, v_cache, total_len, num_heads, scale,
                num_kv_heads=0, mesh_active=False, window=0, sink=None,
                value_scale=1.0, layer="attn", block=None, allow=None):
    """Shared length-masked cache-attention core behind
    :func:`sdpa_decode` (tq == 1) and :func:`sdpa_verify` (tq == k+1).

    A quantized cache (:class:`QuantKV`) is attended as it is stored.  Its
    scale is per (token, kv-head), constant along the contracted head
    width, so it commutes with both products: they read the narrow
    ``data`` plane cast to the query's dtype (every int8 / fp8 value is
    exact in bfloat16) and accumulate in float32, the key scales multiply
    the float32 logits before the mask, and the value scales multiply the
    normalized probabilities before the PV product.  The float (B, C, E)
    copy :func:`dequantize_kv` returns is never built; the result is that
    of attending the dequantized buffers densely, which is what the
    parity tests pin, and with more than one query row it leaves in the
    query's dtype (the stream's: :func:`_out_dtype`; one row leaves
    float32).  K and V go each by their own type, and a
    plain-array cache takes the plain einsums.

    One query row (tq == 1, the decode step) over a stored plane takes
    the products in another shape.  Per head they are matrix-vector
    products, which XLA:TPU lowers to a float32 multiply-reduce over a
    float32 copy of the plane laid out by heads, whatever dtype it was
    handed.  So the plane stays (B, C, E) and the query row becomes
    block-diagonal over kv-heads, (B, E, H): one product per slot whose
    contraction is the plane's own minor dimension, H columns of the MXU
    where a head's product would fill one; PV likewise, keeping the
    diagonal blocks of a (B, H, Ev) result.  The float operand (q, then
    p) is taken at ``Precision.HIGHEST`` — three bfloat16 pieces against
    a plane that is exact in one — because the multiply-reduce this
    replaces was exact in float32.  With tq > 1 the per-head einsums are
    matrix products already (one MXU pass, as over a float cache).  So
    they are under a mesh (``mesh_active``), which shards the plane's E
    by head groups: GSPMD cannot see that a block-diagonal contraction
    is local to a shard, and would all-gather the plane.

    With ``num_kv_heads < num_heads`` the caches hold H_kv heads (and
    QuantKV scale planes are per-(token, kv-head)); q-head ``h`` scores
    kv-head ``h // G`` through the grouped einsum — no broadcast copy.

    With ``window`` the mask is built from absolute positions: slot ``j``
    of a ring of C holds the newest position ``p < total_len`` with ``p %
    C == j``, and query ``i`` (at ``total_len - tq + i``) sees the slots
    whose position lies in its last ``window``.  The ring may then have
    wrapped under a multi-row query, as long as it is at least ``window +
    tq - 1`` long.  ``sink`` and ``value_scale`` as in :func:`sdpa`.

    ``block=(first, capacity)`` says the planes are one block of a longer
    view (:func:`_attend_live_blocks`): slot ``j`` of row ``b``'s plane
    is index ``first[b] + j`` of a view of ``capacity``.  The result is
    then the block's share of the softmax, not yet normalized: ``(max (B,
    tq, H), sum (B, tq, H), acc (B, tq, H, hdv))``, all float32, with no
    sink and no value scale: they join where the blocks are combined.

    ``allow`` (broadcast against the logits, (B, H_kv, [G,] tq, C) with 1
    where it does not vary) is a selection laid over the length mask: a
    slot is attended where both say so (sparse selection,
    :func:`paged_attend_sparse`)."""
    import jax.numpy as jnp
    from jax.lax import Precision

    b, tq, e = q.shape
    kvh, g = check_head_groups(num_heads, num_kv_heads, e,
                               where="sdpa_decode")
    # the q-head axes of the einsums: (H,), or (H_kv, G) when grouped;
    # logits are (B, *heads, tq, C)
    heads, hx = ((num_heads,), "h") if g == 1 else ((kvh, g), "hg")
    ones = (1,) * len(heads)

    def stored(cache):
        # -> (the plane the products read, its scales shaped to multiply
        # the logits, or None for a float cache)
        if not isinstance(cache, QuantKV):
            return cache, None
        assert cache.scale.shape[-1] == kvh, \
            "cache quantized with %d heads, caller expects %d" \
            % (cache.scale.shape[-1], kvh)
        return (cache.data.astype(q.dtype),
                jnp.swapaxes(cache.scale, 1, 2).reshape(
                    (b, kvh) + ones + (-1,)))

    with _scope(layer, "kv_dequant"):
        k_cache, k_scale = stored(k_cache)
        v_cache, v_scale = stored(v_cache)
    c = k_cache.shape[1]
    ev = v_cache.shape[2]
    if ev % kvh != 0:
        raise ValueError("sdpa_decode: value cache dim %d not divisible "
                         "by num_kv_heads=%d" % (ev, kvh))
    hd = e // num_heads
    if k_cache.shape[2] != kvh * hd:
        raise ValueError(
            "sdpa_decode: key cache dim %d != num_kv_heads=%d * "
            "head_dim=%d" % (k_cache.shape[2], kvh, hd))
    scale = scale or 1.0 / np.sqrt(hd)
    row = tq == 1 and not mesh_active
    exact = (Precision.HIGHEST, Precision.DEFAULT)

    def own():
        # (j, 1, h, 1): kv-head j is q-head (h, g)'s own
        return jnp.eye(kvh, dtype=bool)[:, None, :, None]

    qh = q.reshape((b, tq) + heads + (hd,))
    kh = k_cache.reshape(b, c, kvh, hd)
    vh = v_cache.reshape(b, c, kvh, ev // kvh)
    with _scope(layer, "scores"):
        if k_scale is not None and row:
            # (B, j, d, h, g): q-head (h, g)'s row in the columns of its
            # own kv-head, zeros in every other
            qbd = jnp.where(own(), jnp.transpose(
                q.reshape(b, kvh, g, hd), (0, 3, 1, 2))[:, None], 0)
            logits = jnp.einsum(
                "ben,bke->bnk", qbd.reshape(b, kvh * hd, num_heads),
                k_cache, precision=exact, preferred_element_type=jnp.float32
            ).reshape((b,) + heads + (1, c)) * scale
        else:
            logits = jnp.einsum(
                "bq%sd,bkhd->b%sqk" % (hx, hx), qh, kh,
                preferred_element_type=None if k_scale is None
                else jnp.float32).astype(jnp.float32) * scale
        if k_scale is not None:
            with _scope(layer, "kv_dequant"):
                logits = logits * k_scale
        total = jnp.asarray(total_len, jnp.int32).reshape((-1, 1, 1) + ones)
        qpos = jnp.arange(tq, dtype=jnp.int32).reshape((1,) + ones + (tq, 1))
        if window or (sink is not None and block is None):
            slot = jnp.arange(c, dtype=jnp.int32).reshape(
                (1, 1) + ones + (c,))
            qabs = total - tq + qpos
            allowed = slot < jnp.minimum(qabs + 1, c)
            if window:
                # the position slot j holds: the newest p < total, p % c == j
                held = slot + c * jnp.floor_divide(total - 1 - slot, c)
                allowed = (held >= 0) & (held <= qabs) \
                    & (held > qabs - int(window))
            p = _softmax_with_sink(
                logits, allowed,
                None if sink is None else jnp.asarray(sink, jnp.float32)
                .reshape((1,) + heads + (1, 1)))
        else:
            limit = jnp.minimum(total - (tq - 1) + qpos,
                                c if block is None else block[1])
            slot = jnp.arange(c, dtype=jnp.int32).reshape(
                (1, 1) + ones + (c,))
            if block is not None:
                slot = slot + jnp.asarray(block[0], jnp.int32).reshape(
                    (-1, 1) + ones + (1,))
            seen = slot < limit if allow is None else (slot < limit) & allow
            logits = jnp.where(seen, logits, jnp.finfo(jnp.float32).min)
            m = jnp.max(logits, axis=-1, keepdims=True)
            p = jnp.exp(logits - m)
            den = jnp.sum(p, axis=-1, keepdims=True)
            if block is None:
                p = p / den
        if v_scale is not None:
            with _scope(layer, "kv_dequant"):
                p = p * v_scale
    if v_scale is not None and row:
        full = jnp.einsum("bnk,bke->bne", p.reshape(b, num_heads, c), v_cache,
                          precision=exact,
                          preferred_element_type=jnp.float32)
        out = jnp.sum(jnp.where(
            own(), full.reshape(b, kvh, g, kvh, ev // kvh), 0), axis=3)
    else:
        out = jnp.einsum(
            "b%sqk,bkhe->bq%se" % (hx, hx), p.astype(vh.dtype), vh,
            preferred_element_type=None if v_scale is None and block is None
            else jnp.float32)
    if block is not None:
        # (B, *heads, tq, 1) -> (B, tq, H); the row form's (B, H_kv, G, hdv)
        # has tq == 1
        m, den = (jnp.swapaxes(x.reshape(b, num_heads, tq), 1, 2)
                  .astype(jnp.float32) for x in (m, den))
        return m, den, out.reshape(b, tq, num_heads, ev // kvh) \
            .astype(jnp.float32)
    if value_scale != 1.0:
        out = out * jnp.asarray(value_scale, out.dtype)
    if v_scale is not None and tq > 1:
        out = out.astype(q.dtype)       # _out_dtype: the stream's
    return out.reshape(b, tq, num_heads * (ev // kvh))


def sdpa_decode(q, k_cache, v_cache, total_len, num_heads=1, scale=None,
                num_kv_heads=0):
    """Attend query position(s) against a ring-buffer KV cache.

    (B, tq, E) queries over (B, C, E)/(B, C, Ev) caches -> (B, tq, Ev).
    ``total_len`` — scalar or (B,) — counts tokens appended to the cache
    INCLUDING the query position(s): query i (the token at global position
    ``total_len - tq + i``) sees cache slots j < min(total_len - tq + 1 + i,
    C); once the ring has wrapped every slot holds a live token and the
    window is all C slots.  Same fp32-softmax numerics as :func:`sdpa`, so
    prefill+decode logits match the full forward pass.  Caches may be
    :class:`QuantKV` (attended as stored, the scales applied to the
    logits and the probabilities: :func:`_sdpa_cache`).  With
    tq > 1 the caller must not have wrapped past its own queries
    (total <= C) — that multi-position form is :func:`sdpa_verify`.
    """
    return _sdpa_cache(q, k_cache, v_cache, total_len, num_heads, scale,
                       num_kv_heads=num_kv_heads)


def sdpa_verify(q, k_cache, v_cache, total_len, num_heads=1, scale=None,
                num_kv_heads=0):
    """Length-masked multi-position cache attention — the speculative
    verify kernel.

    The target model scores all k+1 speculative positions in ONE pass:
    ``q`` is (B, k+1, E) (last committed token + k drafts), the caches
    already hold their K/V (``cache_append`` fixed-width append), and
    ``total_len`` counts through the last draft.  Query i masks to slots
    ``j < min(total_len - k + i, C)`` — itself and everything before it,
    never a later draft — so the k+1 output rows each equal what a
    sequential :func:`sdpa_decode` chain would have produced (the
    acceptance rule compares them against the proposal distribution).
    Requires the verify window not to wrap (``total_len <= C``); the
    decode layer gates speculation off near the ring boundary and falls
    back to single-token steps, keeping every shape static.
    """
    return _sdpa_cache(q, k_cache, v_cache, total_len, num_heads, scale,
                       num_kv_heads=num_kv_heads)


# ---------------------------------------------------------------------------
# Paged mode — the KV pool is one device-resident buffer of fixed-size pages
# per attention node (vLLM's PagedAttention memory plan, Kwon et al. SOSP
# 2023), shared by every serving slot, and each slot carries a PAGE TABLE:
# position p of a slot lives at pool[table[slot, (p // page_tokens) %
# table_width], p % page_tokens].  The table is DATA, not shape — one traced
# decode/verify/chunk program serves every page mapping (admissions, COW
# forks, retirements never retrace).  Because the table indexes ring-mod over
# its width, a slot's pages in table order are laid out exactly like a dense
# ring buffer of capacity table_width * page_tokens, so sdpa_decode /
# sdpa_verify's length masking (including wrap) applies unchanged.  A view of
# one block (LIVE_BLOCK_TOKENS, 256 positions) is gathered whole and attended
# by the ops a dense ring is attended by: bit-parity with a dense ring of the
# same capacity.  A longer view is never built: its blocks are gathered and
# attended a few at a time, only those a slot's length has reached, and
# combined by one log-sum-exp (_attend_live_blocks): the same positions, the
# softmax's sums in another order, parity within float32 tolerance.  Page id 0
# is reserved as a scratch page: unmapped table entries point at it (their
# slots are masked anyway) and writes of inactive rows are redirected into
# it, which is what lets one fixed-shape batched program carry slots that
# are empty or mid-prefill.  The host side (allocator, refcounts,
# copy-on-write prefix sharing) lives in mxnet_tpu/serve/.
# ---------------------------------------------------------------------------

def _plane(pool):
    """The (P, page_tokens, E) array of a pool, quantized or not."""
    return pool.data if isinstance(pool, QuantKV) else pool


def _latest(new, start_pos, capacity):
    """``(new, start (B,), t)`` with ``new`` (B, t, E) cut to the latest
    ``capacity`` tokens, the only ones that can land in a ring of that
    many (same trim as :func:`cache_append`)."""
    import jax.numpy as jnp

    b, t = new.shape[0], new.shape[1]
    start = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32).reshape(-1),
                             (b,))
    if t > capacity:
        new = new[:, -capacity:]
        start = start + (t - capacity)
        t = capacity
    return new, start, t


def _append_scales(plane, table, new, start, active, valid):
    """Write ``new`` (B, t, W) per-token scales into a scale plane (P,
    page_tokens * W), a page a row, at ring positions [start, start + t)
    of each slot's table; ``active`` and ``valid`` as in
    :func:`paged_append`.  A latent plane's pages may be cut into rows of a
    few positions (``pallas_decode.latent_plane_shape``): a page's values
    lie in the same order, and it is read and written as a page all the
    same.

    XLA:TPU scatters whole rows as one native scatter and expands a
    window narrower than the row into a loop of one update a token (12 ms
    for a 256-token chunk into 12 planes, my chip run, PR 42), so a
    token's scales are not scattered: the rows the tokens fall in are
    read, the tokens' scales put in their places by a select on those few
    rows, and the rows written back whole; a row no token lands in is
    written to the scratch page.  A row is touched by one slot (the
    caller owns every page it really writes), so nothing is lost between
    the read and the write."""
    import jax.numpy as jnp

    b, t, w = new.shape
    m = table.shape[1]
    pt = _page_positions(plane, w)
    n = min((t + pt - 2) // pt + 1, m)        # pages t tokens can touch
    first = (start // pt)[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    page = jnp.take_along_axis(table.astype(jnp.int32), first % m, axis=1)
    # position j of a slot's n pages takes token tok[j] where ok[j]
    tok = jnp.arange(n * pt, dtype=jnp.int32)[None, :] \
        - (start % pt)[:, None]
    if n == m:
        # the pages are the whole ring: what runs past its end lands at
        # its start
        tok = tok % (m * pt)
    ok = (tok >= 0) & (tok < t)
    if active is not None:
        ok &= jnp.asarray(active).reshape(-1, 1).astype(bool)
    if valid is not None:
        ok &= tok < jnp.asarray(valid, jnp.int32).reshape(-1, 1)
    page = jnp.where(jnp.any(ok.reshape(b, n, pt), axis=2), page, 0)
    got = jnp.take_along_axis(new.astype(plane.dtype),
                              jnp.clip(tok, 0, t - 1)[:, :, None], axis=1)
    rows = jnp.where(ok[:, :, None], got,       # (B, n * pt, W)
                     plane[page].reshape(got.shape))
    return plane.at[page.reshape(-1)].set(
        rows.reshape((-1,) + plane.shape[1:]))


def scale_group(num_kv_heads):
    """Floats a token's scales take of its page's row in a pool's scale
    plane: K's ``H_kv`` then V's, and zeros up to the next width that divides
    a lane tile where ``2 * H_kv`` does not (30 heads: 60 -> 64, 16 B a token
    a layer), so that a page's row is whole lane tiles and a token's stretch
    never straddles one (``pallas_decode.tiles``).  A count whose double
    divides 128 keeps its ``2 * H_kv``."""
    w = 2 * int(num_kv_heads)
    return w if w >= 128 or 128 % w == 0 else 1 << (w - 1).bit_length()


def _page_positions(plane, width):
    """Positions a page of a plane of ``width`` values a position holds,
    however the page's values are cut into rows."""
    return int(np.prod(plane.shape[1:])) // width


def paged_gather(pool, table):
    """Gather a per-slot dense-ring view out of the shared page pool.

    ``pool`` is (P, page_tokens, E); ``table`` is (B, M) int32 page ids.
    Returns the (B, M*page_tokens, E) view whose index ``v`` holds the
    slot's position ``p`` with ``v == p % (M*page_tokens)`` — the dense
    ring layout, so the cached attention kernels mask it exactly like a
    ring buffer.  Unmapped table entries (id 0, the scratch page) gather
    garbage into slots the length mask already hides.  ``table`` may be
    any rows of page ids: :func:`_attend_live_blocks` hands it one block's
    pages a row, and gets the blocks' views.  A node's pools, quantized or
    not, are gathered as a pair: :func:`paged_gather_kv`."""
    b, m = table.shape
    pages = pool[table]                       # (B, M, page_tokens, E)
    return pages.reshape(b, m * pool.shape[1], pool.shape[2])


def paged_gather_kv(k_pool, v_pool, table, num_kv_heads=0):
    """:func:`paged_gather` of a node's K and V pools: ``(k_view,
    v_view)``, each what a dense ring of the table's capacity holds.

    Quantized pools (:class:`QuantKV`) keep both pools' scales in one
    plane, ``k_pool.scale`` (P, page_tokens * 2 * H), a page a row: whole
    rows are taken by ONE gather for the two, and only what was gathered
    is reshaped and split into the (B, M*page_tokens, H) planes of two
    dense rings.  Where a token's stretch is padded (:func:`scale_group`),
    ``num_kv_heads`` says how many of its floats are K's and V's.  The
    transposition that puts positions on the lanes for the products
    (:func:`_sdpa_cache`) is paid on the view, a few hundred kilobytes,
    and the pool is read as it lies."""
    import jax.numpy as jnp

    if not isinstance(k_pool, QuantKV):
        return paged_gather(k_pool, table), paged_gather(v_pool, table)
    k_view = paged_gather(k_pool.data, table)
    rows = k_pool.scale[table]                # (B, M, page_tokens * 2 * H)
    # positions onto the lanes once, for both pools; each half goes back
    # to the (B, C, H) a dense ring's plane has, which ``_sdpa_cache``
    # turns again: XLA drops the pair, and the products read the halves.
    # (Split first, (B, C, 2, H), and turned a pool: tiles of 2 x 128 with
    # H lanes filled, 2.0 ms of an OPT tick more; my chip runs, PR 42)
    both = jnp.swapaxes(rows.reshape(k_view.shape[:2] + (-1,)), 1, 2)
    h = int(num_kv_heads) or both.shape[1] // 2
    return (QuantKV(k_view, jnp.swapaxes(both[:, :h], 1, 2)),
            QuantKV(paged_gather(v_pool.data, table),
                    jnp.swapaxes(both[:, h:2 * h], 1, 2)))


def paged_append(pool, table, new, start_pos, active=None, valid=None,
                 layer="attn"):
    """Scatter ``new`` (B, t, E) into the page pool (P, page_tokens, E)
    at ring positions [start_pos, start_pos + t) of each slot's page
    table.

    ``start_pos`` — scalar or (B,) tokens already appended per slot.
    ``active`` — optional (B,) 0/1 mask: rows with 0 (empty or mid-prefill
    slots riding a fixed-shape batched step) redirect their writes to the
    scratch page instead of touching real pages.  ``valid`` — optional (B,)
    count of REAL rows within ``new``'s width (a padded final prefill
    chunk): positions >= valid are redirected too, so pad garbage is never
    written at all.  The caller (serve.PagedKVManager) guarantees
    every really-written page is exclusively owned — copy-on-write forks
    shared pages BEFORE the step — so scatter indices never collide except
    on the scratch page, whose contents are never read unmasked.  A
    node's pools, quantized or not, are appended as a pair:
    :func:`paged_append_kv`.
    """
    import jax.numpy as jnp

    with _scope(layer, "kv_append"):
        new, start, t = _latest(new, start_pos, table.shape[1]
                                * pool.shape[1])
        b = new.shape[0]
        m = table.shape[1]
        pt = pool.shape[1]
        new = new.astype(pool.dtype)
        # (B, t)
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        page = jnp.take_along_axis(table.astype(jnp.int32), (pos // pt) % m,
                                   axis=1)
        write = jnp.ones((b, t), bool)
        if active is not None:
            write &= jnp.asarray(active).reshape(-1, 1).astype(bool)
        if valid is not None:
            write &= jnp.arange(t, dtype=jnp.int32)[None, :] \
                < jnp.asarray(valid, jnp.int32).reshape(-1, 1)
        page = jnp.where(write, page, 0)          # masked writes -> scratch
        slot = pos % pt
        return pool.at[page.reshape(-1), slot.reshape(-1)].set(
            new.reshape(b * t, -1))


def paged_append_kv(k_pool, v_pool, table, k, v, start_pos, num_heads=1,
                    active=None, valid=None, layer="attn"):
    """:func:`paged_append` of a node's new ``k`` and ``v`` (B, t, E_k /
    E_v) into its two pools: ``(k_pool, v_pool)``.

    :class:`QuantKV` pools quantize on the way in (per (token, head):
    pass ``num_heads``, the K/V head count), the data planes at the same
    slots; a token's scales, K's H then V's H, are one stretch of its
    page's row in the one scale plane the pair shares
    (:func:`_append_scales`).  No op here or in :func:`paged_gather_kv`
    reshapes, transposes or slices a POOL: that is what would let layout
    assignment carry a consumer's preferred layout back through a gather
    and convert all of it."""
    import jax.numpy as jnp

    if not isinstance(k_pool, QuantKV):
        return (paged_append(k_pool, table, k, start_pos, active=active,
                             valid=valid, layer=layer),
                paged_append(v_pool, table, v, start_pos, active=active,
                             valid=valid, layer=layer))
    with _scope(layer, "kv_append"):
        qk = quantize_kv(k, k_pool.data.dtype, num_heads)
        qv = quantize_kv(v, v_pool.data.dtype, num_heads)
        k_data, v_data = (
            paged_append(pool.data, table, q.data, start_pos, active=active,
                         valid=valid, layer=layer)
            for pool, q in ((k_pool, qk), (v_pool, qv)))
        pt = k_pool.data.shape[1]
        scales = jnp.concatenate([qk.scale, qv.scale], axis=-1)
        spare = k_pool.scale.shape[1] // pt - scales.shape[-1]
        if spare:       # a padded stretch: scale_group
            scales = jnp.pad(scales, ((0, 0), (0, 0), (0, spare)))
        scales, start, _ = _latest(scales, start_pos, table.shape[1] * pt)
        return (QuantKV(k_data, _append_scales(
            k_pool.scale, table, scales, start, active, valid)),
            QuantKV(v_data, None))


def quantize_pools(k, v, dtype, num_heads=1):
    """(P, page_tokens, E_k / E_v) float pages of a node's K and V -> its
    quantized pools as the paged ops store them: ``(QuantKV(k data, the
    scales of both), QuantKV(v data, None))``."""
    import jax.numpy as jnp

    qk = quantize_kv(k, dtype, num_heads)
    qv = quantize_kv(v, dtype, num_heads)
    scales = jnp.concatenate([qk.scale, qv.scale], axis=-1)
    spare = scale_group(num_heads) - scales.shape[-1]
    if spare:
        scales = jnp.pad(scales, ((0, 0), (0, 0), (0, spare)))
    return (QuantKV(qk.data, scales.reshape(k.shape[0], -1)),
            QuantKV(qv.data, None))


def paged_copy(pool, src, dst):
    """Copy page ``src`` -> page ``dst`` (traced scalar ids) in one pool —
    the device half of a copy-on-write fork: the host allocator picks
    ``dst``, this kernel duplicates the shared page, and the forking slot's
    next append diverges in its own copy.  :class:`QuantKV` pools copy
    every plane they hold."""
    import jax.tree_util as jtu

    return jtu.tree_map(lambda plane: plane.at[dst].set(plane[src]), pool)


# Which path the last dot_product_attention dispatch traced: "flash",
# "einsum" or "ring".  Written at trace time (dispatch happens under jit
# tracing), so tests can assert the kernel path actually ran instead of
# silently regressing to 100%-einsum (round-3 verdict, Weak #2).  The
# counter mx_attn_dispatch_total{path=...} counts every such dispatch.
PATH_TAKEN = {"last": None}

# The smallest sequence length at which the flash kernel is taken, by head
# width: the smallest measured T at which it ran forward + backward
# >= 1.05x the einsum path (TPU v5 lite, jax 0.9.0, bf16, causal, 8192
# tokens a step; benchmarks/bench_flash_attention.py --crossover, PR 26):
#   32 heads of 64:  T 256 0.76x, 512 1.59x, 1024 2.53x, 2048 3.13x
#   16 heads of 128: T 256 0.81x, 512 1.61x, 1024 2.56x, 2048 3.19x
# Widths between and above take the row of the nearest measured width
# below them.
FLASH_MIN_T = {64: 512, 128: 512}


def _note_path(path, taken=None):
    (PATH_TAKEN if taken is None else taken)["last"] = path
    from .. import obs as _obs

    _obs.registry.counter(
        "mx_attn_dispatch_total",
        "dot_product_attention nodes traced, by the path they took",
        labels=("path",)).labels(path=path).inc()


def _note_body(tiles):
    """Leave a "decode-kernel" dispatch's ``Tiles`` in :data:`DECODE_PATH`
    and count its body."""
    DECODE_PATH["tiles"] = tiles
    from .. import obs as _obs

    _obs.registry.counter(
        "mx_attn_decode_body_total",
        "decode-kernel dispatches traced, by the body the kernel took",
        labels=("body",)).labels(body=tiles.body).inc()


def flash_selected(q_shape, k_shape, causal, num_heads, num_kv_heads,
                   mesh_active, plain=True):
    """``(take, interpret)``: whether ``dot_product_attention`` runs the
    Pallas flash kernel for this call, decided from what the call shows.

    All must hold: the backend is a TPU (or ``MXNET_PALLAS_INTERPRET``
    forces the interpreter); no mesh shards the executor (the kernel is
    opaque to GSPMD); ``pallas_attention.supported`` admits the shape;
    and T has reached :data:`FLASH_MIN_T` for the head width.  Anything
    else takes :func:`sdpa`: so does a node that is not ``plain`` (a
    window, a sink, or values of another head width than the keys: the
    kernels know the causal mask only)."""
    from . import pallas_attention as _pa

    runs, interpret = _kernel_backend()
    if mesh_active or not runs or not plain \
            or not _pa.supported(q_shape, k_shape, causal, num_heads,
                                 num_kv_heads=num_kv_heads):
        return False, False
    head_dim = q_shape[2] // num_heads
    min_t = FLASH_MIN_T[max(w for w in FLASH_MIN_T if w <= head_dim)]
    return q_shape[1] >= min_t, interpret


# Same marker for the DECODE-side dispatch (paged_attend / cache_attend):
# "decode-kernel" when the decode row's Pallas kernel traced
# (decode_kernel_selected), "chunk-kernel" when a prefill chunk's did
# (chunk_kernel_selected; paged_attend_sparse's chunk branch too), "walk" for
# the loop over the live blocks (_attend_live_blocks) and "whole" where the
# view is gathered and attended whole (a window ring, a view of one block, a
# sharded pool, a dense ring).
# Each such dispatch also counts in mx_attn_dispatch_total{path=...}.
# mxnet_tpu.decode records it per program, so that an artifact's meta says
# which path its attention took.  "tiles" is the last "decode-kernel"
# dispatch's ``pallas_decode.Tiles``: its ``body`` says whether the kernel
# takes its products a group of KV heads ("grouped", where H > H_kv) or one
# product over them all ("whole"); mx_attn_decode_body_total{body=...}
# counts it.
DECODE_PATH = {"last": None, "tiles": None}


def _kernel_backend():
    """``(runs, interpret)``: whether this backend can run a Pallas
    attention kernel (a TPU natively, anything else only under
    ``MXNET_PALLAS_INTERPRET``) and whether through the interpreter."""
    import jax

    from .. import config as _config

    on_tpu = jax.default_backend() == "tpu"
    interpret = bool(_config.get("MXNET_PALLAS_INTERPRET")) and not on_tpu
    return on_tpu or interpret, interpret


def _extras(window, sink, value_scale, layer):
    """The keywords of a node that is not plain, for :func:`_sdpa_cache`:
    empty for a plain node, so that it is called as it was."""
    if not window and sink is None and value_scale == 1.0 \
            and layer == "attn":
        return {}
    return dict(window=window, sink=sink, value_scale=value_scale,
                layer=layer)


# The einsum path of :func:`paged_attend` attends a view block by block, and
# only the blocks a slot's length has reached (:func:`_attend_live_blocks`).
# LIVE_BLOCK_TOKENS is a block's width by the view's capacity (the widest
# capacity listed that the view reaches).  Measured kernel alone on the chip
# (TPU v5 lite, jax 0.9.0, int8 pools, the two serving cells' shapes: 32
# slots x 2048 of 32 heads of 64, and 64 x 9216 of 64 heads of 192/128 over
# 4 KV heads; benchmarks/bench_decode.py --live-blocks --sweep, PR 36; ms a
# layer, whole view first):
#   decode row, 30 % live:   1.59 -> block 256 0.61, 512 0.61, 1024 0.63
#   decode row, all live:    1.59 -> block 256 1.42, 512 1.22, 1024 1.15
#   64 x 9216, half / all:   7.78 -> block 256 4.09 / 7.41, 512 3.56 / 6.37,
#                                    1024 3.25 / 5.63
#   chunk of 512, the same:  5.57 -> block 256 1.20 / 2.31, 512 1.16 / 2.23
#                            (1024: 0.66 ms a block where 512 takes 0.13)
# and, where kernel alone two widths tie, in the cell whose lengths are its
# traffic's (prompts log-uniform 128-1024 in a view of 2048: most slots
# short), tokens/s over three seeds a width: block 256 1172-1175, 512
# 1129-1147; at 64 x 9216, 512 1240, 1024 1206.
# A step of the walk does best at 4096-8192 positions (a decode row; at
# 16384 the last step's idle blocks cost more than the steps saved), a chunk
# of 512 at one block of 512 a step (two: 3.11 / 5.53, the scores no longer
# fit where the product leaves them); and a table in fewer than 16 steps
# leaves the last one mostly idle (32 x 2048 at 30 %: 0.67 in steps of 16
# blocks of 512, 0.61 in steps of 8).
LIVE_BLOCK_TOKENS = {0: 256, 8192: 512}
LIVE_STEP_TOKENS = 8192
LIVE_STEP_SCORES = 512 * 512


def live_block_plan(q_shape, table_shape, page_tokens, mesh_active=False,
                    window=0):
    """``(block, group)`` for :func:`_attend_live_blocks` — positions a
    block and blocks a step — from what the call shows, or None where the
    view is to be gathered whole: it is one block at most, a block is
    not whole pages, the node has a ``window`` (its ring is full once
    reached, and its mask is built from absolute positions), or a mesh
    shards the pools (GSPMD and a gather loop whose trip count is data
    have not met)."""
    b, tq = q_shape[0], q_shape[1]
    cap = table_shape[1] * page_tokens
    block = LIVE_BLOCK_TOKENS[max(c for c in LIVE_BLOCK_TOKENS if c <= cap)]
    if mesh_active or window or cap <= block or block % page_tokens:
        return None
    blocks = b * -(-cap // block)
    return block, max(1, min(LIVE_STEP_TOKENS // block,
                             LIVE_STEP_SCORES // (block * tq), blocks // 16))


def decode_kernel_selected(q_shape, k_pool, v_pool, table_shape, num_heads,
                           num_kv_heads, mesh_active=False, window=0):
    """``(take, interpret)``: whether :func:`paged_attend` hands the live
    blocks of this call to the decode row's Pallas kernel
    (:mod:`~mxnet_tpu.ops.pallas_decode`), decided from what the call
    shows, as :func:`flash_selected` decides for training.

    All must hold: one query row a slot (more rows are matrix products
    already, and take the walk); a :func:`live_block_plan` (so no window
    node, no mesh, a view of more than a block); a backend that runs
    Pallas (:func:`_kernel_backend`); and shapes the kernel tiles
    (``pallas_decode.tiles``: whole lane tiles of keys, values and scale
    rows, a step's buffers within fast memory).  ``take`` is the call's
    ``Tiles`` where it is taken, None where it is not."""
    from . import pallas_decode as _pd

    runs, interpret = _kernel_backend()
    plan = live_block_plan(q_shape, table_shape, _plane(k_pool).shape[1],
                           mesh_active=mesh_active, window=window)
    if q_shape[1] != 1 or plan is None or not runs:
        return None, False
    return _pd.tiles(q_shape, k_pool, v_pool, num_heads, num_kv_heads,
                     plan[0]), interpret


# Query rows of a one-slot call from which its live blocks go to the chunk's
# Pallas kernel (``pallas_decode.attend_chunk_blocks``) in the walk's place:
# the fewest at which the kernel was ahead of the walk at every shape and
# context measured.  Kernel alone on the chip (TPU v5 lite, jax 0.9.0, int8
# pools, float32 queries, heads of 128; benchmarks/probe_chunk_kernel.py,
# PR 54; ms a layer, walk -> kernel, by rows x context; a call of 0.2 ms is
# the dispatch's own length on either path):
#   32 heads over 2 KV heads, 64 of n blocks chosen a row (minicpm-sala):
#     2048 x 16k / 32k / 64k  18.83 -> 4.33, 37.46 -> 8.46, 74.72 -> 16.75
#     (nothing chosen: 18.58 -> 4.22, 37.00 -> 8.27, 73.84 -> 16.39)
#     1024 x 1k / 4k 0.33 -> 0.31, 0.92 -> 0.70;  512 x 1k / 4k 0.25 -> 0.23,
#     0.46 -> 0.42;  256 x 1k / 4k 0.21 -> 0.22, 0.28 -> 0.32
#   64 heads over 8 (solar-open2): 2048 x 8k / 16k 22.63 -> 4.91, 44.75 ->
#     9.41;  1024 x 1k / 4k 1.36 -> 0.53, 5.02 -> 1.37;  512 x 1k / 4k 0.29
#     -> 0.32, 0.88 -> 0.75;  256 x 1k / 4k 0.21 -> 0.24, 0.50 -> 0.40
#   64 heads over 8, blocks of 256 (k-exaone): 512 x 2k / 6k 0.63 -> 0.68,
#     1.71 -> 1.72
#   20 heads over 4 (falcon-h1): 1024 x 1k / 2k 0.24 -> 0.22, 0.40 -> 0.31;
#     512 x 1k / 2k 0.22 -> 0.24, 0.28 -> 0.25;  256 x 1k 0.22 -> 0.20-0.23
# At 512 rows the two trade places by the context (0.88-1.17 x): a step of
# the walk still holds its scores where the products leave them
# (LIVE_STEP_SCORES); from 1024 rows on it writes them out and the kernel is
# 1.05-4.7 x ahead.  So the chunks of 2048 take the kernel and those of 256
# and 512 (falcon-h1, k-exaone) keep the walk.
CHUNK_MIN_ROWS = 1024


def chunk_kernel_selected(q_shape, k_pool, v_pool, table_shape, num_heads,
                          num_kv_heads, mesh_active=False, window=0,
                          chosen=None):
    """``(take, interpret)``: whether a call of one slot and many query rows
    (a prefill chunk) hands its live blocks to the chunk's Pallas kernel
    (``pallas_decode.attend_chunk_blocks``) in the walk's place, decided
    from what the call shows, as :func:`decode_kernel_selected` decides for
    the decode row.

    All must hold: one slot with at least :data:`CHUNK_MIN_ROWS` query rows;
    a :func:`live_block_plan` (so no window node, no mesh, a view of more
    than a block); a backend that runs Pallas; and shapes the kernel tiles
    (``pallas_decode.chunk_tiles``: heads of whole lane tiles, a tile of the
    rows with every head's running state within fast memory).  ``chosen`` =
    ``(mask shape, width)`` of a selection laid over the walk
    (:func:`paged_attend_sparse`).  A call that brings its own ``gather``
    (latent attention's expanded chunk) does not ask: it has a rule and a
    kernel of its own (:func:`latent_chunk_kernel_selected`).  ``take`` is
    the call's ``ChunkTiles`` where it is taken, None where it is not."""
    from . import pallas_decode as _pd

    runs, interpret = _kernel_backend()
    plan = live_block_plan(q_shape, table_shape, _plane(k_pool).shape[1],
                           mesh_active=mesh_active, window=window)
    if q_shape[0] != 1 or q_shape[1] < CHUNK_MIN_ROWS or plan is None \
            or not runs:
        return None, False
    return _pd.chunk_tiles(q_shape, k_pool, v_pool, num_heads, num_kv_heads,
                           plan[0], chosen=chosen), interpret


def _attend_live_blocks(q, k_pool, v_pool, table, total_len, num_heads,
                        scale, num_kv_heads, block, group, sink=None,
                        value_scale=1.0, layer="attn", chosen=None,
                        kernel=None, gather=None, hdv=None,
                        page_tokens=None, chunk=None):
    """:func:`paged_gather` + :func:`_sdpa_cache` over the blocks the slots
    have reached, and no others.

    A slot's view is cut into blocks of ``block`` positions; block ``j`` of
    slot ``b`` is live when ``total_len[b] > j * block`` (every block, once
    the ring has wrapped; the first always, so that an empty slot has a
    finite answer).  The live blocks of all slots make one flat list, built
    on the device from ``total_len``.  A loop whose trip count is data,
    ``ceil(n_live / group)``, takes ``group`` of them a step: it gathers
    their pages as they are stored, takes the two products as
    :func:`_sdpa_cache` takes them over a whole view, and keeps each
    block's maximum, sum and accumulated values.  One log-sum-exp over a
    slot's blocks then combines them; the sink joins there.  Every live
    position is attended and nothing is approximated: against the whole
    view only the order of the softmax's sums differs.  The pools are
    constants of the loop, never carried.

    ``chosen`` = ``(mask (B, H_kv, tq, n), width)`` lays a selection over
    the walk: query row ``i`` of kv group ``g`` attends position ``p`` only
    where ``mask[b, g, i, p // width]`` (``width`` divides ``block``).  The
    walk still visits every live block; what a row did not choose is masked
    out of its softmax.

    ``kernel`` = ``(tiles, interpret)`` (:func:`decode_kernel_selected`, or
    :func:`latent_kernel_selected` over a latent plane: one query row,
    nothing ``chosen``) puts ONE Pallas kernel over the list in the loop's
    place (``tiles.attend``: ``pallas_decode.attend_blocks``, or
    ``attend_latent_blocks`` with ``block`` its own): a step a live row of
    the list, the block's pages copied from the pools into fast memory by
    the kernel itself, no gathered view written, dead rows neither visited
    nor read.  The list before it and the combine after it are the loop's
    own.

    ``chunk`` = ``(tiles, interpret)`` (:func:`chunk_kernel_selected`: ONE
    slot, many query rows, no ``gather``) puts the chunk's Pallas kernel
    over the slot's blocks in the loop's place
    (``pallas_decode.attend_chunk_blocks``): what it returns is the loop's
    running row, a selection ``chosen`` goes in with it, and the combine
    after it is the loop's own.

    ``gather(ids (rows, pages a block))`` -> ``(k_blk, v_blk)`` stands in
    for :func:`paged_gather_kv` where a node's pages are not keys and values
    as the products read them (a latent plane, :func:`latent_attend`): what
    it returns is attended as a block's keys and values are, ``hdv`` wide a
    head, and ``page_tokens`` says how many positions a page of such a plane
    holds."""
    import jax
    import jax.numpy as jnp

    b, tq, _ = q.shape
    pt = page_tokens or _plane(k_pool).shape[1]
    ppb = block // pt
    cap = table.shape[1] * pt
    nb = -(-table.shape[1] // ppb)
    rows = -(-b * nb // group) * group            # the list, padded
    total = jnp.broadcast_to(
        jnp.asarray(total_len, jnp.int32).reshape(-1), (b,))
    with _scope(layer, "kv_gather"):
        # a last block that is not whole reads the scratch page past the
        # table's end: those positions lie at or above the capacity
        pages = jnp.pad(table.astype(jnp.int32),
                        ((0, 0), (0, nb * ppb - table.shape[1])))
        reached = jnp.where(total >= cap, nb,
                            jnp.clip(-(-total // block), 1, nb))
        ends = jnp.cumsum(reached)
        flat = jnp.arange(rows, dtype=jnp.int32)
        slot = jnp.minimum(
            jnp.sum(flat[:, None] >= ends[None, :], axis=1), b - 1)
        first = ends - reached
        blk = jnp.clip(flat - first[slot], 0, nb - 1)
        pages = pages.reshape(b, nb, ppb)[slot, blk]          # (rows, ppb)
        steps = -(-ends[-1] // group)
    hdv = hdv or _plane(v_pool).shape[2] // (int(num_kv_heads) or num_heads)
    out_dtype = _out_dtype(q, v_pool)
    if chunk is not None:
        from . import pallas_decode as _pd

        with _scope(layer, "scores"):
            m, den, acc = _pd.attend_chunk_blocks(
                q, k_pool, v_pool, pages[:nb], total[0], cap, chunk[0],
                scale or 1.0 / np.sqrt(q.shape[2] // num_heads),
                chosen=chosen, interpret=chunk[1])
        return _combine_blocks(m[:, None], den[:, None], acc[:, None], sink,
                               value_scale, out_dtype, layer)
    if kernel is not None:
        with _scope(layer, "scores"):
            j = jnp.arange(nb, dtype=jnp.int32)[None, :]
            parts = kernel[0].attend(
                q, k_pool, v_pool, pages, slot,
                jnp.clip(jnp.minimum(total, cap)[slot] - blk * block, 0,
                         block),
                ends[-1], scale or 1.0 / np.sqrt(q.shape[2] // num_heads),
                interpret=kernel[1])
            # a slot's dead blocks read its first and weigh nothing
            where = jnp.where(j < reached[:, None], first[:, None] + j,
                              first[:, None])
            live = (j < reached[:, None])[:, :, None, None]
            m, den, acc = (x[where][:, :, None] for x in parts)
            m = jnp.where(live, m, jnp.finfo(jnp.float32).min)
            den = jnp.where(live, den, 0.0)
            acc = jnp.where(live[..., None], acc, 0.0)
        return _combine_blocks(m, den, acc, sink, value_scale, out_dtype,
                               layer)
    # B slots' blocks are kept apart until the loop has ended, one row a
    # block (row ``rows`` is never written: a slot's dead blocks read it).
    # One slot's blocks fold into one running row as the loop goes: a
    # prefill chunk's rows would be nb x tq x H x hdv floats to write,
    # read back and sum, most of them for blocks never reached
    running = b == 1
    fill = jnp.finfo(jnp.float32).min
    kept = 1 if running else rows + 1
    parts = (jnp.full((kept, tq, num_heads), fill, jnp.float32),
             jnp.zeros((kept, tq, num_heads), jnp.float32),
             jnp.zeros((kept, tq, num_heads, hdv), jnp.float32))

    def fold(parts, got, at):
        (m0, den0, acc0), (m, den, acc) = parts, got
        live = at + jnp.arange(group, dtype=jnp.int32) < ends[-1]
        m = jnp.where(live[:, None, None], m, fill)
        top = jnp.maximum(m0, jnp.max(m, axis=0, keepdims=True))
        w0, w = jnp.exp(m0 - top), jnp.exp(m - top)
        return (top, w0 * den0 + jnp.sum(w * den, axis=0, keepdims=True),
                w0[..., None] * acc0
                + jnp.sum(w[..., None] * acc, axis=0, keepdims=True))

    def step(carry):
        i, parts = carry
        at = i * group
        take = lambda x: jax.lax.dynamic_slice_in_dim(x, at, group)
        rows_of, ids = take(slot), take(pages)
        with _scope(layer, "kv_gather"):
            k_blk, v_blk = paged_gather_kv(
                k_pool, v_pool, ids, int(num_kv_heads) or num_heads) \
                if gather is None else gather(ids)
        allow = {}
        if chosen is not None:
            mask, width = chosen
            per = block // width
            at_blk = take(blk)[:, None] * per \
                + jnp.arange(per, dtype=jnp.int32)[None, :]    # (group, per)
            got_mask = mask[rows_of[:, None], :, :,
                            jnp.minimum(at_blk, mask.shape[3] - 1)]
            # (group, per, H_kv, tq) -> (group, H_kv, [1,] tq, block)
            got_mask = jnp.repeat(jnp.transpose(got_mask, (0, 2, 3, 1)),
                                  width, axis=3)
            allow = {"allow": got_mask if num_heads == mask.shape[1]
                     else got_mask[:, :, None]}
        got = _sdpa_cache(
            jnp.broadcast_to(q, (group,) + q.shape[1:]) if running
            else q[rows_of], k_blk, v_blk, total[rows_of], num_heads, scale,
            num_kv_heads=num_kv_heads, layer=layer,
            block=(take(blk) * block, cap), **allow)
        with _scope(layer, "scores"):
            if running:
                return i + 1, fold(parts, got, at)
            return i + 1, tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, x, at, 0)
                for buf, x in zip(parts, got))

    _, parts = jax.lax.while_loop(lambda carry: carry[0] < steps, step,
                                  (jnp.int32(0), parts))
    with _scope(layer, "scores"):
        if running:
            m, den, acc = (buf[:, None] for buf in parts)
        else:
            j = jnp.arange(nb, dtype=jnp.int32)[None, :]
            where = jnp.where(j < reached[:, None], first[:, None] + j, rows)
            m, den, acc = (buf[where] for buf in parts)  # (B, nb, tq, H[, e])
    return _combine_blocks(m, den, acc, sink, value_scale, out_dtype, layer)


def _out_dtype(q, v_pool):
    """The type a cached attention's output leaves in: a float pool's own;
    over a quantized pool, which has no float type to return to, that of
    the queries (the stream's) where a slot brings more than one row, and
    the float32 of the sums where it brings one.  The running maximum, sum
    and accumulator inside are float32 either way.

    Rows of many positions (a prefill chunk, a verify window, a drafting
    tick's pair) are where a float32 stream costs: every product behind the
    attention writes and reads float32 activations (6.2 ms of a 127-ms
    chunk in ``sala_serve_longctx``, 5.7 of 84.4 in ``solar2_serve_agent``).
    A decode row's products stream weights past 24-96 rows, which weigh
    nothing, and with a bfloat16 stream ``opt_serve_backlog``'s tick read
    8.48 ms where the float32 one reads 7.91 (my chip runs, PR 56: the same
    attention, ``linear`` 1.9 -> 2.5 ms a tick; PERF.md section 7 asks
    why): the row keeps the type it had."""
    import jax.numpy as jnp

    if not isinstance(v_pool, QuantKV):
        return _plane(v_pool).dtype
    return q.dtype if q.shape[1] > 1 else jnp.float32


def _combine_blocks(m, den, acc, sink, value_scale, out_dtype, layer):
    """One softmax a slot from its blocks' shares ``m``, ``den`` (B, nb, tq,
    H) and ``acc`` (B, nb, tq, H, hdv), all float32; the sink and the value
    scale join here, and the result is cast once, after both, to
    ``out_dtype`` (:func:`_out_dtype`).  -> (B, tq, H * hdv)."""
    import jax.numpy as jnp

    b, _, tq, num_heads, hdv = acc.shape
    with _scope(layer, "scores"):
        top = jnp.max(m, axis=1)
        if sink is not None:
            sink = jnp.asarray(sink, jnp.float32).reshape(1, 1, num_heads)
            top = jnp.maximum(top, sink)
        w = jnp.exp(m - top[:, None])
        den = jnp.sum(w * den, axis=1)
        if sink is not None:
            den = den + jnp.exp(sink - top)
        out = jnp.sum(w[..., None] * acc, axis=1) / den[..., None]
    if value_scale != 1.0:
        out = out * jnp.asarray(value_scale, out.dtype)
    return out.astype(out_dtype).reshape(b, tq, num_heads * hdv)


def paged_attend(q, k_pool, v_pool, table, total_len, num_heads=1,
                 scale=None, mesh_active=False, num_kv_heads=0, window=0,
                 sink=None, value_scale=1.0, layer="attn"):
    """Decode/verify attention over shared page pools — the ONE entry the
    decode programs call.  Which of four paths a call takes follows from
    what the call shows; each counts in ``mx_attn_dispatch_total{path}``.

    ``walk``: the view is attended block by block, only the blocks the
    slots have reached (:func:`_attend_live_blocks`, by
    :func:`live_block_plan`): one program, ``total_len`` as data, no view
    of the whole table; a loop gathers a few blocks' pages and takes the
    products of :func:`sdpa_decode`/:func:`sdpa_verify` over them in the
    pool's storage dtype (an int8/fp8 view is never dequantized into a
    float copy).

    ``decode-kernel``: one query row a slot over shapes the kernel tiles
    (:func:`decode_kernel_selected`) hands the same list of blocks to ONE
    Pallas kernel that copies each live block's pages from the pools into
    fast memory itself and takes both products there
    (:mod:`~mxnet_tpu.ops.pallas_decode`): every live byte is read once
    and no gathered view is written.  The arithmetic is the walk's in
    another order (docs/inference.md).

    ``chunk-kernel``: ONE slot with many query rows (a prefill chunk) over
    shapes the chunk's kernel tiles (:func:`chunk_kernel_selected`: at
    least :data:`CHUNK_MIN_ROWS` rows, heads of whole lane tiles) hands the
    slot's blocks to ONE Pallas kernel that takes a tile of the rows
    against every block under the tile's causal limit, the pages copied as
    the decode row's kernel copies them, and keeps the logits, the mask,
    the exponentials and the probabilities in fast memory: the walk's
    ``tq > 1`` arithmetic (one bfloat16 pass a product, float32 sums) with
    only the order of the softmax's sums changed.  What stays on the walk:
    a backend without Pallas, a few rows of many slots (a verify window, a
    self-drafting tick), heads of 64 or keys of 192, and latent
    attention's expanded chunk (its own ``gather``).

    ``whole``: where the plan is None — a view of one block, a window
    node, a sharded pool — the whole view is gathered
    (:func:`paged_gather`) and attended as a dense ring is, the same jaxpr
    as ever and bit-parity with a dense ring; the other two agree with
    that within the tolerance of reordered float32 sums (the chunk's
    kernel within that of probabilities rounded to bfloat16 about another
    maximum, as the walk rounds them about a block's)."""
    plan = live_block_plan(q.shape, table.shape, _plane(k_pool).shape[1],
                           mesh_active=mesh_active, window=window)
    if plan is not None:
        shown = (q.shape, k_pool, v_pool, table.shape, num_heads,
                 num_kv_heads)
        path, kernel, chunk = "walk", None, None
        tiles, interpret = decode_kernel_selected(
            *shown, mesh_active=mesh_active, window=window)
        if tiles is not None:
            path, kernel = "decode-kernel", (tiles, interpret)
            _note_body(tiles)
        else:
            tiles, interpret = chunk_kernel_selected(
                *shown, mesh_active=mesh_active, window=window)
            if tiles is not None:
                path, chunk = "chunk-kernel", (tiles, interpret)
        _note_path(path, DECODE_PATH)
        return _attend_live_blocks(
            q, k_pool, v_pool, table, total_len, num_heads, scale,
            num_kv_heads, *plan, sink=sink, value_scale=value_scale,
            layer=layer, kernel=kernel, chunk=chunk)
    _note_path("whole", DECODE_PATH)
    with _scope(layer, "kv_gather"):
        k_view, v_view = paged_gather_kv(
            k_pool, v_pool, table, int(num_kv_heads) or num_heads)
    return _sdpa_cache(q, k_view, v_view, total_len, num_heads, scale,
                       num_kv_heads=num_kv_heads, mesh_active=mesh_active,
                       **_extras(window, sink, value_scale, layer))


# ---------------------------------------------------------------------------
# Sparse selection (InfLLM-v2, the ``minicpm4`` layers of ``minicpm_sala``):
# a node with ``sparse_topk`` attends, past ``sparse_dense_len`` positions of
# context, only the blocks it chooses.  Beside the keys and values it keeps
# an INDEX of compressed keys a KV head, ``kbar_j = mean(k[stride * j :
# stride * j + kernel])``.  The query at position t of a context of n = t + 1
# scores the complete windows (softmax over j of q . kbar_j / sqrt(D), summed
# over the query heads of a KV group), a block of ``block`` positions takes
# the largest score of the windows that overlap it, the first ``init_blocks``
# and the ``window / block`` blocks that end at the query's own are always
# taken, and the highest scores fill the list up to ``topk`` blocks a KV
# group.  With n <= dense_len every position is attended.
#
# Paged, the index is one more plane of the node, (P, H_kv * D), a row a
# PAGE: ``page_tokens`` is the stride, so page j of a slot owns window j, and
# the row is written when the window is complete, one page later
# (:func:`paged_append_index`), from the pool's own (dequantized) keys: the
# same row whatever the chunking.  A row is read only where the slot's length
# says it is complete, so a page that is freed and taken again needs no
# clearing.  The selection is data a slot and a KV group; the switch at
# ``dense_len`` is the slot's length.
# ---------------------------------------------------------------------------

class SparseSpec(NamedTuple):
    """The sizes of a node's sparse selection (its ``sparse_*``
    attributes), in positions."""

    topk: int
    block: int
    kernel: int
    stride: int
    init_blocks: int
    window: int
    dense_len: int

    @property
    def list_width(self):
        """Blocks a decode row's list holds: ``topk``, or every block of a
        context still attended densely where that is more."""
        return max(self.topk, -(-self.dense_len // self.block))


def sparse_spec(attrs):
    """The :class:`SparseSpec` of a ``dot_product_attention`` node, or None
    where it selects nothing (``sparse_topk`` 0)."""
    topk = int(attrs.get("sparse_topk", 0) or 0)
    if not topk:
        return None
    spec = SparseSpec(topk, *(
        int(attrs.get("sparse_" + f, default)) for f, default in zip(
            SparseSpec._fields[1:], (64, 32, 16, 1, 2048, 8192))))
    if spec.kernel != 2 * spec.stride or spec.block % spec.stride \
            or spec.window % spec.block:
        raise ValueError(
            "dot_product_attention: sparse selection is built for windows "
            "of two strides, blocks of whole strides and a local window of "
            "whole blocks; got %r" % (spec,))
    return spec


def compress_keys(k, spec):
    """(B, T, E) keys -> (B, W, E) means of the ``W = (T - kernel) // stride
    + 1`` complete windows, float32 (W 0 where none is)."""
    import jax.numpy as jnp

    b, t, e = k.shape
    w = max(0, (t - spec.kernel) // spec.stride + 1)
    if not w:
        return jnp.zeros((b, 0, e), jnp.float32)
    halves = k[:, :(w + 1) * spec.stride].astype(jnp.float32).reshape(
        b, w + 1, spec.stride, e).sum(axis=2)
    return (halves[:, :-1] + halves[:, 1:]) / spec.kernel


def sparse_block_scores(q, kbar, n, spec, blocks, num_heads, num_kv_heads,
                        scale=None):
    """``R (B, H_kv, tq, blocks)`` float32: each block's score for each
    query row, +inf where the block is always taken, -inf where it holds no
    position the row may see.  ``q`` (B, tq, H * D); ``kbar`` (B, W, H_kv *
    D) compressed keys, window ``j`` at row ``j`` (rows of windows that are
    not complete are masked by ``n``); ``n`` (B, tq) the context's length at
    each row, the row's own position included.  Where ``n <= dense_len``
    every block the row may see reads +inf."""
    import jax.numpy as jnp

    b, tq, e = q.shape
    kvh, g = check_head_groups(num_heads, num_kv_heads, e,
                               where="sparse selection")
    hd = e // num_heads
    w = kbar.shape[1]
    per, extra = spec.block // spec.stride, spec.kernel // spec.stride - 1
    n = jnp.asarray(n, jnp.int32).reshape(b, 1, tq, 1)
    blk = jnp.arange(blocks, dtype=jnp.int32).reshape(1, 1, 1, blocks)
    own = (n - 1) // spec.block
    if w:
        logits = jnp.einsum(
            "bqhgd,bwhd->bhgqw", q.reshape(b, tq, kvh, g, hd),
            kbar.astype(q.dtype).reshape(b, w, kvh, hd),
            preferred_element_type=jnp.float32) \
            * (scale or 1.0 / np.sqrt(hd))
        complete = jnp.arange(w, dtype=jnp.int32).reshape(1, 1, 1, 1, w) \
            * spec.stride + spec.kernel <= n[:, :, None]
        p = _softmax_with_sink(logits, complete, None)
        r = jnp.sum(jnp.where(complete, p, 0.0), axis=2)    # (B, kvh, tq, W)
        # block c overlaps windows per * c - extra .. per * c + per - 1
        r = jnp.pad(r, ((0, 0),) * 3 + ((0, max(0, blocks * per - w)),))
        r = r[..., :blocks * per]
        score = jnp.max(r.reshape(b, kvh, tq, blocks, per), axis=-1)
        for back in range(1, extra + 1):
            before = jnp.pad(r, ((0, 0),) * 3 + ((back, 0),))[
                ..., :blocks * per:per]
            score = jnp.maximum(score, before)
    else:
        score = jnp.zeros((b, kvh, tq, blocks), jnp.float32)
    forced = (blk < spec.init_blocks) | (blk > own - spec.window // spec.block)
    score = jnp.where(forced | (n <= spec.dense_len), jnp.inf, score)
    return jnp.where(blk <= own, score, -jnp.inf)


def sparse_choose(score, n, spec, width):
    """``(blocks, valid)``, each (B, H_kv, tq, width): the ``width``
    highest-scored blocks of each row of ``score``
    (:func:`sparse_block_scores`), best first, and which of them the row
    attends: the first ``topk`` (all of them where ``n <= dense_len``) that
    hold a position it may see."""
    import jax
    import jax.numpy as jnp

    width = min(int(width), score.shape[-1])
    top, blocks = jax.lax.top_k(score, width)
    rank = jnp.arange(width, dtype=jnp.int32)
    dense = jnp.asarray(n, jnp.int32).reshape(
        score.shape[0], 1, -1, 1) <= spec.dense_len
    return blocks.astype(jnp.int32), \
        (top > -jnp.inf) & ((rank < spec.topk) | dense)


def _largest_mask(score, k):
    """bool like ``score`` (..., n): its ``k`` largest entries along the
    last axis, the earlier index first among equals: what
    ``jax.lax.top_k`` lists, as a mask and without a sort.  XLA:TPU lowers
    ``top_k`` to a whole sort a row (17 ms a tick of the first form of
    ``sala_serve_longctx``, my chip run, PR 43); here the k-th largest
    value is found by bisection over the bits of a float's ordered integer
    image, 32 counts a row."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(score.astype(jnp.float32), jnp.int32)
    # order-preserving image: negative floats reversed
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    lowest = jnp.iinfo(jnp.int32).min
    at_least = lambda t: jnp.sum(keys >= t[..., None], axis=-1) >= k
    # the sign bit first, then the 31 below it: the largest t with at
    # least k keys >= t is the k-th largest key
    t = jnp.where(at_least(jnp.zeros(keys.shape[:-1], jnp.int32)), 0, lowest)
    for bit in range(30, -1, -1):
        up = t + jnp.int32(1 << bit)
        t = jnp.where(at_least(up), up, t)
    above = keys > t[..., None]
    ties = keys == t[..., None]
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def sparse_block_mask(q, kbar, n, spec, blocks, num_heads, num_kv_heads,
                      scale=None, rows=256):
    """(B, H_kv, tq, blocks) bool: the blocks each query row attends, as a
    mask (the chunk's and the whole sequence's form of the selection that
    :func:`sparse_choose` lists for a decode row).  Computed ``rows`` query
    rows at a time: the scores over the index are (H, rows, W) floats."""
    import jax
    import jax.numpy as jnp

    b, tq, e = q.shape
    n = jnp.broadcast_to(jnp.asarray(n, jnp.int32).reshape(b, -1), (b, tq))

    def part(args):
        q_part, n_part = args
        score = sparse_block_scores(q_part, kbar, n_part, spec, blocks,
                                    num_heads, num_kv_heads, scale)
        seen = score > -jnp.inf
        dense = (n_part <= spec.dense_len)[:, None, :, None]
        if blocks <= spec.topk:
            return seen
        return seen & (dense | _largest_mask(score, spec.topk))

    rows = min(int(rows), tq)
    pad = -tq % rows
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        n = jnp.pad(n, ((0, 0), (0, pad)), constant_values=1)
    parts = (jnp.moveaxis(q.reshape(b, -1, rows, e), 1, 0),
             jnp.moveaxis(n.reshape(b, -1, rows), 1, 0))
    if parts[0].shape[0] == 1:
        mask = part((parts[0][0], parts[1][0]))
    else:
        got = jax.lax.map(part, parts)          # (parts, B, H_kv, rows, n)
        mask = jnp.moveaxis(got, 0, 2).reshape(
            b, got.shape[2], -1, blocks)
    return mask[:, :, :tq]


def sdpa_sparse(q, k, v, spec, num_heads=1, scale=None, num_kv_heads=0,
                layer="attn_sparse"):
    """Causal attention with sparse selection over a whole sequence, no
    cache (``Module`` forward): the index from the keys themselves, the
    selection as a mask over the dense scores."""
    import jax.numpy as jnp

    b, t, e = q.shape
    kvh, g = check_head_groups(num_heads, num_kv_heads, e, v.shape[2],
                               k.shape[2], where="sdpa_sparse")
    hd = e // num_heads
    blocks = -(-t // spec.block)
    with _scope(layer, "select"):
        mask = sparse_block_mask(
            q, compress_keys(k, spec), jnp.arange(1, t + 1)[None, :], spec,
            blocks, num_heads, num_kv_heads, scale)
    qh = q.reshape(b, t, kvh, g, hd)
    with _scope(layer, "scores"):
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qh,
                            k.reshape(b, t, kvh, hd)).astype(jnp.float32) \
            * (scale or 1.0 / np.sqrt(hd))
        pos = jnp.arange(t, dtype=jnp.int32)
        seen = jnp.repeat(mask, spec.block, axis=3)[..., :t] \
            & (pos[None, :] <= pos[:, None])
        p = _softmax_with_sink(logits, seen[:, :, None], None)
    vh = v.reshape(b, t, kvh, v.shape[2] // kvh)
    out = jnp.einsum("bhgqk,bkhe->bqhge", p.astype(vh.dtype), vh)
    return out.reshape(b, t, num_heads * (v.shape[2] // kvh))


def paged_append_index(index, k_pool, table, start_pos, t, spec,
                       active=None, valid=None, layer="attn_sparse",
                       num_kv_heads=0):
    """Write the index rows of the windows that the ``t`` positions just
    appended at ``start_pos`` completed: window ``j`` (positions ``stride *
    j .. stride * j + kernel - 1``, pages ``j`` and ``j + 1`` of the slot's
    table) is the mean of its keys as the pool holds them, written at the
    row of page ``j``.  ``index`` is (P, H_kv * D); ``k_pool`` the node's key
    pool AFTER the append.  Rows of windows not completed here (and those
    of a masked slot) go to the scratch row."""
    import jax.numpy as jnp

    data = _plane(k_pool)
    pt = data.shape[1]
    if pt != spec.stride:
        raise ValueError(
            "sparse selection keeps one index row a page: page_tokens %d "
            "has to be the compression's stride %d" % (pt, spec.stride))
    b, m = table.shape
    with _scope(layer, "index_append"):
        start = jnp.broadcast_to(
            jnp.asarray(start_pos, jnp.int32).reshape(-1), (b,))
        end = start + (t if valid is None
                       else jnp.asarray(valid, jnp.int32).reshape(-1))
        nw = (t + pt - 2) // pt + 1         # windows t tokens can complete
        first = start // pt - 1
        j = first[:, None] + jnp.arange(nw, dtype=jnp.int32)[None, :]
        done = (j >= 0) & (j * pt + spec.kernel <= end[:, None]) \
            & (j * pt + spec.kernel > start[:, None])
        if active is not None:
            done &= jnp.asarray(active).reshape(-1, 1).astype(bool)
        ids = jnp.take_along_axis(
            table.astype(jnp.int32),
            jnp.clip(first[:, None] + jnp.arange(nw + 1, dtype=jnp.int32),
                     0, m - 1), axis=1)                    # (B, nw + 1)
        keys = data[ids].astype(jnp.float32)       # (B, nw + 1, pt, E)
        if isinstance(k_pool, QuantKV):
            w = k_pool.scale.shape[1] // pt
            kvh = int(num_kv_heads) or w // 2
            scales = k_pool.scale[ids]
            scales = scales.reshape(b, nw + 1, pt, 2, kvh)[..., 0, :] \
                if w == 2 * kvh \
                else scales.reshape(b, nw + 1, pt, w)[..., :kvh]
            keys = (keys.reshape(b, nw + 1, pt, kvh, -1)
                    * scales[..., None]).reshape(keys.shape)
        halves = jnp.sum(keys, axis=2)
        rows = ((halves[:, :-1] + halves[:, 1:]) / spec.kernel) \
            .astype(index.dtype)
        at = jnp.where(done, ids[:, :-1], 0)
        return index.at[at.reshape(-1)].set(rows.reshape(b * nw, -1))


def _attend_block_list(q, k_pool, v_pool, table, blocks, valid, n, spec,
                       num_heads, num_kv_heads, scale, layer):
    """One query row a slot over the blocks its KV groups chose: ``blocks``
    / ``valid`` (B, H_kv, L).  The L blocks of every group are gathered
    (whole pages, as the pool lies) into one view a slot, group after
    group, and each head attends its own group's stretch."""
    import jax.numpy as jnp

    b, kvh, width = blocks.shape
    pt = _plane(k_pool).shape[1]
    per = spec.block // pt
    with _scope(layer, "kv_gather"):
        pages = blocks[..., None] * per \
            + jnp.arange(per, dtype=jnp.int32)              # (B, kvh, L, per)
        ids = jnp.take_along_axis(
            table.astype(jnp.int32),
            jnp.clip(pages.reshape(b, -1), 0, table.shape[1] - 1), axis=1)
        ids = jnp.where(jnp.repeat(valid.reshape(b, -1), per, axis=1),
                        ids, 0)
        k_view, v_view = paged_gather_kv(k_pool, v_pool, ids, kvh)
    c = kvh * width * spec.block
    pos = (blocks[..., None] * spec.block
           + jnp.arange(spec.block, dtype=jnp.int32)).reshape(b, kvh, -1)
    seen = (pos < jnp.asarray(n, jnp.int32).reshape(b, 1, 1)) \
        & jnp.repeat(valid, spec.block, axis=2)             # (B, kvh, L * blk)
    # head group g reads stretch g of the view
    allow = (jnp.eye(kvh, dtype=bool)[None, :, :, None]
             & seen[:, :, None, :]).reshape(b, kvh, c)
    g = num_heads // kvh
    allow = allow.reshape((b, kvh) + ((1,) if g > 1 else ()) + (1, c))
    return _sdpa_cache(q, k_view, v_view, jnp.full((b,), c, jnp.int32),
                       num_heads, scale, num_kv_heads=num_kv_heads,
                       layer=layer, allow=allow)


def paged_attend_sparse(q, k_pool, v_pool, index, table, total_len, spec,
                        num_heads=1, scale=None, num_kv_heads=0,
                        active=None, mesh_active=False, layer="attn_sparse"):
    """:func:`paged_attend` for a node with sparse selection: ``(out,
    (blocks chosen, blocks live))``, the counts int32 scalars summed over
    the rows, the KV groups and (a chunk) the query rows, masked slots left
    out.

    One query row a slot (the decode step): the index rows of the slot's
    pages are scored, the list of blocks chosen (:func:`sparse_choose`) and
    only those blocks' pages gathered and attended
    (:func:`_attend_block_list`): what the step reads of the pools follows
    the selection, not the context's length.  The list is ``topk`` wide;
    only while some slot is still under ``dense_len`` with more live blocks
    than that does the step take the wide branch
    (:attr:`SparseSpec.list_width`), one program for both.

    More rows (a chunk): each row has its own list, and this first form
    lays it as a mask over every live block (:func:`_attend_live_blocks`):
    the same mathematics, at dense cost.  One slot's chunk over shapes the
    chunk's kernel tiles (:func:`chunk_kernel_selected`, the mask's shape
    shown to it) takes the blocks and the mask to that kernel
    (``chunk-kernel`` in ``mx_attn_dispatch_total{path}``), which widens a
    row's choice to positions in fast memory and visits every live block
    as the walk does; anything else keeps the walk's loop (``walk``), or,
    without a plan, the whole gathered view (``whole``)."""
    import jax
    import jax.numpy as jnp

    b, tq, _ = q.shape
    kvh = int(num_kv_heads) or num_heads
    pt = _plane(k_pool).shape[1]
    m = table.shape[1]
    nblocks = -(-m * pt // spec.block)
    total = jnp.broadcast_to(
        jnp.asarray(total_len, jnp.int32).reshape(-1), (b,))
    n = total[:, None] - (tq - 1) + jnp.arange(tq, dtype=jnp.int32)[None, :]
    on = jnp.ones((b,), bool) if active is None \
        else jnp.asarray(active).reshape(-1).astype(bool)
    live = jnp.sum(jnp.where(on[:, None], -(-n // spec.block), 0)) * kvh
    with _scope(layer, "select"):
        kbar = index[table]                               # (B, M, E)
    if tq == 1:
        width = min(spec.list_width, nblocks)
        with _scope(layer, "select"):
            score = sparse_block_scores(q, kbar, n, spec, nblocks, num_heads,
                                        kvh, scale)
            blocks, valid = sparse_choose(score, n, spec, width)
            blocks, valid = blocks[:, :, 0], valid[:, :, 0]
            chosen = jnp.sum(jnp.where(on[:, None, None], valid, False))
        attend = lambda w: _attend_block_list(
            q, k_pool, v_pool, table, blocks[..., :w], valid[..., :w],
            total, spec, num_heads, kvh, scale, layer)
        narrow = min(spec.topk, width)
        if narrow < width:
            wide = jnp.any(valid[..., narrow:] & on[:, None, None])
            out = jax.lax.cond(wide, lambda: attend(width),
                               lambda: attend(narrow))
        else:
            out = attend(width)
        return out, (chosen.astype(jnp.int32), live.astype(jnp.int32))
    with _scope(layer, "select"):
        mask = sparse_block_mask(q, kbar, n, spec, nblocks, num_heads, kvh,
                                 scale)
        chosen = jnp.sum(jnp.where(on[:, None, None, None], mask, False))
    plan = live_block_plan(q.shape, table.shape, pt, mesh_active=mesh_active)
    if plan is not None and plan[0] % spec.block == 0:
        tiles, interpret = chunk_kernel_selected(
            q.shape, k_pool, v_pool, table.shape, num_heads, kvh,
            mesh_active=mesh_active, chosen=(mask.shape, spec.block))
        _note_path("walk" if tiles is None else "chunk-kernel", DECODE_PATH)
        out = _attend_live_blocks(
            q, k_pool, v_pool, table, total, num_heads, scale, kvh, *plan,
            layer=layer, chosen=(mask, spec.block),
            chunk=None if tiles is None else (tiles, interpret))
    else:
        _note_path("whole", DECODE_PATH)
        with _scope(layer, "kv_gather"):
            k_view, v_view = paged_gather_kv(k_pool, v_pool, table, kvh)
        allow = jnp.repeat(mask, spec.block, axis=3)[..., :m * pt]
        out = _sdpa_cache(q, k_view, v_view, total, num_heads, scale,
                          num_kv_heads=kvh, mesh_active=mesh_active,
                          layer=layer,
                          allow=allow if kvh == num_heads else allow[:, :, None])
    return out, (chosen.astype(jnp.int32), live.astype(jnp.int32))


def cache_attend(q, k_cache, v_cache, total_len, num_heads=1, scale=None,
                 mesh_active=False, num_kv_heads=0, window=0, sink=None,
                 value_scale=1.0, layer="attn"):
    """Decode/verify attention over dense (B, C, E) ring buffers — the
    non-paged twin of :func:`paged_attend`: :func:`sdpa_decode` /
    :func:`sdpa_verify` over the whole ring (``whole`` in
    ``mx_attn_dispatch_total``)."""
    _note_path("whole", DECODE_PATH)
    return _sdpa_cache(q, k_cache, v_cache, total_len, num_heads, scale,
                       num_kv_heads=num_kv_heads, mesh_active=mesh_active,
                       **_extras(window, sink, value_scale, layer))


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, section 2.1): a position is cached
# as ONE row shared by all heads, ``[c ; rot(k_rope)]``: the normed latent of
# ``kv_lora_rank`` values from which every head's key (without positions) and
# value are linear maps (``W_kvb``, (H * (nope + v), rank)), and one rotated
# key part of ``qk_rope_head_dim``.  A head's score is ``scale * (q_nope .
# W_k c + q_rope . k_rope)``.  :func:`latent_mix` is the one mathematics in
# three forms, as ``ops.ssm.mix`` and ``ops.linattn.mix`` have theirs:
#
# * a whole sequence from nothing (``cache`` None): the rows are expanded
#   into per-head keys and values and attended causally; the rows are
#   returned for the caller to keep;
# * rows against the cache, EXPANDED (a prefill chunk): the cached rows of
#   each live block are turned back into per-head keys and values inside the
#   walk over the live blocks (never a view of the whole table), and
#   attended as any keys and values are;
# * rows against the cache, ABSORBED (a decode row): ``W_k`` is folded into
#   the query (``q_abs = W_k^T q_nope``, rank wide) and ``W_v`` applied after
#   the weighted sum, so the pages are read as they lie: one "key" of rank +
#   rope and one "value" (its first rank values) a position for all heads.
#
# Paged, a page of the plane holds its positions' values one after the other,
# and never as (page_tokens, 320): a row of 320 values is no whole number of
# 128 lanes, and XLA:TPU lays a (P, page_tokens, 320) array out pages-minor
# to save the padding, then converts the whole pool to rows-minor and back
# around every scatter and gather (seen in the optimized HLO of the decode
# step compiled for a described v5e, PR 50: two copies of 0.85 GB a layer a
# tick).  The page is cut into rows of the fewest positions that make whole
# lanes, (P, page_tokens / 2, 2 * 320) at 320 values and pages of 16
# (``pallas_decode.latent_plane_shape``): a page is then whole tiles of HBM,
# eight rows of five, contiguous, and the decode row's kernel copies it
# alone.  (Stored a page a ROW, (P, page_tokens * 320), as a quantized pool's
# scale plane is, a tile of HBM holds eight PAGES' rows, two interleaved word
# by word: no copy brings one page, Mosaic refuses the cut, and XLA's own
# gather of such rows moved 217 GB/s, PR 50.  Widths and pages that give no
# rows of whole lanes keep a page a row.)  Rows are written by
# :func:`_append_scales`' read-select-write of the few pages a call touches,
# and what a gather brings is reshaped to positions, a copy of the gathered
# block and never of the pool.
#
# An absorbed call of ONE row a slot over such a plane takes a third path
# (:func:`latent_kernel_selected`): the list of live blocks the walk builds
# goes to one Pallas kernel that copies each live block's pages into fast
# memory itself and takes both folded products there as the pages lie
# (``pallas_decode.attend_latent_blocks``): no gathered view, no re-layout, a
# live byte read once.
#
# Which of the two cached forms a call takes follows from its rows a slot
# (:data:`LATENT_EXPAND_ROWS`): a (query row, cached position) pair costs
# ``2 (rank + rope) + 2 rank`` FLOP a head absorbed against ``2 (nope + rope)
# + 2 v`` expanded, and expanding costs ``2 rank (nope + v)`` a head a
# position once a call.
# ---------------------------------------------------------------------------

LATENT_OP = "LatentAttention"
# rows a slot from which the cached rows are expanded: with r = rank, e =
# rope, n = nope, v: absorbed t (4 r + 2 e) = expanded t (2 n + 2 e + 2 v) +
# 2 r (n + v) at t = r (n + v) / (2 r - n - v), 154 at 256 / 64 / 128; the
# expanded products are also the better fed (a head's own contraction of n +
# e against t rows, where the absorbed one keeps H x t rows against one key),
# so the switch is taken somewhat below.  RECKONED from those counts, NOT
# MEASURED: the one cell that runs the op calls it with 1 row and with 2048,
# and no chip reading lies near the flip
LATENT_EXPAND_ROWS = 96


class LatentSpec(NamedTuple):
    """The sizes and constants of a :data:`LATENT_OP` node, read off its
    attributes (:func:`latent_spec`)."""

    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    inv_freq: tuple     # turn a position of pair (2j, 2j + 1), rope / 2
    trig_scale: float   # cos and sin are multiplied by this
    scale: float        # the softmax's
    temp_beta: float    # the query x (1 + beta ln(1 + floor(p / temp_span)))
    temp_span: int
    layer: str


def yarn_mscale(factor, mscale):
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 at factor
    <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * float(mscale) * np.log(factor) + 1.0


def yarn_ramp(dim, theta, beta_fast, beta_slow, original_max):
    """``(lo, hi)``: the pairs between which YaRN blends interpolated and
    original frequencies: ``lo = floor(corr(beta_fast))``, ``hi =
    ceil(corr(beta_slow))``, ``corr(n) = dim ln(original_max / (2 pi n)) /
    (2 ln theta)``, held to [0, dim - 1]."""
    corr = lambda n: dim * np.log(original_max / (2 * np.pi * n)) \
        / (2 * np.log(theta))
    return (max(int(np.floor(corr(beta_fast))), 0),
            min(int(np.ceil(corr(beta_slow))), dim - 1))


def rope_frequencies(dim, theta, rope_type="default", factor=1.0,
                     beta_fast=32.0, beta_slow=1.0, original_max=0):
    """The ``dim / 2`` inverse frequencies of a rotation (float64 numpy):
    ``theta^(-2j / dim)``, and under ``rope_type`` "yarn" the blend ``(1 -
    g_j) theta_j / factor + g_j theta_j`` with ``g_j = 1 - clip((j - lo) /
    (hi - lo), 0, 1)`` over :func:`yarn_ramp`'s ``lo`` and ``hi``."""
    j = np.arange(dim // 2, dtype=np.float64)
    inv = float(theta) ** (-2.0 * j / dim)
    if rope_type != "yarn":
        return inv
    lo, hi = yarn_ramp(dim, theta, beta_fast, beta_slow, original_max)
    keep = 1.0 - np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - keep) * inv / float(factor) + keep * inv


def latent_spec(attrs):
    """The :class:`LatentSpec` of a node's attributes."""
    g = lambda k, d: attrs.get(k, d)
    nope, rope_dim = int(attrs["qk_nope_head_dim"]), \
        int(attrs["qk_rope_head_dim"])
    yarn = g("rope_type", "default") == "yarn"
    factor = float(g("rope_factor", 1.0)) if yarn else 1.0
    inv = rope_frequencies(
        rope_dim, float(g("rope_theta", 10000.0)), g("rope_type", "default"),
        factor, float(g("beta_fast", 32.0)), float(g("beta_slow", 1.0)),
        int(g("original_max_position_embeddings", 0)))
    all_dim = float(g("mscale_all_dim", 0.0))
    trig = yarn_mscale(factor, float(g("mscale", 1.0))) \
        / yarn_mscale(factor, all_dim) if yarn else 1.0
    scale = float(nope + rope_dim) ** -0.5
    if yarn and all_dim:
        scale *= yarn_mscale(factor, all_dim) ** 2
    return LatentSpec(
        int(attrs["num_heads"]), nope, rope_dim, int(attrs["v_head_dim"]),
        int(attrs["kv_lora_rank"]), tuple(float(x) for x in inv),
        float(trig), float(scale),
        float(g("query_scaling_beta", 0.0)),
        int(g("original_max_position_embeddings", 0)),
        attrs.get("__layer__") or "attn_latent")


def latent_rotate(x, positions, heads, spec):
    """``x`` (B, t, heads * rope) turned at ``positions`` (B, t) by the
    node's frequencies, dims (2j, 2j + 1) a pair.  Float32 inside, ``x``'s
    type out."""
    import jax.numpy as jnp

    b, t, _ = x.shape
    with _scope(spec.layer, "rope"):
        ang = jnp.asarray(positions, jnp.float32)[:, :, None, None] \
            * jnp.asarray(spec.inv_freq, jnp.float32)
        cos = jnp.cos(ang) * spec.trig_scale
        sin = jnp.sin(ang) * spec.trig_scale
        xh = x.reshape(b, t, heads, spec.rope).astype(jnp.float32)
        x1, x2 = xh[..., 0::2], xh[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(b, t, heads * spec.rope).astype(x.dtype)


def latent_query_scale(positions, spec):
    """The query's temperature at ``positions``: ``1 + beta ln(1 + floor(p
    / span))``, float32; None where the node has none."""
    import jax.numpy as jnp

    if not spec.temp_beta or not spec.temp_span:
        return None
    steps = jnp.floor_divide(jnp.asarray(positions, jnp.int32),
                             spec.temp_span).astype(jnp.float32)
    return 1.0 + spec.temp_beta * jnp.log1p(steps)


def _latent_weights(w_kvb, spec):
    """``W_kvb`` (H * (nope + v), rank) as ``(W_k (H, nope, rank), W_v (H,
    v, rank))``."""
    w = w_kvb.reshape(spec.heads, spec.nope + spec.v, spec.rank)
    return w[:, :spec.nope], w[:, spec.nope:]


def latent_expand(rows, w_kvb, spec):
    """Cached rows (N, C, rank + rope) -> every head's keys (N, C, H *
    (nope + rope)) and values (N, C, H * v): the latent through ``W_kvb``,
    the one rotated part beside every head's own."""
    import jax.numpy as jnp

    n, c, _ = rows.shape
    h = spec.heads
    with _scope(spec.layer, "expand"):
        kv = jnp.einsum("ncr,hdr->nchd", rows[..., :spec.rank],
                        w_kvb.reshape(h, spec.nope + spec.v, spec.rank)
                        .astype(rows.dtype))
        k = jnp.concatenate(
            [kv[..., :spec.nope], jnp.broadcast_to(
                rows[:, :, None, spec.rank:], (n, c, h, spec.rope))], axis=-1)
        return (k.reshape(n, c, h * (spec.nope + spec.rope)),
                kv[..., spec.nope:].reshape(n, c, h * spec.v))


def _note_latent(form):
    """Count a node traced against a cache under the form it took
    (``expanded``, ``absorbed``, or ``absorbed-kernel`` / ``expanded-kernel``
    where the absorbed row / the expanded chunk went to its Pallas kernel),
    and leave it in :data:`DECODE_PATH` for the program's record, as
    :func:`paged_attend` leaves its path."""
    from .. import obs as _obs

    DECODE_PATH["last"] = form
    _obs.registry.counter(
        "mx_attn_latent_dispatch_total",
        "LatentAttention nodes traced against a cache, by the form they took",
        labels=("form",)).labels(form=form).inc()


def latent_form(rows):
    """``"expanded"`` or ``"absorbed"``: the form a call with ``rows`` query
    rows a slot takes against the cache."""
    return "expanded" if int(rows) >= LATENT_EXPAND_ROWS else "absorbed"


def latent_pages(plane, ids, width):
    """The pages ``ids`` (N, M) of a latent plane (a page's positions one
    after the other, in rows of any length) as rows a position: (N, M *
    page_tokens, width)."""
    return plane[ids].reshape(ids.shape[0], -1, width)


def latent_kernel_selected(q_shape, plane, table_shape, spec,
                           mesh_active=False):
    """``(take, interpret)``: whether :func:`latent_attend` hands the live
    blocks of this call to the absorbed row's Pallas kernel
    (``pallas_decode.attend_latent_blocks``), decided from what the call
    shows, as :func:`decode_kernel_selected` decides for
    :func:`paged_attend`.

    All must hold: the absorbed form with ONE query row a slot (``q_shape``
    is (B, 1, H * (rank + rope)); rows 2-95 are matrix products already and
    take the walk); a paged plane (``table_shape`` not None: a dense ring is
    attended whole); a :func:`live_block_plan` (no mesh, a view of more than
    a block); a backend that runs Pallas (:func:`_kernel_backend`); and
    shapes the kernel tiles (``pallas_decode.latent_tiles``: a plane stored
    in rows of whole lane tiles and pages of whole sublane tiles, a rank of
    whole lane tiles, a step's buffers within fast memory).  ``take`` is the
    call's ``LatentTiles`` where it is taken (its ``block`` the positions a
    step, the kernel's own rule), None where it is not."""
    from . import pallas_decode as _pd

    runs, interpret = _kernel_backend()
    if q_shape[1] != 1 or table_shape is None or not runs \
            or live_block_plan(
                q_shape, table_shape,
                _page_positions(plane, spec.rank + spec.rope),
                mesh_active=mesh_active) is None:
        return None, False
    return _pd.latent_tiles(q_shape, plane, table_shape, spec.heads,
                            spec.rank, spec.rank + spec.rope), interpret


def latent_chunk_kernel_selected(q_shape, plane, table_shape, spec,
                                 mesh_active=False):
    """``(take, interpret)``: whether :func:`latent_attend` hands a prefill
    chunk in the expanded form to the chunk's Pallas kernel
    (``pallas_decode.attend_latent_segment`` under
    :func:`_attend_latent_segments`) in the walk's place, decided from what
    the call shows, as :func:`latent_kernel_selected` decides for the decode
    row.

    All must hold: the expanded form (:func:`latent_form`) with ONE slot of
    at least :data:`CHUNK_MIN_ROWS` query rows (``q_shape`` is (1, t, H *
    (nope + rope))); a paged plane (``table_shape`` not None: a dense ring is
    attended whole); a :func:`live_block_plan` (no mesh, a view of more than
    a block); a backend that runs Pallas (:func:`_kernel_backend`); and
    shapes the kernel tiles (``pallas_decode.latent_chunk_tiles``: keys and
    values of whole lane tiles a head, a head's running row and a tile's
    scores within fast memory).  ``take`` is the call's ``LatentChunkTiles``
    where it is taken, None where it is not."""
    from . import pallas_decode as _pd

    runs, interpret = _kernel_backend()
    pt = _page_positions(plane, spec.rank + spec.rope)
    if q_shape[0] != 1 or q_shape[1] < CHUNK_MIN_ROWS \
            or latent_form(q_shape[1]) != "expanded" or table_shape is None \
            or not runs or live_block_plan(
                q_shape, table_shape, pt, mesh_active=mesh_active) is None:
        return None, False
    return _pd.latent_chunk_tiles(
        q_shape[1], spec.heads, spec.nope + spec.rope, spec.v, plane.dtype,
        pt, table_shape[1] * pt), interpret


def _attend_latent_segments(q_nope, q_rope, cache, table, total_len, w_kvb,
                            spec, tiles, interpret):
    """:func:`_attend_live_blocks`'s running row for an expanded chunk, a
    SEGMENT of ``tiles.segment`` positions a step and the step a Pallas
    kernel (:func:`latent_chunk_kernel_selected`).  A loop whose trip count
    is data, the segments the slot's length has reached, gathers a segment's
    pages, re-lays them out to positions and expands them through ``W_kvb``
    heads first (the walk's step at a wider stride), and
    ``pallas_decode.attend_latent_segment`` folds the segment into the
    running ``(max, sum, acc)`` with the scores in fast memory.  The limit is
    the walk's, every live position is attended, and the combine after the
    loop is the walk's own."""
    import jax
    import jax.numpy as jnp

    from . import pallas_decode as _pd

    _, tq, h, _ = q_nope.shape
    width = spec.rank + spec.rope
    pt = _page_positions(cache, width)
    cap = table.shape[1] * pt
    pps = tiles.segment // pt
    ns = -(-table.shape[1] // pps)
    total = jnp.asarray(total_len, jnp.int32).reshape(-1)[0]
    with _scope(spec.layer, "kv_gather"):
        # a last segment that is not whole reads the scratch page past the
        # table's end: those positions lie at or above the capacity
        pages = jnp.pad(table[0].astype(jnp.int32),
                        (0, ns * pps - table.shape[1])).reshape(ns, pps)
        steps = jnp.where(total >= cap, ns,
                          jnp.clip(-(-total // tiles.segment), 1, ns))
    w_k, w_v = (w.astype(cache.dtype) for w in _latent_weights(w_kvb, spec))
    # a head's key weights over a whole cached row: its own columns from the
    # latent, the rotated part as it lies (an identity), (H, nope + rope,
    # width), so that ONE product lays a head's keys side by side
    w_k = jnp.concatenate(
        [jnp.pad(w_k, ((0, 0), (0, 0), (0, spec.rope))),
         jnp.broadcast_to(jnp.pad(jnp.eye(spec.rope, dtype=cache.dtype),
                                  ((0, 0), (spec.rank, 0))),
                          (h, spec.rope, width))], axis=1)
    q = jnp.swapaxes(jnp.concatenate([q_nope, q_rope], axis=-1)[0], 0, 1)
    state = (jnp.concatenate(
        [jnp.full((h, 1, tq), jnp.finfo(jnp.float32).min, jnp.float32),
         jnp.zeros((h, 7, tq), jnp.float32)], axis=1),
        jnp.zeros((h, tq, spec.v), jnp.float32))

    def step(carry):
        i, state = carry
        with _scope(spec.layer, "kv_gather"):
            rows = latent_pages(cache, jax.lax.dynamic_slice_in_dim(
                pages, i, 1), width)[0]                     # (segment, width)
        with _scope(spec.layer, "expand"):
            keys = jnp.einsum("cw,hdw->hcd", rows, w_k)
            values = jnp.einsum("cr,hdr->hcd", rows[:, :spec.rank], w_v)
        with _scope(spec.layer, "scores"):
            state = _pd.attend_latent_segment(
                q, keys, values, state, i * tiles.segment, total, cap, tiles,
                spec.scale, interpret=interpret)
        return i + 1, state

    _, (stats, acc) = jax.lax.while_loop(
        lambda carry: carry[0] < steps, step, (jnp.int32(0), state))
    with _scope(spec.layer, "scores"):
        m, den = (jnp.swapaxes(stats[:, r], 0, 1)[None, None]
                  for r in (0, 1))                          # (1, 1, tq, H)
        acc = jnp.swapaxes(acc, 0, 1)[None, None]
    return _combine_blocks(m, den, acc, None, 1.0, cache.dtype, spec.layer)


def latent_attend(q_nope, q_rope, cache, table, total_len, w_kvb, spec,
                  mesh_active=False):
    """(B, t, H, nope) and (B, t, H, rope) queries, already rotated, against
    the cached rows: ``cache`` a pool of pages of ``page_tokens * (rank +
    rope)`` values (``pallas_decode.latent_plane_shape``) read through
    ``table`` (B, M), or a dense ring (B, C, rank + rope) where ``table`` is
    None.  ``total_len`` counts the rows appended, the queries' own included.
    -> (B, t, H * v), in the form the rows a slot choose (:func:`latent_form`).

    The absorbed form has two paths over a table, chosen from what the call
    shows and counted in ``mx_attn_latent_dispatch_total{form}``: the walk
    over the live blocks (``absorbed``: a step gathers its blocks' pages and
    re-lays them out to positions), and, for one row a slot over shapes the
    kernel tiles (:func:`latent_kernel_selected`), ONE Pallas kernel over the
    same list that copies each live block's pages as they lie and multiplies
    them there (``absorbed-kernel``).  The arithmetic is the walk's (the
    serving type's operands, float32 sums, probabilities rounded to the
    plane's type) in another order; the logits are not rounded on the way.

    So has the expanded form: the walk (``expanded``: a step gathers and
    expands a block and leaves its float32 scores to HBM between the two
    products), and, for ONE slot's prefill chunk over shapes the kernel tiles
    (:func:`latent_chunk_kernel_selected`), the walk by segments of several
    blocks with a Pallas kernel as its step (``expanded-kernel``,
    :func:`_attend_latent_segments`): the scores and a head's running row
    stay in fast memory.  The same arithmetic in another order again."""
    import jax.numpy as jnp

    b, t, h, _ = q_nope.shape
    form = latent_form(t)
    width = spec.rank + spec.rope
    shown = (None if table is None else table.shape, spec)
    tiles, interpret = latent_chunk_kernel_selected(
        (b, t, h * (spec.nope + spec.rope)), cache, *shown,
        mesh_active=mesh_active) if form == "expanded" else \
        latent_kernel_selected((b, t, h * width), cache, *shown,
                               mesh_active=mesh_active)
    _note_latent(form if tiles is None else form + "-kernel")
    if form == "expanded" and tiles is not None:
        return _attend_latent_segments(q_nope, q_rope, cache, table,
                                       total_len, w_kvb, spec, tiles,
                                       interpret)
    w_k, w_v = _latent_weights(w_kvb, spec)
    if form == "expanded":
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        kvh, hdv = h, spec.v
        blocks = lambda rows: latent_expand(rows, w_kvb, spec)
    else:
        with _scope(spec.layer, "absorb"):
            q_abs = jnp.einsum("bthd,hdr->bthr", q_nope,
                               w_k.astype(q_nope.dtype))
        q = jnp.concatenate([q_abs, q_rope], axis=-1)
        kvh, hdv = 1, spec.rank
        blocks = lambda rows: (rows, rows[..., :spec.rank])
    q = q.reshape(b, t, -1)
    pt = None if table is None else _page_positions(cache, width)
    if tiles is not None:
        # the kernel's own step, a row of the list a block
        plan, kernel = (tiles.block, 1), (tiles, interpret)
    else:
        plan, kernel = None if table is None else live_block_plan(
            q.shape, table.shape, pt, mesh_active=mesh_active), None
    if plan is not None:
        out = _attend_live_blocks(
            q, cache, cache, table, total_len, h, spec.scale, kvh, *plan,
            layer=spec.layer, hdv=hdv, page_tokens=pt, kernel=kernel,
            gather=lambda ids: blocks(latent_pages(cache, ids, width)))
    else:
        with _scope(spec.layer, "kv_gather"):
            k, v = blocks(cache if table is None
                          else latent_pages(cache, table, width))
        out = _sdpa_cache(q, k, v, total_len, h, spec.scale,
                          num_kv_heads=kvh, mesh_active=mesh_active,
                          layer=spec.layer)
    if form == "absorbed":
        with _scope(spec.layer, "absorb"):
            out = jnp.einsum("bthr,her->bthe",
                             out.reshape(b, t, h, spec.rank),
                             w_v.astype(out.dtype)).reshape(b, t, h * spec.v)
    return out


def latent_mix(attrs, q, c, k_rope, w_kvb, cache=None, table=None, pos0=None,
               active=None, valid=None, mesh_active=False):
    """``(out (B, t, H * v), rows or cache)``: latent attention over the
    projected streams in one of the section's three forms.  ``q`` (B, t, H *
    (nope + rope)) is the up-projected query, ``c`` (B, t, rank) the normed
    latent, ``k_rope`` (B, t, rope) the shared key part before its rotation,
    ``w_kvb`` (H * (nope + v), rank).  ``pos0`` (B,) is the first position
    (0 where None).  ``cache`` None: the sequence is attended by itself and
    its rows ``[c ; rot(k_rope)]`` (B, t, rank + rope) come back.  Else the
    rows are appended to ``cache`` first (a pool (P, page_tokens * (rank +
    rope)) through ``table``, its writes masked by ``active`` and ``valid``
    as :func:`paged_append` masks them; a dense ring where ``table`` is None) and the rows attend what it
    then holds; the cache comes back."""
    import jax.numpy as jnp

    spec = latent_spec(attrs)
    b, t, _ = q.shape
    h = spec.heads
    if q.shape[2] != h * (spec.nope + spec.rope) or c.shape[2] != spec.rank \
            or k_rope.shape[2] != spec.rope:
        raise ValueError(
            "%s: q %s, latent %s, key_rope %s are not (B, T, %d x %d), (B, "
            "T, %d), (B, T, %d)" % (LATENT_OP, q.shape, c.shape,
                                    k_rope.shape, h, spec.nope + spec.rope,
                                    spec.rank, spec.rope))
    start = jnp.zeros((b,), jnp.int32) if pos0 is None else \
        jnp.broadcast_to(jnp.asarray(pos0, jnp.int32).reshape(-1), (b,))
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    qh = q.reshape(b, t, h, spec.nope + spec.rope)
    q_nope = qh[..., :spec.nope]
    q_rope = latent_rotate(qh[..., spec.nope:].reshape(b, t, -1), positions,
                           h, spec).reshape(b, t, h, spec.rope)
    temp = latent_query_scale(positions, spec)
    if temp is not None:
        with _scope(spec.layer, "rope"):
            warm = lambda x: (x.astype(jnp.float32)
                              * temp[:, :, None, None]).astype(x.dtype)
            q_nope, q_rope = warm(q_nope), warm(q_rope)
    rows = jnp.concatenate(
        [c, latent_rotate(k_rope, positions, 1, spec)], axis=-1)
    if cache is None:
        k, v = latent_expand(rows, w_kvb, spec)
        out = _sdpa_extra(
            jnp.concatenate([q_nope, q_rope], axis=-1).reshape(b, t, -1), k,
            v, h, True, spec.scale, h, 0, None, 1.0, spec.layer)
        return out, rows
    if table is None:
        cache = cache_append(cache, rows, start, layer=spec.layer)
    else:
        with _scope(spec.layer, "kv_append"):
            cache = _append_scales(
                cache, table, *_latest(
                    rows, start, table.shape[1]
                    * _page_positions(cache, rows.shape[2]))[:2],
                active, valid)
    return latent_attend(q_nope, q_rope, cache, table, start + t, w_kvb,
                         spec, mesh_active=mesh_active), cache


def _latent_arguments(attrs):
    return ["query", "latent", "key_rope", "kv_b_weight"]


def _latent_shape(attrs, in_shapes, aux_shapes):
    h = int(attrs["num_heads"])
    nope, rope_dim = int(attrs["qk_nope_head_dim"]), \
        int(attrs["qk_rope_head_dim"])
    v, rank = int(attrs["v_head_dim"]), int(attrs["kv_lora_rank"])
    lead = tuple(in_shapes[0][:-1])
    return ([lead + (h * (nope + rope_dim),), lead + (rank,),
             lead + (rope_dim,), (h * (nope + v), rank)],
            [lead + (h * v,)], [])


def _register_latent():
    def fcompute(attrs, inputs, aux, octx):
        return [latent_mix(attrs, *inputs)[0]], list(aux)

    register_op(OpDef(
        LATENT_OP, fcompute,
        schema=ParamSchema(
            Param("num_heads", int, required=True),
            Param("qk_nope_head_dim", int, required=True,
                  doc="dims of a head's query and key that take no position"),
            Param("qk_rope_head_dim", int, required=True,
                  doc="rotated dims: a head's own in the query, ONE vector a "
                      "position shared by all heads in the key"),
            Param("v_head_dim", int, required=True),
            Param("kv_lora_rank", int, required=True,
                  doc="width of the cached latent"),
            Param("rope_theta", float, default=10000.0),
            Param("rope_type", str, default="default",
                  doc="'yarn': frequencies blended between theta_j / "
                      "rope_factor and theta_j (rope_frequencies)"),
            Param("rope_factor", float, default=1.0),
            Param("beta_fast", float, default=32.0),
            Param("beta_slow", float, default=1.0),
            Param("original_max_position_embeddings", int, default=0,
                  doc="the window YaRN extends, and the span of the query "
                      "temperature's steps"),
            Param("mscale", float, default=1.0),
            Param("mscale_all_dim", float, default=0.0,
                  doc="not 0 under yarn: the softmax scale x m(factor, "
                      "this)^2, and cos / sin x m(factor, mscale) / "
                      "m(factor, this)"),
            Param("query_scaling_beta", float, default=0.0,
                  doc="the query after rotation x (1 + beta ln(1 + floor(p "
                      "/ original_max_position_embeddings))); 0 = none"),
        ),
        num_inputs=4, arguments=_latent_arguments,
        infer_shape=_latent_shape,
        doc="Multi-head latent attention over a projected query (B, T, H * "
            "(nope + rope)), the normed latent (B, T, rank), the shared "
            "rotary key part (B, T, rope) and the up-projection W_kvb (H * "
            "(nope + v), rank): causal, returns (B, T, H * v).  Stateful in "
            "serving: DecodePredictor keeps one row of rank + rope a "
            "position (a 'latent' cache layout) and attends it expanded "
            "(chunks) or absorbed (decode rows)."))


_KV_LAYOUT_WARNED = {"done": False}


def apply_kv_layout(buf, device=None):
    """Place a KV cache/pool buffer with the device layout requested by
    ``MXNET_KV_LAYOUT`` — a comma-separated ``major_to_minor``
    permutation, set from the winning row of ``benchmarks/layout_probe.py
    --kv`` (which times decode attention under each candidate pool layout
    on the bench chip, per the ROADMAP's wire-the-probe clause).

    Empty knob (default): a plain ``device_put`` to ``device`` (or the
    buffer as-is when no device is given).  A backend that refuses
    the requested layout falls back to its native one with a one-time
    warning, so the knob is safe to leave set in mixed fleets."""
    import jax

    from .. import config as _config

    spec = str(_config.get("MXNET_KV_LAYOUT")).strip()
    if not spec:
        return jax.device_put(buf, device) if device is not None else buf
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    try:
        order = tuple(int(t) for t in spec.split(","))
        if sorted(order) != list(range(buf.ndim)):
            raise ValueError(
                "MXNET_KV_LAYOUT=%r is not a permutation of 0..%d"
                % (spec, buf.ndim - 1))
        dev = device if device is not None else jax.devices()[0]
        # some backends accept the request but silently keep their native
        # layout; that is fine — the request is best-effort by design
        return jax.device_put(buf, Format(Layout(major_to_minor=order),
                                          SingleDeviceSharding(dev)))
    except (ValueError, RuntimeError) as exc:
        # a malformed spec, or a backend that refuses the layout
        if not _KV_LAYOUT_WARNED["done"]:
            _KV_LAYOUT_WARNED["done"] = True
            import warnings

            warnings.warn(
                "MXNET_KV_LAYOUT=%r not applied (%s); KV buffers keep "
                "the backend's native layout" % (spec, exc))
        return jax.device_put(buf, device) if device is not None else buf


def node_extras(attrs, sink=None):
    """What a ``dot_product_attention`` node's attributes (and its fourth
    input, the ``sink``) add to plain causal attention, as the keywords
    :func:`sdpa`, :func:`paged_attend` and :func:`cache_attend` take;
    empty for a plain node, which is then called as it always was.
    ``layer`` is the node's ``__layer__`` attribute: the scope its
    sub-scopes sit under."""
    return _extras(int(attrs.get("window", 0) or 0), sink,
                   float(attrs.get("value_scale", 1.0) or 1.0),
                   attrs.get("__layer__") or "attn")


def rotate_qk(attrs, q, k, positions):
    """``q`` and ``k`` with the node's rotary embedding applied at
    ``positions`` ((t,) or (B, t)); as they are where the node has none."""
    rd = int(attrs.get("rotary_dim", 0) or 0)
    if not rd:
        return q, k
    heads = attrs.get("num_heads", 1)
    kv_heads = attrs.get("num_kv_heads", 0) or heads
    theta = float(attrs.get("rope_theta", 10000.0))
    layer = attrs.get("__layer__") or "attn"
    return (rope(q, positions, heads, rd, theta, layer=layer),
            rope(k, positions, kv_heads, rd, theta, layer=layer))


def _attn_shape(attrs, in_shapes, aux_shapes):
    q, k, v = in_shapes[:3]
    heads = attrs.get("num_heads", 1)
    kvh, _ = check_head_groups(heads, attrs.get("num_kv_heads", 0),
                               q[-1], v[-1], k[-1],
                               where="dot_product_attention")
    assert k[0] == v[0] and k[1] == v[1], "key/value (B, T) differ"
    # grouped K/V carry H_kv heads of width hdv each; the output is one
    # hdv-wide slice per Q head (v[-1] itself when H_kv == H)
    out = (q[0], q[1], heads * (v[-1] // kvh))
    sink = [(heads,)] if attrs.get("sink") else []
    return [tuple(q), tuple(k), tuple(v)] + sink, [out], []


def register_all():
    _register_latent()

    def _compute_full(attrs, inputs, aux, octx):
        q, k, v, *sink = inputs
        heads = attrs.get("num_heads", 1)
        kv_heads = attrs.get("num_kv_heads", 0) or heads
        causal = attrs.get("causal", False)
        scale = attrs.get("scale", 0.0) or None
        # malformed head configs (e % heads, heads % kv_heads, grouped
        # K/V width mismatch) raise HERE, before any dispatch — they used
        # to fall through silently until some downstream reshape tripped
        check_head_groups(heads, kv_heads, q.shape[2], v.shape[2],
                          k.shape[2], where="dot_product_attention")
        from .. import config as _config

        # a node that says more than "causal" (a window, a sink, a value
        # scale, values narrower than keys) is attended by sdpa: the ring
        # and the flash kernels know the causal mask only.  Rotary turns
        # q and k first, at the positions of a whole forward pass
        extra = node_extras(attrs, sink[0] if sink else None)
        plain = not extra and v.shape[2] == k.shape[2]
        q, k = rotate_qk(attrs, q, k, np.arange(q.shape[1]))
        spec = sparse_spec(attrs)
        if spec is not None:
            _note_path("einsum")
            return [sdpa_sparse(q, k, v, spec, num_heads=heads, scale=scale,
                                num_kv_heads=kv_heads,
                                layer=attrs.get("__layer__")
                                or "attn_sparse")], []
        if not plain:
            _note_path("einsum")
            return [sdpa(q, k, v, num_heads=heads, causal=causal,
                         scale=scale, num_kv_heads=kv_heads, **extra)], []

        # mesh path: with the time axis sharded on 'seq', run
        # explicit-collective ring attention INSIDE the executor program —
        # a shard_map region whose per-hop compute is the flash kernel on
        # TPU — instead of leaving the partitioner to all-gather K/V.
        # Ring attention is per-head independent, so Megatron head-group
        # sharding on 'model' composes with the K/V rotation on 'seq': the
        # in/out specs carry 'model' on the embed dim (an E-split IS a
        # head-group split — heads are contiguous hd-wide slices of E),
        # and each model shard rotates only its own K/V slice — the full
        # ring×TP (data, seq, model) composition, Module-reachable.
        if octx.mesh is not None and _config.get("MXNET_RING_ATTENTION"):
            mesh_axes = dict(octx.mesh.shape)
            b, tq, e = q.shape
            seq_par = mesh_axes.get("seq", 1)
            model_par = mesh_axes.get("model", 1)
            # malformed head configs already raised above (ValueError
            # naming the dims); what remains here are legitimate DEGRADE
            # conditions: heads % model (and kv_heads % model — a grouped
            # E-split is an H_kv-split on K/V) keep head groups whole per
            # model shard; indivisible configs degrade to the GSPMD
            # einsum, never to wrong numbers.
            if (seq_par > 1 and k.shape[1] == tq and v.shape[1] == tq
                    and heads % model_par == 0
                    and kv_heads % model_par == 0
                    and tq % seq_par == 0
                    and b % mesh_axes.get("data", 1) == 0):
                from jax.sharding import PartitionSpec as P

                from ..parallel.compat import shard_map
                from ..parallel.ring import ring_attention

                data_ax = "data" if mesh_axes.get("data", 1) > 1 else None
                model_ax = "model" if model_par > 1 else None
                spec = P(data_ax, "seq", model_ax)
                # schedule knob threaded explicitly so the trace bakes in
                # the CURRENT config value (the ring would otherwise read
                # it lazily at trace time — same value, but the dispatch
                # is where benchmarks A/B the schedules from)
                dbuf = _config.get("MXNET_RING_DOUBLE_BUFFER")
                ring = shard_map(
                    lambda q_, k_, v_: ring_attention(
                        q_, k_, v_, axis_name="seq", num_heads=heads,
                        causal=causal, scale=scale, head_axis=model_ax,
                        double_buffer=dbuf, num_kv_heads=kv_heads),
                    mesh=octx.mesh, in_specs=(spec,) * 3, out_specs=spec,
                    check_vma=False)
                _note_path("ring")
                return [ring(q, k, v)], []

        # single-chip fast path, training AND inference (the backward
        # kernels + custom_vjp make pallas differentiable), where the
        # call's own shape says the kernel wins (flash_selected)
        take, interpret = flash_selected(q.shape, k.shape, causal, heads,
                                         kv_heads, octx.mesh_active)
        if take:
            from . import pallas_attention as _pa

            _note_path("flash")
            return [_pa.sdpa_flash(q, k, v, heads, causal, scale,
                                   interpret=interpret,
                                   num_kv_heads=kv_heads)], []
        _note_path("einsum")
        return [sdpa(q, k, v, num_heads=heads, causal=causal,
                     scale=scale, num_kv_heads=kv_heads)], []

    register_op(OpDef(
        "dot_product_attention", _compute_full,
        schema=ParamSchema(
            Param("num_heads", int, default=1),
            Param("num_kv_heads", int, default=0,
                  doc="grouped-query attention: K/V head count "
                      "(must divide num_heads); 0 = num_heads (MHA)"),
            Param("causal", bool, default=False),
            Param("scale", float, default=0.0,
                  doc="0 = 1/sqrt(head_dim)"),
            Param("window", int, default=0,
                  doc="sliding window: a query sees its own position and "
                      "the window - 1 before it; 0 = none"),
            Param("sink", bool, default=False,
                  doc="a fourth input (H,): one learned logit a head in "
                      "the softmax's denominator, with no value"),
            Param("rotary_dim", int, default=0,
                  doc="rotary embedding on the first rotary_dim dims of "
                      "each q and k head (half-split pairing), applied "
                      "inside the node at the positions it is run at; "
                      "0 = none"),
            Param("rope_theta", float, default=10000.0),
            Param("value_scale", float, default=1.0,
                  doc="the output is multiplied by this"),
            Param("sparse_topk", int, default=0,
                  doc="sparse selection (SparseSpec): past "
                      "sparse_dense_len positions of context a query "
                      "attends this many blocks a KV group, chosen from an "
                      "index of compressed keys; 0 = attend everything"),
            Param("sparse_block", int, default=64,
                  doc="positions a selectable block"),
            Param("sparse_kernel", int, default=32,
                  doc="positions a compressed key averages"),
            Param("sparse_stride", int, default=16,
                  doc="positions between two compressed keys (paged: the "
                      "page size)"),
            Param("sparse_init_blocks", int, default=1,
                  doc="leading blocks always attended"),
            Param("sparse_window", int, default=2048,
                  doc="trailing positions always attended, in whole blocks"),
            Param("sparse_dense_len", int, default=8192,
                  doc="contexts up to this long are attended whole"),
        ),
        num_inputs=lambda a: 4 if a.get("sink") else 3,
        arguments=lambda a: ["query", "key", "value"]
        + (["sink"] if a.get("sink") else []),
        infer_shape=_attn_shape, needs_train=True,
        doc="Multi-head scaled-dot-product attention over projected "
            "(B, T, E) inputs.  Leapfrog op: no reference analog "
            "(SURVEY §2.5 row 'Sequence-length scaling'); sequence "
            "parallelism arrives via GSPMD seq-axis sharding or "
            "parallel.ring.ring_attention."),
        aliases=("_contrib_DotProductAttention",))
