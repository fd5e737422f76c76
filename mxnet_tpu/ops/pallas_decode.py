"""The decode row's kernel over paged KV pools (TPU): a slot's live blocks
are read from the pools once, inside the kernel that multiplies them.

``ops.attention._attend_live_blocks`` attends a view block by block, only
the blocks a slot's length has reached, and combines a slot's blocks by one
log-sum-exp.  Its loop gathers a block's pages into a copy and takes the two
products over the copy: every live byte is read, written and read again.
:func:`attend_blocks` is that loop as ONE ``pallas_call`` that walks the
live rows of the padded list of blocks, a prefix of it.  The list's page ids
ride in as scalar prefetch; the pools stay in HBM as they lie and a step
copies its block's pages — whole pages, every head — and their scale rows
into fast memory itself, the next block's copies in flight while this
block's arithmetic runs.  No gathered view is written.  (A page's scale row
is copied with the seven rows that share its ``(8, 128)`` tile: Mosaic cuts
a tiled plane at whole tiles.  A plane stored a page a TILE would be read
once too; that is the pools' storage, not this kernel's.)

The arithmetic is ``_sdpa_cache``'s row form in another order.  A narrow
plane (int8, fp8, bfloat16) is exact in bfloat16; the float operand (the
query row, then the probabilities) goes as its three bfloat16 pieces,
stacked as ROWS of one product.  Accumulation is float32; the key scales
multiply the float32 logits and the value scales the probabilities.  A
float32 pool takes the same two products in float32 at
``Precision.HIGHEST``.  In a page's scale row a token's floats lie ``(K | V,
head)``; a select and a lane fold put them ``(block, 2 * H_kv)`` and one
transposition turns positions onto the lanes.

Which rows meet which columns follows from ``H / H_kv`` and the head widths
(``Tiles.group``, the KV heads a product spans; ``Tiles.body``):

* ``H = H_kv`` (``"whole"``): the query rows of all heads are laid
  block-diagonally over the KV heads, ``(3 * H, E_k) x (block, E_k)^T`` gives
  the logits of every head with positions on the lanes, ``(3 * H, block) x
  (block, E_v)`` the values, and the diagonal blocks of that result are
  folded out in fast memory.  A head's row against its own head's columns is
  all there is to do, and one product does it.
* ``H > H_kv`` (``"grouped"``): that layout would take every row against
  every KV head's columns, ``H_kv`` times the arithmetic and all but one
  part of it against zeros (64 heads over 8: ``(192, 1024) x (1024, 512)``
  a block where ``8 x (24, 128) x (128, 512)`` is the work).  A step takes
  its two products a GROUP of KV heads at a time, with the query rows of
  that group's heads only: the group is the fewest KV heads whose key
  columns and whose value columns are whole lane tiles (1 at heads of 128,
  2 at keys of 192: a product reads its columns out of the block at lane
  tiles), block-diagonal inside the group where it holds more than one.  A
  group's rows are its ``group * H / H_kv`` heads a piece, every piece from
  a float32 sublane tile on (``Tiles.stride``; the pieces' sums are cut out
  of the float32 product at whole tiles), ``Tiles.prows`` rows a product
  (64 over 8: 3 x 8 -> 32; 20 over 4: 3 x 8 -> 32; 64 over 4 with keys of
  192: 3 x 32 = 96).  A group's scales are its own rows of the turned tile,
  broadcast over its query rows.  The shares leave group by group, a
  group's heads from a multiple of ``stride`` on, and :func:`attend_blocks`
  closes the gaps.  Where the groups' rows would not fit the one lane tile
  of heads the shares leave in, the one product stays.

What a step writes is the block's share of the softmax, ``(max, sum, acc)``
not yet normalized, as the walk's loop kept it: the combine, the sink and
the value scale stay with the caller.  A dead row of the padded list is not
visited: it copies nothing and writes nothing, and the combine reads none.

:func:`tiles` is the shape rule: tile sizes follow from ``E``, ``H``,
``H_kv`` and the block; ``ops.attention.decode_kernel_selected`` adds what
the call shows (one query row, a live-block plan, a backend that runs
Pallas).  ``interpret=True`` runs the same kernel on the CPU
(tests/test_pallas_decode.py).

A second kernel, :func:`attend_latent_blocks`, does the same for the absorbed
decode row of latent attention (``ops.attention.latent_attend``): ONE headless
plane of ``rank + rope`` values a position, stored a page eight rows of two
positions (:func:`latent_plane_shape`), multiplied as it lies against query
rows laid block-diagonally over a row's positions.  Its shape rule is
:func:`latent_tiles`, its step :data:`LATENT_STEP_TOKENS`, its chooser
``ops.attention.latent_kernel_selected``; it shares the list and the combine
with the kernel above and none of its body (the section's header below says
why the plane is stored so, and in which order a block's positions lie inside
the kernel).

Two more serve a prefill chunk, ONE slot's many query rows, each under a
section header of its own below: :func:`attend_chunk_blocks` over pools whose
pages ARE keys and values (it copies them), and :func:`attend_latent_segment`
over a latent plane's rows once a segment of them has been multiplied into
keys and values (ordinary blocks; the step of the walk by segments,
``ops.attention._attend_latent_segments``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

from ..obs.startup import pallas as _pallas

LANES = 128
# what a step's buffers and temporaries may take of fast memory (a v5e
# core has 128 MiB; Mosaic's default scoped limit is 16)
_VMEM_BUDGET = 40 << 20


def _is_quant(pool):
    from .attention import QuantKV

    return isinstance(pool, QuantKV)


def _plane(pool):
    return pool.data if _is_quant(pool) else pool


class Tiles(NamedTuple):
    """The static sizes of one :func:`attend_blocks` call."""

    heads: int       # H
    kv_heads: int    # H_kv
    rows: int        # rows of a block's shares: H rounded up to the bfloat16
                     # sublane tile; grouped, ``stride`` rows a group
    pieces: int      # bfloat16 pieces of the float operand (1: float32)
    hd: int          # key head width
    hdv: int         # value head width
    qw: int          # lanes of a query row as handed in: lcm(hd, 128);
                     # grouped, a group's key columns, group * hd
    ow: int          # lanes of the folded PV result: max(hdv, 128)
    pt: int          # positions a page
    ppb: int         # pages a block
    quant: bool
    vmem: int        # bytes of fast memory a step may take
    group: int       # KV heads a product: H_kv where one product spans all

    @property
    def body(self):
        """``"grouped"``: a step takes its two products a group of KV heads,
        with that group's query rows; ``"whole"``: one product, block-
        diagonal over every KV head (all there is to do where H = H_kv)."""
        return "grouped" if self.group < self.kv_heads else "whole"

    @property
    def gheads(self):
        """Query heads a product: those of its ``group`` KV heads."""
        return self.group * self.heads // self.kv_heads

    @property
    def stride(self):
        """Grouped: rows a piece of a group's float operand takes
        (:func:`_group_rows`)."""
        return _group_rows(self.gheads, self.pieces)[0]

    @property
    def prows(self):
        """Grouped: rows a product (:func:`_group_rows`)."""
        return _group_rows(self.gheads, self.pieces)[1]

    def attend(self, q, k_pool, v_pool, pages, slot, valid, live, scale,
               interpret=False):
        """The listed blocks' shares by this shape's kernel: what
        ``ops.attention._attend_live_blocks`` calls, whichever kernel the
        call's rule chose."""
        return attend_blocks(q, k_pool, v_pool, pages, slot, valid, live,
                             self, scale, interpret=interpret)


def tiles(q_shape, k_pool, v_pool, num_heads, num_kv_heads, block):
    """The :class:`Tiles` of a decode row over these pools by blocks of
    ``block`` positions, or None where the kernel does not tile the
    shapes: the walk then serves them."""
    import jax.numpy as jnp

    kd, vd = _plane(k_pool), _plane(v_pool)
    quant = _is_quant(k_pool)
    if quant != _is_quant(v_pool) or kd.dtype != vd.dtype \
            or kd.ndim != 3 or vd.shape[:2] != kd.shape[:2]:
        return None
    e = q_shape[2]
    h = int(num_heads)
    kvh = int(num_kv_heads) or h
    pt, ek, ev = kd.shape[1], kd.shape[2], vd.shape[2]
    if h <= 0 or kvh <= 0 or h % kvh or e % h or ev % kvh \
            or ek != kvh * (e // h) or block % pt or h > LANES:
        return None
    hd, hdv = e // h, ev // kvh
    qw = hd * LANES // math.gcd(hd, LANES)
    # whole pages of whole sublane tiles; planes of whole lane tiles; a
    # query row tiled to whole lane tiles; value heads that fold into one
    if pt % 8 or ek % qw or ev % LANES \
            or (hdv % LANES and LANES % hdv) or hdv < 8:
        return None
    if quant:
        # a page's scale row: whole lane tiles, a token's stretch (K's
        # H_kv floats, V's, and what ops.attention.scale_group pads them
        # with) dividing one
        w = k_pool.scale.shape[1] // pt
        if k_pool.scale.shape[1] != pt * w or w < 2 * kvh \
                or (pt * w) % LANES or LANES % w:
            return None
    item = jnp.dtype(kd.dtype).itemsize
    rows = -(-h // 16) * 16
    pieces = 1 if item > 2 else 3       # a float32 pool: one piece
    group = _kv_heads_a_product(h, kvh, hd, hdv)
    kw, vw, prods = ek, ev, pieces * rows
    if group < kvh:
        # a product a group: that group's columns, its heads' rows
        stride, prows = _group_rows(group * (h // kvh), pieces)
        rows, qw = kvh // group * stride, group * hd
        kw, vw, prods = qw, group * hdv, kvh // group * prows
    # two buffers a plane, and the planes once more as the products read
    # them (float32 on the way to bfloat16)
    vmem = block * (ek + ev) * (2 * item + 6) \
        + (block // pt * (2 * 8 + pt) * pt * w * 4 if quant else 0) \
        + prods * (kw + vw + 4 * block) * 4 \
        + q_shape[0] * prods * qw * 4
    if vmem > _VMEM_BUDGET:
        return None
    return Tiles(h, kvh, rows, pieces, hd, hdv, qw, max(hdv, LANES), pt,
                 block // pt, quant, vmem, group)


def _kv_heads_a_product(h, kvh, hd, hdv):
    """KV heads a product of the kernel spans.  Where H = H_kv every one: a
    head's row against its own head's columns is the block-diagonal product
    whole.  Where several query heads share a KV head, the fewest whose key
    columns and whose value columns are whole lane tiles (a product reads its
    columns out of the block at lane tiles), if the groups' rows, each
    group's rounded up to a sublane tile, still fit the shares' one lane
    tile of heads; else every one."""
    g = h // kvh
    if g > 1:
        for n in range(1, kvh):
            if kvh % n == 0 and n * hd % LANES == 0 and n * hdv % LANES == 0 \
                    and kvh // n * _group_rows(n * g, 1)[0] <= LANES:
                return n
    return kvh


def _group_rows(heads, pieces):
    """``(stride, rows)`` of a group's product over ``heads`` query heads:
    a piece of the float operand takes ``stride`` rows, the heads rounded up
    to the float32 sublane tile (the pieces' sums are cut out of the float32
    product at whole tiles), and the product ``pieces * stride`` rows
    rounded up to the operand's sublane tile."""
    stride = -(-heads // 8) * 8
    tile = 16 if pieces == 3 else 8
    return stride, -(-pieces * stride // tile) * tile


def _split3(x):
    """float32 -> its three bfloat16 pieces, largest first: their sum is
    ``x`` to the last bit float32 holds."""
    import jax.numpy as jnp

    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _div(x, n):
    """``x // n`` and ``x % n`` (:func:`_rem`) of non-negative int32 by a
    Python int: the truncating forms, without ``//``'s sign fix-ups."""
    import jax
    import jax.numpy as jnp

    return jax.lax.div(x, jnp.int32(n))


def _rem(x, n):
    import jax
    import jax.numpy as jnp

    return jax.lax.rem(x, jnp.int32(n))


def _fold_lanes(x, width):
    """(n, 128) whose lanes hold one nonzero group of ``width``: every
    group becomes the sum of all (the nonzero one)."""
    pltpu = _pallas()[1]

    shift = LANES // 2
    while shift >= width:
        x = x + pltpu.roll(x, shift, 1)
        shift //= 2
    return x


def _kernel(pages_ref, slot_ref, valid_ref, live_ref, q_ref, k_hbm, v_hbm,
            *rest, t, scale):
    """One invocation walks the live rows of the list, a prefix of it:
    ``pages_ref`` (rows * ppb,) the blocks' page ids, ``slot_ref`` and
    ``valid_ref`` (rows,) each block's slot and the positions of the block
    that slot has reached, ``live_ref`` (1,) the live rows.  ``q_ref`` (B,
    pieces * rows, qw): a slot's query row a head, by pieces, tiled to
    whole lane tiles; grouped, (B, groups, prows, qw): :func:`_grouped_rows`.
    The pools and the two outputs stay in HBM; row
    ``r``'s copies fly while row ``r - 1`` is multiplied, and its share
    leaves while row ``r + 1`` is."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    if t.quant:
        s_hbm, acc_hbm, stat_hbm, kbuf, vbuf, sbuf, accbuf, statbuf, sems, \
            osems = rest
    else:
        acc_hbm, stat_hbm, kbuf, vbuf, accbuf, statbuf, sems, osems = rest
        s_hbm = sbuf = None
    live = live_ref[0]
    block = t.ppb * t.pt
    g = t.heads // t.kv_heads
    fill = jnp.finfo(jnp.float32).min
    narrow = t.pieces == 3
    mm = jnp.bfloat16 if narrow else jnp.float32
    prec = None if narrow else jax.lax.Precision.HIGHEST
    rows3 = t.pieces * t.rows
    ek = kbuf.shape[-1]

    def pages_of(row, buf, go, unrolled=False):
        """``go`` (start or wait) each copy of row ``row``'s pages into
        buffer ``buf``.  A loop where it may be: a descriptor costs
        milliseconds to trace and to lower, in every session's set-up, and
        a kernel that unrolled all three of its uses took 5 s of a 24-s
        one.  ``unrolled`` for the starts that run beside the arithmetic:
        issued by a loop they delay it (2 % of the OPT cell's tokens/s;
        my chip runs, PR 48)."""
        def page(i, _):
            at = pages_ref[row * t.ppb + i]
            go(pltpu.make_async_copy(k_hbm.at[at], kbuf.at[buf, i],
                                     sems.at[buf, 0]))
            go(pltpu.make_async_copy(v_hbm.at[at], vbuf.at[buf, i],
                                     sems.at[buf, 1]))
            if t.quant:
                # a page's row with the seven that share its sublane
                # tile: a tiled plane is cut at whole tiles
                go(pltpu.make_async_copy(
                    s_hbm.at[pl.ds(pl.multiple_of(_div(at, 8) * 8, 8), 8)],
                    sbuf.at[buf, i], sems.at[buf, 2]))

        if unrolled:
            for i in range(t.ppb):
                page(i, None)
        else:
            jax.lax.fori_loop(0, t.ppb, page, None)

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    def shares(row, buf):
        return (pltpu.make_async_copy(accbuf.at[buf], acc_hbm.at[row],
                                      osems.at[buf, 0]),
                pltpu.make_async_copy(statbuf.at[buf], stat_hbm.at[row],
                                      osems.at[buf, 1]))

    def plane(ref, buf, lo=0, width=None):
        x = ref[buf] if width is None \
            else ref[buf, :, :, lo:lo + width]  # (ppb, pt, E)
        if x.dtype != mm:
            x = x.astype(jnp.float32)
        return x.reshape(block, x.shape[-1]).astype(mm)

    def turned_scales(r, buf):
        """(ppb, pt * W) -> (block, W) -> (W, block): a page's row to each
        of its tokens, a token's own stretch kept, the stretches folded onto
        one lane tile, positions turned onto the lanes: K's heads' scales
        in rows ``[0, H_kv)``, V's in ``[H_kv, 2 * H_kv)``."""
        width = sbuf.shape[-1]
        w = width // t.pt
        per = jnp.concatenate(
            [jnp.broadcast_to(
                sbuf[buf, i, pl.ds(_rem(pages_ref[r * t.ppb + i], 8), 1), :],
                (t.pt, width)) for i in range(t.ppb)], axis=0)
        tok = _rem(jax.lax.broadcasted_iota(
            jnp.int32, (block, width), 0), t.pt)
        at = _div(jax.lax.broadcasted_iota(
            jnp.int32, (block, width), 1), w)
        own = jnp.where(tok == at, per, 0.0)
        one = own[:, :LANES]
        for c in range(1, width // LANES):
            one = one + own[:, c * LANES:(c + 1) * LANES]
        return _fold_lanes(one, w).T                # (128, block)

    def folded(out):
        """(rows, width) values of a product over KV heads side by side,
        head ``n`` of the rows' reading kv-head ``n // g``'s columns ->
        (rows, ow): each row's own head's columns, the rest masked out and
        the heads folded onto one."""
        of = _div(jax.lax.broadcasted_iota(jnp.int32, out.shape, 0), g)
        col = _div(jax.lax.broadcasted_iota(jnp.int32, out.shape, 1), t.hdv)
        out = jnp.where(of == col, out, 0.0)
        acc = out[:, :t.ow]
        for c in range(1, out.shape[1] // t.ow):
            acc = acc + out[:, c * t.ow:(c + 1) * t.ow]
        if t.hdv < LANES:
            acc = _fold_lanes(acc, t.hdv)
        return acc

    def share_stats(m, den):
        """The maxima and the sums, a head a lane once turned: columns 0
        and 1 of a lane tile."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (m.shape[0], LANES), 1)
        return jnp.where(lane == 0, m, jnp.where(lane == 1, den, 0.0))

    if t.body == "whole":
        # which kv-head a lane of the query's lane tile lies in, which a
        # row's head reads: the same for every row of the list
        q_head = _div(_rem(jax.lax.broadcasted_iota(
            jnp.int32, (rows3, t.qw), 0), t.rows), g)
        q_lane = _div(jax.lax.broadcasted_iota(jnp.int32, (rows3, t.qw), 1),
                      t.hd)

    def whole(r, buf):
        """Row ``r``'s shares by ONE product a plane, the query rows
        block-diagonal over every KV head: ``(acc (rows, ow), stats (rows,
        128))``."""
        # the query rows, block-diagonal over kv-heads: a lane tile at a
        # time, head n's row kept in the columns of kv-head n // g
        qq = q_ref[slot_ref[r]]
        qbd = jnp.concatenate(
            [jnp.where(q_lane + c * (t.qw // t.hd) == q_head, qq,
                       jnp.zeros_like(qq))
             for c in range(ek // t.qw)], axis=1)
        s = jax.lax.dot_general(
            qbd, plane(kbuf, buf), (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)     # (pieces * rows, block)
        logits = s[:t.rows]
        for i in range(1, t.pieces):
            logits = logits + s[i * t.rows:(i + 1) * t.rows]
        logits = logits * jnp.float32(scale)

        vs = None
        if t.quant:
            turned = turned_scales(r, buf)
            if g == 1 and t.kv_heads == t.rows:
                ks, vs = turned[:t.rows], turned[t.rows:2 * t.rows]
            else:
                of = _div(jax.lax.broadcasted_iota(
                    jnp.int32, (t.rows, block), 0), g)
                ks = jnp.zeros((t.rows, block), jnp.float32)
                vs = jnp.zeros((t.rows, block), jnp.float32)
                for j in range(t.kv_heads):
                    ks = jnp.where(of == j, turned[j:j + 1], ks)
                    vs = jnp.where(
                        of == j,
                        turned[t.kv_heads + j:t.kv_heads + j + 1], vs)
            logits = logits * ks

        pos = jax.lax.broadcasted_iota(jnp.int32, (t.rows, block), 1)
        logits = jnp.where(pos < valid_ref[r], logits, fill)
        m = jnp.max(logits, axis=1, keepdims=True)
        p = jnp.exp(logits - m)
        den = jnp.sum(p, axis=1, keepdims=True)
        if vs is not None:
            p = p * vs
        p3 = jnp.concatenate(_split3(p), axis=0) if narrow else p
        full = jax.lax.dot_general(
            p3, plane(vbuf, buf), (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)     # (pieces * rows, E_v)
        out = full[:t.rows]
        for i in range(1, t.pieces):
            out = out + full[i * t.rows:(i + 1) * t.rows]
        # head n's values are the columns of kv-head n // g
        return folded(out), share_stats(m, den)

    def grouped(r, buf):
        """Row ``r``'s shares, the products a group of ``t.group`` KV heads
        at a time: the group's query rows (``q_ref[slot, j]``: its heads a
        piece, the pieces ``t.stride`` rows apart, block-diagonal inside the
        group where it holds more than one KV head) against the group's key
        columns, the probabilities against its value columns.  The groups'
        products stand side by side in the program and ONE softmax runs
        between them over every group's rows: a chain a group (product,
        reduction, exponential, product) is a group's latency eight times
        over.  -> ``(acc (rows, ow), stats (rows, 128))``, group ``j``'s
        heads from row ``j * t.stride`` on."""
        n, stride = t.group, t.stride
        groups = range(t.kv_heads // n)
        spare = t.prows - t.pieces * stride
        kw, vw = n * t.hd, n * t.hdv
        qs = q_ref.at[slot_ref[r]]
        turned = turned_scales(r, buf) if t.quant else None

        def scales(first):
            """A group's rows of ``turned`` from ``first`` on, each over the
            query rows of its KV head."""
            rows = turned[first:first + 1]
            if n > 1:
                of = _div(jax.lax.broadcasted_iota(
                    jnp.int32, (stride, block), 0), g)
            for i in range(1, n):
                rows = jnp.where(of == i, turned[first + i:first + i + 1],
                                 rows)
            return rows

        def pieces_summed(x):
            out = x[:stride]
            for i in range(1, t.pieces):
                out = out + x[i * stride:(i + 1) * stride]
            return out

        def of_group(x, j):
            return x[j * stride:(j + 1) * stride]

        logits = [pieces_summed(jax.lax.dot_general(
            qs[j], plane(kbuf, buf, j * kw, kw), (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32))
            for j in groups]                            # (stride, block) each
        if t.quant:
            logits = [x * scales(j * n) for j, x in zip(groups, logits)]
        logits = jnp.concatenate(logits, axis=0) * jnp.float32(scale)
        pos = jax.lax.broadcasted_iota(jnp.int32, (t.rows, block), 1)
        logits = jnp.where(pos < valid_ref[r], logits, fill)
        m = jnp.max(logits, axis=1, keepdims=True)
        p = jnp.exp(logits - m)
        den = jnp.sum(p, axis=1, keepdims=True)
        if t.quant:
            p = jnp.concatenate(
                [of_group(p, j) * scales(t.kv_heads + j * n) for j in groups],
                axis=0)
        # a group's pieces a sublane tile of float32 apart, as its query's
        # are: a bfloat16 piece is itself in float32 and back
        pieces = [x.astype(jnp.float32) for x in _split3(p)] if narrow \
            else [p]
        zeros = [jnp.zeros((spare, block), jnp.float32)] * (spare > 0)
        accs = []
        for j in groups:
            full = jax.lax.dot_general(
                jnp.concatenate([of_group(x, j) for x in pieces] + zeros,
                                axis=0).astype(mm),
                plane(vbuf, buf, j * vw, vw), (((1,), (0,)), ((), ())),
                precision=prec,
                preferred_element_type=jnp.float32)     # (prows, vw)
            out = pieces_summed(full)
            accs.append(folded(out) if n > 1 else out)
        return jnp.concatenate(accs, axis=0), share_stats(m, den)

    def attend(r, _):
        buf = _rem(r, 2)

        @pl.when(r + 1 < live)
        def _next():
            pages_of(r + 1, 1 - buf, start, unrolled=True)

        pages_of(r, buf, wait)
        acc, stats = (whole if t.body == "whole" else grouped)(r, buf)
        stats = jnp.concatenate(
            [stats, jnp.zeros((LANES - t.rows, LANES), jnp.float32)], axis=0)

        @pl.when(r >= 2)
        def _sent():                    # row r - 2 has left these buffers
            for c in shares(r, buf):
                c.wait()

        accbuf[buf] = acc
        statbuf[buf] = stats.T[:8]
        for c in shares(r, buf):
            c.start()

    @pl.when(live > 0)
    def _first():
        pages_of(0, 0, start)

    jax.lax.fori_loop(0, live, attend, None)
    for back in (1, 2):
        @pl.when(live >= back)
        def _drain():
            for c in shares(0, _rem(live - back, 2)):
                c.wait()


def attend_blocks(q, k_pool, v_pool, pages, slot, valid, live, t, scale,
                  interpret=False):
    """The listed blocks' shares of each slot's softmax: ``(m (rows, H),
    den (rows, H), acc (rows, H, hdv))`` float32, block ``r`` of the list
    at row ``r``.  Dead rows are not visited and not written: what they
    hold is to be read by nobody.

    ``q`` (B, 1, E); the pools as the paged ops store them; ``pages``
    (rows, ppb) page ids, ``slot`` (rows,) the slot of each block,
    ``valid`` (rows,) how many of a block's positions its slot has
    reached, ``live`` the number of live rows, a prefix of the list;
    ``t`` the call's :class:`Tiles`.

    The kernel's launch is traced as ONE function a (shapes, ``t``,
    ``scale``): the layers of a decode program share a kernel shape, and a
    program of 24 nodes traces and lowers the kernel once, not 24 times
    (0.6 s a node on the host, in every session's set-up, warm or cold).
    The query is cut into its pieces out here, so that a first layer's
    bfloat16 row and a later layer's float32 one are one shape to it."""
    import jax.numpy as jnp

    b = q.shape[0]
    qh = q.astype(jnp.float32).reshape(b, t.heads, t.hd)
    if t.body == "grouped":
        qh = _grouped_rows(qh, t)           # (B, groups, prows, qw)
    else:
        qh = jnp.pad(qh, ((0, 0), (0, t.rows - t.heads), (0, 0)))
        if t.pieces == 3:
            qh = jnp.concatenate(_split3(qh), axis=1)
        qh = jnp.tile(qh, (1, 1, t.qw // t.hd))    # (B, pieces * rows, qw)
    return _jitted()(qh, k_pool, v_pool, pages, slot, valid, live, t=t,
                     scale=float(scale), interpret=bool(interpret))


def _grouped_rows(qh, t):
    """(B, H, hd) float32 query rows -> (B, H_kv / group, prows, group *
    hd): a group's heads a piece, the pieces ``t.stride`` rows apart, a
    head's row in the columns of its own KV head of the group and zeros in
    the others'."""
    import jax.numpy as jnp

    b, n = qh.shape[0], t.group
    groups, ng = t.kv_heads // n, t.gheads
    qh = jnp.pad(qh.reshape(b, groups, ng, t.hd),
                 ((0, 0), (0, 0), (0, t.stride - ng), (0, 0)))
    if t.pieces == 3:
        qh = jnp.concatenate(_split3(qh), axis=2)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, t.prows - qh.shape[2]), (0, 0)))
    if n > 1:
        of = jnp.arange(t.prows) % t.stride // (ng // n)
        qh = jnp.where((of[:, None] == jnp.arange(n)[None, :])[:, :, None],
                       qh[:, :, :, None, :], jnp.zeros((), qh.dtype))
    return qh.reshape(b, groups, t.prows, t.qw)


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax

    return jax.jit(_attend_blocks, static_argnames=("t", "scale",
                                                    "interpret"))


def _attend_blocks(qh, k_pool, v_pool, pages, slot, valid, live, *, t, scale,
                   interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    rows = pages.shape[0]
    kd, vd = _plane(k_pool), _plane(v_pool)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec(memory_space=pltpu.VMEM), hbm, hbm]
    args = [qh, kd, vd]
    scratch = [pltpu.VMEM((2, t.ppb) + kd.shape[1:], kd.dtype),
               pltpu.VMEM((2, t.ppb) + vd.shape[1:], vd.dtype)]
    if t.quant:
        in_specs.append(hbm)
        plane = k_pool.scale
        if interpret and plane.shape[0] % 8:
            # a step copies the whole sublane tile a page's row lies in.
            # The chip's plane is stored by whole tiles; the interpreter's
            # ends at its last row
            plane = jnp.pad(plane, ((0, -plane.shape[0] % 8), (0, 0)))
        args.append(plane)
        scratch.append(pltpu.VMEM((2, t.ppb, 8, k_pool.scale.shape[1]),
                                  jnp.float32))
    scratch += [pltpu.VMEM((2, t.rows, t.ow), jnp.float32),
                pltpu.VMEM((2, 8, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 3)),
                pltpu.SemaphoreType.DMA((2, 2))]
    # Mosaic has no 64-bit integers: the kernel is traced with 32-bit
    # defaults whatever ``jax_enable_x64`` says (the tests set it)
    with jax.enable_x64(False):
        acc, stats = pl.pallas_call(
            functools.partial(_kernel, t=t, scale=float(scale)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(1,),
                in_specs=in_specs,
                out_specs=[hbm, hbm],
                scratch_shapes=scratch),
            out_shape=[jax.ShapeDtypeStruct((rows, t.rows, t.ow), jnp.float32),
                       jax.ShapeDtypeStruct((rows, 8, LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 2 * t.vmem)))),
            name="decode_live_blocks",
            interpret=interpret,
        )(pages.reshape(-1).astype(jnp.int32), slot.astype(jnp.int32),
          valid.astype(jnp.int32), jnp.reshape(live, (1,)).astype(jnp.int32),
          *args)
    if t.body == "grouped" and t.gheads < t.stride:
        # a group's heads lie from a multiple of ``stride`` on
        at = np.arange(t.rows).reshape(-1, t.stride)[:, :t.gheads].reshape(-1)
        stats, acc = stats[:, :, at], acc[:, at]
    return (stats[:, 0, :t.heads], stats[:, 1, :t.heads],
            acc[:, :t.heads, :t.hdv])


def block_bytes(t, k_pool, v_pool):
    """Bytes one live block of the list is in the pools: what a step must
    read (``benchmarks/bench_decode_kernel.py`` prices a call by it)."""
    kd, vd = _plane(k_pool), _plane(v_pool)
    per = (kd.shape[2] + vd.shape[2]) * np.dtype(kd.dtype).itemsize
    if t.quant:
        per += 2 * t.kv_heads * 4
    return t.ppb * t.pt * per


# ---------------------------------------------------------------------------
# The absorbed decode row of latent attention (``ops.attention.latent_attend``
# with one query row a slot): ONE headless plane of ``rank + rope`` values a
# position, read as the keys whole and as the values by its first ``rank``.
# A second kernel with a shape rule of its own: it shares the list of live
# blocks before it and the combine after it with :func:`attend_blocks`, and
# none of :func:`_kernel`'s body (no scale rows, no KV heads to keep apart,
# one plane for both products).
#
# The plane is stored ``(P, page_tokens / k, k * width)``: a page's positions
# ``k`` a row, the fewest that make a row whole lane tiles (2 at a width of
# 320: a row holds positions ``2 j`` and ``2 j + 1`` side by side, 640 lanes),
# the rows of a page one sublane tile or more, so that a page is whole tiles
# of HBM, contiguous, and one copy brings it.  (A plane stored a page a row,
# ``(P, page_tokens * width)``, cannot be read a page at a time: its tiles
# hold eight pages' rows each, two rows interleaved word by word, and Mosaic
# cuts a tiled plane at whole tiles.)  The copied block ``(block / k, k *
# width)`` is multiplied as it lies: the query rows are laid block-diagonally
# over a row's ``k`` positions, ``(k * H, k * width)``, so one product gives
# the logits of position ``k j + i`` of the block in row group ``i`` and
# column ``j``; the probabilities go back the same way, ``(k * H, block / k)
# x (block / k, k * width)``, and the diagonal blocks' first ``rank`` columns
# are summed.  Inside the kernel a block's positions so lie ``(position in
# its row, row)``; the length mask follows the same order, and the softmax
# does not care.  Nothing is re-laid out, in HBM or in fast memory.
# ---------------------------------------------------------------------------

# Positions a step of the latent kernel, by the view's capacity (the widest
# capacity listed that the view reaches), as LIVE_BLOCK_TOKENS is the walk's.
# Measured kernel alone on the chip (TPU v5 lite, jax 0.9.0, the one cell's
# shapes: 20 slots of 66,560 positions, 32 heads over 256 + 64 values in
# bfloat16; benchmarks/probe_latent_decode.py, PR 51; device ms a call with
# every slot at 16k / 32k / 64k positions, the walk over the same plane
# first):
#   1.39 / 2.42 / 4.48 -> step 512 1.18 / 2.12 / 3.96, 1024 0.90 / 1.64 /
#   3.12, 2048 0.78 / 1.43 / 2.76
# A block is loaded into the matrix unit as the stationary operand of both
# products whatever the step (the queries are 64 rows), so a step's fixed
# cost (its shares out, its copies' waits, the combine's rows) is what a
# longer step saves; a slot's last step is half idle on average, 3 % of the
# cell's mean context at 2048.  Views under 8192 positions keep 512.
LATENT_STEP_TOKENS = {0: 512, 8192: 2048}


class LatentTiles(NamedTuple):
    """The static sizes of one :func:`attend_latent_blocks` call."""

    heads: int       # H
    rows: int        # H rounded up to the float32 sublane tile
    width: int       # values a position: rank + rope
    rank: int        # the first of them are the values
    per: int         # k: positions a row of a page
    pr: int          # rows a page
    ppb: int         # pages a block
    exact: bool      # a float32 plane: products at Precision.HIGHEST
    vmem: int        # bytes of fast memory a step may take

    @property
    def block(self):
        """Positions a step: what the list of live blocks is cut by."""
        return self.ppb * self.pr * self.per

    def attend(self, q, plane, _values, pages, slot, valid, live, scale,
               interpret=False):
        """As ``Tiles.attend``: the plane is keys and values both."""
        return attend_latent_blocks(q, plane, pages, slot, valid, live, self,
                                    scale, interpret=interpret)


def latent_plane_shape(pages, page_tokens, width):
    """The shape a latent plane of ``pages`` pages is stored in: ``(P,
    page_tokens / k, k * width)`` where some ``k`` positions a row make rows
    of whole lane tiles and pages of whole sublane tiles (what
    :func:`attend_latent_blocks` copies a page at a time), else a page a
    row, ``(P, page_tokens * width)``."""
    k = LANES // math.gcd(width, LANES)
    if page_tokens % k == 0 and (page_tokens // k) % 8 == 0:
        return (pages, page_tokens // k, k * width)
    return (pages, page_tokens * width)


def latent_tiles(q_shape, plane, table_shape, heads, rank, width):
    """The :class:`LatentTiles` of an absorbed decode row over this plane
    and table, or None where the kernel does not tile the shapes: the walk
    then serves them."""
    import jax.numpy as jnp

    if plane.ndim != 3 or q_shape[2] != heads * width:
        return None
    pr, lanes = plane.shape[1], plane.shape[2]
    per = lanes // width
    item = jnp.dtype(plane.dtype).itemsize
    # pages of whole tiles as the plane's type packs them in HBM (8 rows of
    # 128 lanes), values of whole lane tiles, a head a lane of the shares'
    # tile
    if lanes != per * width or lanes % LANES or pr % 8 or rank % LANES \
            or rank > width or item not in (2, 4) or heads > LANES:
        return None
    pt = pr * per
    cap = table_shape[1] * pt
    step = LATENT_STEP_TOKENS[max(c for c in LATENT_STEP_TOKENS if c <= cap)]
    if step % pt or cap <= step:
        return None
    rows = -(-heads // 8) * 8
    # two buffers of a block, the block once more as the products read it,
    # the logits and the probabilities, the folded result
    vmem = step * width * (2 * item + 4) \
        + per * rows * (3 * step // per + 2 * lanes) * 4 \
        + q_shape[0] * per * rows * lanes * item
    if vmem > _VMEM_BUDGET:
        return None
    return LatentTiles(heads, rows, width, rank, per, pr, step // pt,
                       item == 4, vmem)


def _latent_kernel(pages_ref, slot_ref, valid_ref, live_ref, q_ref, hbm,
                   acc_hbm, stat_hbm, buf, accbuf, statbuf, sems, osems, *,
                   t, scale):
    """One invocation walks the live rows of the list as :func:`_kernel`
    does.  ``q_ref`` (B, per * rows, per * width): a slot's query rows,
    block-diagonal over the positions of a page's row.  ``buf`` (2, ppb *
    pr, per * width): a block's pages as they are stored, one under the
    other."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    live = live_ref[0]
    n = t.ppb * t.pr                            # rows of a block
    fill = jnp.finfo(jnp.float32).min
    prec = jax.lax.Precision.HIGHEST if t.exact else None

    def pages_of(row, b, go, unrolled=False):
        def page(i, _):
            go(pltpu.make_async_copy(
                hbm.at[pages_ref[row * t.ppb + i]],
                buf.at[b, pl.ds(pl.multiple_of(i * t.pr, t.pr), t.pr)],
                sems.at[b]))

        if unrolled:
            for i in range(t.ppb):
                page(i, None)
        else:
            jax.lax.fori_loop(0, t.ppb, page, None)

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    def shares(row, b):
        return (pltpu.make_async_copy(accbuf.at[b], acc_hbm.at[row],
                                      osems.at[b, 0]),
                pltpu.make_async_copy(statbuf.at[b], stat_hbm.at[row],
                                      osems.at[b, 1]))

    # position of the block that column j of row group i is: per * j + i
    pos = jax.lax.broadcasted_iota(jnp.int32, (t.per * t.rows, n), 1) * t.per \
        + _div(jax.lax.broadcasted_iota(jnp.int32, (t.per * t.rows, n), 0),
               t.rows)

    def attend(r, _):
        b = _rem(r, 2)

        @pl.when(r + 1 < live)
        def _next():
            pages_of(r + 1, 1 - b, start, unrolled=True)

        pages_of(r, b, wait)
        x = buf[b]                                  # (n, per * width)
        s = jax.lax.dot_general(
            q_ref[slot_ref[r]], x, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)     # (per * rows, n)
        logits = jnp.where(pos < valid_ref[r], s * jnp.float32(scale), fill)
        m = jnp.max(logits, axis=1, keepdims=True)
        top = m[:t.rows]
        for i in range(1, t.per):
            top = jnp.maximum(top, m[i * t.rows:(i + 1) * t.rows])
        p = jnp.exp(logits - jnp.concatenate([top] * t.per, axis=0))
        sums = jnp.sum(p, axis=1, keepdims=True)
        full = jax.lax.dot_general(
            p.astype(x.dtype), x, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)     # (per * rows, per * width)
        # row group i's values are the first ``rank`` columns of position i
        acc, den = full[:t.rows, :t.rank], sums[:t.rows]
        for i in range(1, t.per):
            acc = acc + full[i * t.rows:(i + 1) * t.rows,
                             i * t.width:i * t.width + t.rank]
            den = den + sums[i * t.rows:(i + 1) * t.rows]
        # the maxima and the sums, a head a lane: rows 0 and 1 of a tile
        lane = jax.lax.broadcasted_iota(jnp.int32, (t.rows, LANES), 1)
        stats = jnp.where(lane == 0, top, jnp.where(lane == 1, den, 0.0))
        stats = jnp.concatenate(
            [stats, jnp.zeros((LANES - t.rows, LANES), jnp.float32)], axis=0)

        @pl.when(r >= 2)
        def _sent():                    # row r - 2 has left these buffers
            for c in shares(r, b):
                c.wait()

        accbuf[b] = acc
        statbuf[b] = stats.T[:8]
        for c in shares(r, b):
            c.start()

    @pl.when(live > 0)
    def _first():
        pages_of(0, 0, start)

    jax.lax.fori_loop(0, live, attend, None)
    for back in (1, 2):
        @pl.when(live >= back)
        def _drain():
            for c in shares(0, _rem(live - back, 2)):
                c.wait()


def attend_latent_blocks(q, plane, pages, slot, valid, live, t, scale,
                         interpret=False):
    """The listed blocks' shares of each slot's softmax over a latent plane,
    as :func:`attend_blocks` gives them: ``(m (rows, H), den (rows, H), acc
    (rows, H, rank))`` float32.  ``q`` (B, 1, H * width) the absorbed query
    rows in the plane's type; ``plane`` as :func:`latent_plane_shape` stores
    it; the list as :func:`attend_blocks` takes it, blocks of ``t.block``
    positions."""
    import jax.numpy as jnp

    b = q.shape[0]
    qh = jnp.pad(q.reshape(b, t.heads, t.width).astype(plane.dtype),
                 ((0, 0), (0, t.rows - t.heads), (0, 0)))
    # (B, per * rows, per * width): row group i reads position i of a row
    qbd = jnp.einsum("ij,bhw->bihjw", jnp.eye(t.per, dtype=qh.dtype),
                     qh).reshape(b, t.per * t.rows, t.per * t.width)
    return _jitted_latent()(qbd, plane, pages, slot, valid, live, t=t,
                            scale=float(scale), interpret=bool(interpret))


@functools.lru_cache(maxsize=None)
def _jitted_latent():
    import jax

    return jax.jit(_attend_latent_blocks,
                   static_argnames=("t", "scale", "interpret"))


def _attend_latent_blocks(qbd, plane, pages, slot, valid, live, *, t, scale,
                          interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    rows = pages.shape[0]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    with jax.enable_x64(False):
        acc, stats = pl.pallas_call(
            functools.partial(_latent_kernel, t=t, scale=float(scale)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), hbm],
                out_specs=[hbm, hbm],
                scratch_shapes=[
                    pltpu.VMEM((2, t.ppb * t.pr, t.per * t.width),
                               plane.dtype),
                    pltpu.VMEM((2, t.rows, t.rank), jnp.float32),
                    pltpu.VMEM((2, 8, LANES), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2, 2))]),
            out_shape=[jax.ShapeDtypeStruct((rows, t.rows, t.rank),
                                            jnp.float32),
                       jax.ShapeDtypeStruct((rows, 8, LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 2 * t.vmem)))),
            name="latent_live_blocks",
            interpret=interpret,
        )(pages.reshape(-1).astype(jnp.int32), slot.astype(jnp.int32),
          valid.astype(jnp.int32), jnp.reshape(live, (1,)).astype(jnp.int32),
          qbd, plane)
    return (stats[:, 0, :t.heads], stats[:, 1, :t.heads],
            acc[:, :t.heads])


# ---------------------------------------------------------------------------
# A prefill chunk: ONE slot, many query rows (``ops.attention.
# _attend_live_blocks`` with ``b == 1``, the walk's ``running`` row).  The
# walk takes a block a step: it gathers the block's pages into a copy, and
# between its two products writes and reads again, through HBM, the float32
# logits, the mask repeated to positions, the exponentials and the
# probabilities of ``heads x rows x block`` entries.  :func:`attend_chunk_
# blocks` is that walk with the scores kept in fast memory: a grid step a
# tile of query rows, a loop inside it over the slot's blocks up to the
# tile's causal limit, the pages copied as :func:`_kernel` copies them (the
# next block's in flight), the running ``(max, sum, acc)`` of every head of
# the tile in fast memory across the blocks and written once.  A third kernel
# with a shape rule of its own (:func:`chunk_tiles`): it shares the pools'
# storage and the page copies with :func:`_kernel` and the combine after it
# with the walk; its products are matrix products of a tile's rows a head
# (one bfloat16 pass, float32 accumulation: ``_sdpa_cache``'s ``tq > 1``
# form), not the row form's three pieces, and a quantized pool's scale rows
# come to it already turned to positions (:func:`attend_chunk_blocks`).
#
# A selection (``chosen`` of the walk: which blocks of ``width`` positions a
# row of a KV group attends) rides in as int8, ``(H_kv, lane tiles of
# blocks, rows, 128)``: a step reads the lane tile its block's columns lie in
# and widens it to positions by one small product against a 0/1 matrix built
# from iotas, once a KV group, for all the group's heads.
# ---------------------------------------------------------------------------

# Query rows a tile, the largest first that divides the chunk and fits:
# the fixed cost of a visit to a block (its 64 page copies, its planes'
# casts, a group's mask) is shared by a tile's rows.  Measured kernel alone
# at MiniCPM-SALA's 32 heads, 2048 rows x 16k / 32k / 64k (my chip runs, PR
# 54): tiles of 128 rows 7.55 / 15.07 / 30.11 ms (the copies of 4 KB a page
# no longer hide behind the arithmetic), 256 4.76 / 9.34 / 18.53, 512 4.33 /
# 8.46 / 16.75.
CHUNK_TILE_ROWS = (512, 256, 128, 64, 32)


class ChunkTiles(NamedTuple):
    """The static sizes of one :func:`attend_chunk_blocks` call."""

    heads: int       # H
    kv_heads: int    # H_kv
    hd: int          # key head width
    hdv: int         # value head width
    pt: int          # positions a page
    ppb: int         # pages a block
    rows: int        # query rows a tile
    per: int         # selection blocks a block (0: nothing chosen)
    quant: bool
    vmem: int        # bytes of fast memory a step may take


def chunk_tiles(q_shape, k_pool, v_pool, num_heads, num_kv_heads, block,
                chosen=None):
    """The :class:`ChunkTiles` of one slot's ``q_shape[1]`` query rows over
    these pools by blocks of ``block`` positions, or None where the kernel
    does not tile the shapes: the walk then serves them.  ``chosen`` =
    ``(mask shape, width)`` of a selection laid over the walk."""
    import jax.numpy as jnp

    kd, vd = _plane(k_pool), _plane(v_pool)
    quant = _is_quant(k_pool)
    if quant != _is_quant(v_pool) or kd.dtype != vd.dtype \
            or kd.ndim != 3 or vd.shape[:2] != kd.shape[:2]:
        return None
    item = jnp.dtype(kd.dtype).itemsize
    tq, e = q_shape[1], q_shape[2]
    h = int(num_heads)
    kvh = int(num_kv_heads) or h
    pt, ek, ev = kd.shape[1], kd.shape[2], vd.shape[2]
    if q_shape[0] != 1 or h <= 0 or kvh <= 0 or h % kvh or e % h \
            or ev % kvh or ek != kvh * (e // h) or block % pt or item > 2:
        return None
    hd, hdv = e // h, ev // kvh
    # whole pages of whole sublane tiles, whole lane tiles a head: a head's
    # keys and values are cut out of a block at lane tiles
    if pt % 8 or hd % LANES or hdv % LANES or block % LANES:
        return None
    if quant and (k_pool.scale.shape[1] % pt
                  or k_pool.scale.shape[1] // pt < 2 * kvh):
        return None
    per = groups = 0
    if chosen is not None:
        shape, wide = chosen
        if len(shape) != 4 or shape[0] != 1 or shape[1] != kvh \
                or shape[2] != tq or block % wide \
                or LANES % (block // wide):
            return None
        per, groups = block // wide, -(-shape[3] // LANES)
    for rows in CHUNK_TILE_ROWS:
        if tq % rows:
            continue
        # the tile's running state and its queries (one buffer each), the
        # block's two buffers a plane and the planes once more as the
        # products read them, its scale rows, a group's mask, the scores of
        # a head five times over
        vmem = h * rows * (hdv * 4 + 2 * LANES * 4 + hd * 2 + 2 * 8 * 4) \
            + 2 * kvh * groups * rows * LANES \
            + block * (ek + ev) * (2 * item + 6) \
            + (2 * _scale_rows(kvh) * block * 4 if quant else 0) \
            + 5 * rows * block * 4
        if vmem <= _VMEM_BUDGET:
            return ChunkTiles(h, kvh, hd, hdv, pt, block // pt, rows, per,
                              quant, vmem)
    return None


def _scale_rows(kvh):
    """Rows of a block's turned scales: K's heads, V's heads, whole sublane
    tiles."""
    return -(-2 * kvh // 8) * 8


def _chunk_kernel(pages_ref, total_ref, q_ref, *rest, t, scale, cap, tq):
    """One invocation is a tile of ``t.rows`` query rows against the slot's
    blocks up to the tile's causal limit.  ``pages_ref`` (blocks * ppb,) the
    slot's page ids in the table's order, ``total_ref`` (1,) the slot's
    length through the chunk's last row.  ``q_ref`` (H, rows, hd) bfloat16;
    ``mask_ref`` (H_kv, lane tiles, rows, 128) int8 where something is
    chosen.  The pools stay in HBM.  ``acc_ref`` (H, rows, hdv) is the
    tile's accumulator and its output; ``stat_ref`` (H, 8, rows) takes the
    maxima in row 0 and the sums in row 1 when the tile's last block is
    done."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    rest = list(rest)
    mask_ref = rest.pop(0) if t.per else None
    k_hbm, v_hbm = rest.pop(0), rest.pop(0)
    s_hbm = rest.pop(0) if t.quant else None
    acc_ref, stat_ref, kbuf, vbuf = (rest.pop(0) for _ in range(4))
    sbuf = rest.pop(0) if t.quant else None
    m_scr, l_scr, sems = rest
    rows, block = t.rows, t.ppb * t.pt
    g = t.heads // t.kv_heads
    nb = pages_ref.shape[0] // t.ppb
    fill = jnp.finfo(jnp.float32).min
    mm = q_ref.dtype

    # row r of the tile sees the positions under total - (tq - 1) + r, and
    # none at or above the view's capacity: the walk's limit
    low = total_ref[0] - (tq - 1) + pl.program_id(0) * rows
    limit = jnp.minimum(
        low + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), cap)
    # the blocks that hold a position some row of the tile sees
    visit = jnp.clip(_div(jnp.minimum(low + rows - 1, cap) + block - 1,
                          block), 1, nb)

    def pages_of(b, buf, go, unrolled=False):
        def page(i, _):
            at = pages_ref[b * t.ppb + i]
            go(pltpu.make_async_copy(k_hbm.at[at], kbuf.at[buf, i],
                                     sems.at[buf, 0]))
            go(pltpu.make_async_copy(v_hbm.at[at], vbuf.at[buf, i],
                                     sems.at[buf, 1]))

        if unrolled:
            for i in range(t.ppb):
                page(i, None)
        else:
            jax.lax.fori_loop(0, t.ppb, page, None)
        if t.quant:
            go(pltpu.make_async_copy(s_hbm.at[b], sbuf.at[buf],
                                     sems.at[buf, 2]))

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    def plane(ref, buf):
        x = ref[buf]                            # (ppb, pt, E)
        if x.dtype != mm:
            x = x.astype(jnp.float32)
        return x.reshape(block, x.shape[-1]).astype(mm)

    m_scr[...] = jnp.full(m_scr.shape, fill, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    pages_of(0, 0, start)

    def attend(b, _):
        buf = _rem(b, 2)

        @pl.when(b + 1 < visit)
        def _next():
            pages_of(b + 1, 1 - buf, start, unrolled=True)

        pages_of(b, buf, wait)
        keys, values = plane(kbuf, buf), plane(vbuf, buf)
        turned = sbuf[buf] if t.quant else None
        pos = b * block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 1)
        under = pos < limit
        if t.per:
            # the block's columns of the selection: ``per`` lanes of one
            # lane tile, widened to positions by a 0/1 matrix
            lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, block), 0)
            col = _div(jax.lax.broadcasted_iota(
                jnp.int32, (LANES, block), 1), block // t.per)
            widen = jnp.where(lane == _rem(b * t.per, LANES) + col, 1.0,
                              0.0).astype(mm)
            tile_of = _div(b * t.per, LANES)
        for j in range(t.kv_heads):
            seen = under
            if t.per:
                picked = mask_ref[j, tile_of].astype(jnp.float32).astype(mm)
                seen = under & (jax.lax.dot_general(
                    picked, widen, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) > 0.5)
            kj = keys[:, j * t.hd:(j + 1) * t.hd]
            vj = values[:, j * t.hdv:(j + 1) * t.hdv]
            ks = jnp.float32(scale)
            vs = None
            if t.quant:
                ks = turned[j:j + 1] * jnp.float32(scale)
                vs = turned[t.kv_heads + j:t.kv_heads + j + 1]

            def head(i, _, seen=seen, kj=kj, vj=vj, ks=ks, vs=vs, j=j):
                h = j * g + i
                s = jax.lax.dot_general(
                    q_ref[h], kj, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # (rows, block)
                s = jnp.where(seen, s * ks, fill)
                m0 = m_scr[h]                               # (rows, 128)
                m1 = jnp.maximum(m0, jnp.max(s, axis=1, keepdims=True))
                shrink = jnp.exp(m0 - m1)
                p = jnp.exp(s - jnp.concatenate([m1] * (block // LANES),
                                                axis=1))
                l_scr[h] = shrink * l_scr[h] \
                    + jnp.sum(p, axis=1, keepdims=True)
                m_scr[h] = m1
                if vs is not None:
                    p = p * vs
                acc_ref[h] = acc_ref[h] * jnp.concatenate(
                    [shrink] * (t.hdv // LANES), axis=1) \
                    + jax.lax.dot_general(
                        p.astype(mm), vj, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

            jax.lax.fori_loop(0, g, head, None)

    jax.lax.fori_loop(0, visit, attend, None)

    # the maxima and the sums leave a row a lane: rows 0 and 1 of a tile
    first = jax.lax.broadcasted_iota(jnp.int32, (8, rows), 0) == 0

    def leave(h, _):
        stat_ref[h] = jnp.where(first, m_scr[h].T[:8], l_scr[h].T[:8])

    jax.lax.fori_loop(0, t.heads, leave, None)


def attend_chunk_blocks(q, k_pool, v_pool, pages, total, cap, t, scale,
                        chosen=None, interpret=False):
    """One slot's query rows over its blocks: the walk's running row, ``(m
    (1, tq, H), den (1, tq, H), acc (1, tq, H, hdv))`` float32, not yet
    normalized.

    ``q`` (1, tq, E), row ``i`` at position ``total - tq + i``; the pools as
    the paged ops store them; ``pages`` (blocks, ppb) the slot's page ids in
    the table's order (a last block past the table's end reads the scratch
    page); ``total`` the slot's length through the chunk's last row and
    ``cap`` the view's capacity: row ``i`` attends the positions under
    ``min(total - (tq - 1) + i, cap)``.  ``chosen`` = ``(mask (1, H_kv, tq,
    n) bool, width)`` as the walk takes it; ``t`` the call's
    :class:`ChunkTiles`."""
    import jax.numpy as jnp

    tq = q.shape[1]
    qh = jnp.swapaxes(q[0].reshape(tq, t.heads, t.hd), 0, 1)  # (H, tq, hd)
    if not interpret:
        # the products read their operands in the query's type, as the
        # walk's einsums do; a float32 one is ONE bfloat16 pass on the chip
        # (XLA's default precision), so the cast is made once, out here.
        # The interpreter's products are the CPU's: float32 as they lie
        qh = qh.astype(jnp.bfloat16)
    mask = None
    if chosen is not None:
        mask = chosen[0][0].astype(jnp.int8)                # (H_kv, tq, n)
        n = mask.shape[2]
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, -n % LANES)))
        mask = jnp.swapaxes(mask.reshape(t.kv_heads, tq, -1, LANES), 1, 2)
    turned = None
    if t.quant:
        # the slot's scale rows, turned once a call: (blocks, K's heads then
        # V's, positions of a block).  Not copied page by page inside the
        # kernel as the decode row's are: a plane of 2 KV heads is 64 floats
        # a page, half a lane tile, and Mosaic cuts a tiled plane at whole
        # tiles; and a turn made in the kernel is made again by every tile
        # of rows that visits the block
        w = 2 * t.kv_heads
        turned = jnp.swapaxes(k_pool.scale[pages.reshape(-1)].reshape(
            pages.shape[0], t.ppb * t.pt, -1), 1, 2)
        if turned.shape[1] != w:    # a padded stretch: scale_group
            turned = turned[:, :w]
        turned = jnp.pad(turned, ((0, 0), (0, _scale_rows(t.kv_heads) - w),
                                  (0, 0)))
    acc, stats = _jitted_chunk()(
        qh, mask, _plane(k_pool), _plane(v_pool), turned, pages, total, t=t,
        scale=float(scale), cap=int(cap), interpret=bool(interpret))
    return (jnp.swapaxes(stats[:, 0], 0, 1)[None],
            jnp.swapaxes(stats[:, 1], 0, 1)[None],
            jnp.swapaxes(acc, 0, 1)[None])


@functools.lru_cache(maxsize=None)
def _jitted_chunk():
    import jax

    return jax.jit(_attend_chunk_blocks,
                   static_argnames=("t", "scale", "cap", "interpret"))


def _attend_chunk_blocks(qh, mask, kd, vd, turned, pages, total, *, t, scale,
                         cap, interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    tq = qh.shape[1]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # one buffer a tile's queries and values: a tile is long (every block
    # under its limit, every head), so the copies at its ends need not hide
    # behind arithmetic, and a second buffer of each would halve the tile
    once = pl.Buffered(1)
    in_specs = [pl.BlockSpec((t.heads, t.rows, t.hd),
                             lambda i, *_: (0, i, 0), pipeline_mode=once)]
    args = [qh]
    if mask is not None:
        in_specs.append(pl.BlockSpec(
            (t.kv_heads, mask.shape[1], t.rows, LANES),
            lambda i, *_: (0, 0, i, 0)))
        args.append(mask)
    in_specs += [hbm, hbm]
    args += [kd, vd]
    scratch = [pltpu.VMEM((2, t.ppb) + kd.shape[1:], kd.dtype),
               pltpu.VMEM((2, t.ppb) + vd.shape[1:], vd.dtype)]
    if t.quant:
        in_specs.append(hbm)
        args.append(turned)
        scratch.append(pltpu.VMEM((2,) + turned.shape[1:], jnp.float32))
    scratch += [pltpu.VMEM((t.heads, t.rows, LANES), jnp.float32),
                pltpu.VMEM((t.heads, t.rows, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 3))]
    with jax.enable_x64(False):
        acc, stats = pl.pallas_call(
            functools.partial(_chunk_kernel, t=t, scale=float(scale),
                              cap=int(cap), tq=tq),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(tq // t.rows,),
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((t.heads, t.rows, t.hdv),
                                 lambda i, *_: (0, i, 0), pipeline_mode=once),
                    pl.BlockSpec((t.heads, 8, t.rows),
                                 lambda i, *_: (0, 0, i))],
                scratch_shapes=scratch),
            out_shape=[
                jax.ShapeDtypeStruct((t.heads, tq, t.hdv), jnp.float32),
                jax.ShapeDtypeStruct((t.heads, 8, tq), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=int(min(100 << 20,
                                         max(32 << 20, 2 * t.vmem)))),
            name="chunk_live_blocks",
            interpret=interpret,
        )(pages.reshape(-1).astype(jnp.int32),
          jnp.reshape(total, (1,)).astype(jnp.int32), *args)
    return acc, stats


# ---------------------------------------------------------------------------
# The expanded latent chunk (``ops.attention.latent_attend`` with ONE slot and
# many query rows over a paged plane).  The walk's step gathers a block of 512
# positions, expands it through ``W_kvb`` and takes ``_sdpa_cache`` over it:
# between the two products the float32 logits of ``heads x rows x block``
# entries go to HBM and come back, and the running accumulator of ``rows x
# heads x v`` floats is read and written a block.  Here the walk keeps its
# shape and takes a wider step: the loop (``ops.attention.
# _attend_latent_segments``) gathers and expands a SEGMENT of
# :data:`LATENT_CHUNK_SEGMENT` positions in XLA, heads first, and ONE kernel
# takes every row against the segment's dense keys and values.  A grid step is
# (head, block of the segment); a head's running ``(max, sum, acc)`` comes in
# from the segment before, stays in fast memory over the segment's blocks and
# leaves once: the kernel IS the walk's fold, so the accumulator crosses HBM
# once a segment and the scores never do.  A fourth kernel with a shape rule
# of its own (:func:`latent_chunk_tiles`); it copies no page (a segment's keys
# and values are ordinary blocks) and shares the walk's limit and the combine
# after the loop.
#
# A head's keys are its own ``nope`` columns beside the ONE rotated part every
# head shares (64 + 64 at Mistral-Small-4: one lane tile, one pass of the
# matrix unit's depth, where the two parts apart would be two half-filled
# ones), so the expansion lays them side by side, heads first.  The causal
# limit is the walk's, by absolute position; a block no row can see is neither
# multiplied nor fetched (its index is held at the last live block's), a tile
# of rows skips the blocks above its last row, and only the blocks its limit
# crosses build a mask.
#
# The other form, the expansion INSIDE the kernel from the pages as they lie
# (block-diagonal weights over a row's two positions, a head-major grid), was
# measured beside this one and is kept, with its numbers, in
# benchmarks/probe_latent_chunk.py.
# ---------------------------------------------------------------------------

# Positions a step of the loop (a segment), positions a grid step (a block)
# and query rows a tile (the largest first that divides the chunk and fits).
# Measured alone on the chip (TPU v5 lite, jax 0.9.0, the one cell's shapes:
# ONE slot's 2048 rows of 32 heads of 64 + 64 key and 128 value columns over a
# bfloat16 plane of 83,201 pages, the chunk's last row at 16k / 32k / 64k
# positions; benchmarks/probe_latent_chunk.py, its lines in benchmarks/runs/
# pr62_probe.out, PR 62; ms a layer, gather and expansion inside on every
# side; in brackets the share of the bfloat16 peak by the causal FLOPs the
# benchmark's reader counts):
#   the walk (a block of 512 a step)        6.04 / 11.90 / 23.65  (48-51 %)
#   the expansion inside the kernel, tiles of 256 rows
#                                           5.20 / 10.04 / 19.69  (55-61 %)
#   segment 8192, block 1024, tile 256      4.06 /  7.88 / 15.49  (71-78 %)
#     segment 4096                          4.21 /  8.17 / 16.06
#     segment 16384                         4.01 /  7.72 / 15.17
#     block 512                             4.10 /  7.93 / 15.63
#     block 2048                            4.41 /  8.36 / 16.34
#     tile 128                              4.68 /  9.13 / 17.93
#     tile 512                              4.23 /  8.16 / 16.01
# A segment of 16384 is 2 % ahead (the running row crosses HBM half as often)
# and holds 0.27 GB of expanded keys and values where 8192 holds 0.13, and a
# chunk's last segment is half dead on average, expanded all the same.  What
# the kernel cannot hide, with both products taken out of it (the same probe,
# a block of 512 and tiles of 512 without the straight run below: 8.86 ms
# at 32k whole, 6.64 without the first product, 7.00 without the second, 5.10
# without both, 8.59 without the exponentials): the softmax's passes over the
# scores and XLA's gather and expansion are 5.1 ms where the two products are
# 5.6 at the peak, so the two sides nearly tie and the rest is how well they
# overlap: tiles of 256 in one straight run gave 8.86 -> 7.88.
LATENT_CHUNK_SEGMENT = 8192
LATENT_CHUNK_BLOCK = 1024
LATENT_CHUNK_TILE_ROWS = (256, 128)


class LatentChunkTiles(NamedTuple):
    """The static sizes of one :func:`attend_latent_segment` call."""

    heads: int       # H
    hd: int          # a head's key columns: nope + rope
    v: int           # a head's value columns
    rows: int        # query rows of the chunk
    tile: int        # query rows a tile
    block: int       # positions a grid step
    segment: int     # positions a step of the loop
    exact: bool      # a float32 plane: products at Precision.HIGHEST
    vmem: int        # bytes of fast memory a step may take


def latent_chunk_tiles(rows, heads, hd, v, dtype, page_tokens, cap):
    """The :class:`LatentChunkTiles` of one slot's ``rows`` query rows of
    ``heads`` heads (keys of ``hd`` = nope + rope columns, values of ``v``)
    over a plane of ``dtype`` read through a table of ``cap`` positions in
    pages of ``page_tokens``, or None where the kernel does not tile the
    shapes: the walk then serves them."""
    import jax.numpy as jnp

    item = jnp.dtype(dtype).itemsize
    block = LATENT_CHUNK_BLOCK
    # a head's keys and values are whole lane tiles, a block whole pages
    if item not in (2, 4) or hd % LANES or v % LANES or heads <= 0 \
            or block % page_tokens or block % LANES or rows % LANES:
        return None
    # no more of a segment than the view holds, in whole blocks
    segment = min(LATENT_CHUNK_SEGMENT, -(-cap // block) * block)
    if segment % block:
        return None
    for tile in LATENT_CHUNK_TILE_ROWS:
        if rows % tile:
            continue
        # a head's queries and a block's keys and values (two buffers each),
        # the head's accumulator in and out (two each) and its maxima and
        # sums, a tile's scores five times over
        vmem = 2 * rows * hd * item + 2 * block * (hd + v) * item \
            + 4 * rows * v * 4 + 4 * 8 * rows * 4 + 2 * rows * LANES * 4 \
            + 5 * tile * block * 4
        if vmem <= _VMEM_BUDGET:
            return LatentChunkTiles(heads, hd, v, rows, tile, block, segment,
                                    item == 4, vmem)
    return None


def _segment_live_blocks(at_ref, t, cap):
    """Blocks of the segment that hold a position some row sees (the first
    always): ``at_ref`` = (the segment's first position, the slot's length
    through the chunk's last row)."""
    import jax.numpy as jnp

    seen = jnp.minimum(at_ref[1], cap) - at_ref[0]
    return jnp.clip(_div(seen + t.block - 1, t.block), 1,
                    t.segment // t.block)


def _latent_chunk_kernel(at_ref, q_ref, k_ref, v_ref, acc_in, stat_in,
                         acc_ref, stat_ref, m_scr, l_scr, *, t, scale, cap):
    """One invocation is a head's ``t.rows`` query rows against one block of
    the segment.  ``q_ref`` (1, rows, hd) the head's queries; ``k_ref`` (1,
    block, hd), ``v_ref`` (1, block, v) its keys and values.  ``acc_in`` (1,
    rows, v) and ``stat_in`` (1, 8, rows: the maxima in row 0, the sums in
    row 1) are the head's running row as the segment before left it;
    ``acc_ref`` and ``stat_ref`` take it as this one leaves it."""
    import jax
    import jax.numpy as jnp
    pl, _ = _pallas()

    j = pl.program_id(1)
    rows, tile, block = t.rows, t.tile, t.block
    fill = jnp.finfo(jnp.float32).min
    prec = jax.lax.Precision.HIGHEST if t.exact else None
    # row r sees the positions under total - (rows - 1) + r, and none at or
    # above the view's capacity: the walk's limit
    low = at_ref[1] - (rows - 1)
    pos0 = at_ref[0] + j * block

    @pl.when(j == 0)
    def _enter():
        acc_ref[...] = acc_in[...]
        stats = stat_in[0]                                  # (8, rows)
        m_scr[...] = jnp.broadcast_to(stats[0:1], (LANES, rows)).T
        l_scr[...] = jnp.broadcast_to(stats[1:2], (LANES, rows)).T

    def update(r0, masked):
        at = pl.ds(r0, tile)
        s = jax.lax.dot_general(
            q_ref[0, at], k_ref[0], (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if masked:                                          # (tile, block)
            pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1)
            limit = jnp.minimum(low + r0 + jax.lax.broadcasted_iota(
                jnp.int32, (tile, 1), 0), cap)
            s = jnp.where(pos < limit, s, fill)
        m0 = m_scr[at]                                      # (tile, 128)
        m1 = jnp.maximum(m0, jnp.max(s, axis=1, keepdims=True))
        shrink = jnp.exp(m0 - m1)
        p = jnp.exp(s - jnp.concatenate([m1] * (block // LANES), axis=1))
        l_scr[at] = shrink * l_scr[at] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[at] = m1
        acc_ref[0, at] = acc_ref[0, at] * jnp.concatenate(
            [shrink] * (t.v // LANES), axis=1) + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)

    def crossed(r, _):
        r0 = pl.multiple_of(r * tile, tile)
        first = jnp.minimum(low + r0, cap)
        # (a row that sees nothing still takes the view's first block, so
        # that its answer is finite: the walk's)
        last = jnp.maximum(jnp.minimum(low + r0 + tile - 1, cap), 1)

        @pl.when(pos0 + block <= first)
        def _whole():
            update(r0, False)

        @pl.when((pos0 < last) & (pos0 + block > first))
        def _some():
            update(r0, True)

    @pl.when(j < _segment_live_blocks(at_ref, t, cap))
    def _block():
        # a block under every row's limit (all but the chunk's own few) is
        # ONE straight run of the tiles, no branch between them: the
        # scheduler lays a tile's products beside its neighbour's softmax
        every = pos0 + block <= jnp.minimum(low, cap)

        @pl.when(every)
        def _all():
            for r in range(rows // tile):
                update(r * tile, False)

        @pl.when(jnp.logical_not(every))
        def _edge():
            jax.lax.fori_loop(0, rows // tile, crossed, None)

    @pl.when(j == pl.num_programs(1) - 1)
    def _leave():
        top = jax.lax.broadcasted_iota(jnp.int32, (8, rows), 0) == 0
        stat_ref[0] = jnp.where(top, m_scr[...].T[:8], l_scr[...].T[:8])


def attend_latent_segment(q, keys, values, state, first, total, cap, t, scale,
                          interpret=False):
    """One slot's query rows over one expanded segment, folded into the
    walk's running row: ``state`` = ``(stats (H, 8, rows), acc (H, rows, v))``
    float32 as the segments before left it (the maxima in row 0 of ``stats``,
    the sums in row 1; ``finfo.min``, 0 and 0 before the first) -> the same
    after this one, not yet normalized.

    ``q`` (H, rows, hd), row ``i`` at position ``total - rows + i``; ``keys``
    (H, segment, hd) and ``values`` (H, segment, v) the positions ``first``
    to ``first + segment`` of the view, in the plane's type, as the queries
    are taken; ``cap`` the view's capacity: row ``i`` attends the positions
    under ``min(total - (rows - 1) + i, cap)``.  ``t`` the call's
    :class:`LatentChunkTiles`."""
    import jax.numpy as jnp

    at = jnp.stack([jnp.asarray(first, jnp.int32).reshape(()),
                    jnp.asarray(total, jnp.int32).reshape(())])
    return _jitted_latent_chunk()(
        at, q.astype(keys.dtype), keys, values, *state, t=t,
        scale=float(scale), cap=int(cap), interpret=bool(interpret))


@functools.lru_cache(maxsize=None)
def _jitted_latent_chunk():
    import jax

    return jax.jit(_attend_latent_segment,
                   static_argnames=("t", "scale", "cap", "interpret"))


def _attend_latent_segment(at, q, keys, values, stats, acc, *, t, scale, cap,
                           interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    head = lambda h, j, at_ref: (h, 0, 0)
    # a block no row sees is not fetched: the last live one stays
    block = lambda h, j, at_ref: (
        h, jnp.minimum(j, _segment_live_blocks(at_ref, t, cap) - 1), 0)
    with jax.enable_x64(False):
        acc, stats = pl.pallas_call(
            functools.partial(_latent_chunk_kernel, t=t, scale=float(scale),
                              cap=int(cap)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(t.heads, t.segment // t.block),
                in_specs=[pl.BlockSpec((1, t.rows, t.hd), head),
                          pl.BlockSpec((1, t.block, t.hd), block),
                          pl.BlockSpec((1, t.block, t.v), block),
                          pl.BlockSpec((1, t.rows, t.v), head),
                          pl.BlockSpec((1, 8, t.rows), head)],
                out_specs=[pl.BlockSpec((1, t.rows, t.v), head),
                           pl.BlockSpec((1, 8, t.rows), head)],
                scratch_shapes=[pltpu.VMEM((t.rows, LANES), jnp.float32),
                                pltpu.VMEM((t.rows, LANES), jnp.float32)]),
            out_shape=[
                jax.ShapeDtypeStruct((t.heads, t.rows, t.v), jnp.float32),
                jax.ShapeDtypeStruct((t.heads, 8, t.rows), jnp.float32)],
            # the running row is folded where it lies
            input_output_aliases={4: 0, 5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=int(min(100 << 20,
                                         max(32 << 20, 2 * t.vmem)))),
            name="latent_chunk_segment",
            interpret=interpret,
        )(at, q, keys, values, acc, stats)
    return stats, acc
