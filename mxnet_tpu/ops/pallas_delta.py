"""The delta rule's decode step as one kernel (TPU): a slot's matrices are
read from HBM once, corrected in fast memory and written once, in place.

``ops.kda._step`` is the step's mathematics, one token a row over a carried
(B, H, Dk, Dv) float32 state; slot b, head h, ``S`` the (Dk, Dv) matrix:

    S'    = Diag(exp g) S          g (Dk,): a decay a channel (Kimi delta
                                   attention) or one number a head (Gated
                                   DeltaNet) broadcast over the key dim
    nu    = beta (v - k^T S')      (Dv,)
    o     = q^T S' + (q . k) nu    (Dv,)
    S_new = S' + k nu^T

and ``mix`` keeps ``S`` where the row's ``active`` is 0.  XLA lowers it to
two fusions: one reads every matrix for the two sums over the key dim, the
next reads every matrix again, decays it again, adds the rank-one term,
selects and writes (three passes where the mathematics needs two).
:func:`delta_step` is those fusions as ONE ``pallas_call`` over a grid of
(slot, group of heads): a step's block is the matrices of :func:`head_block`
heads of one slot, moved to fast memory by the pipeline, both sums, the
correction and the mask taken while they lie there, and the block written
back into the SAME buffer (``input_output_aliases``: the caller's donated
leaf is the result's storage, no copy of it exists).

What multiplies a matrix along its rows (``k``, ``q``, ``exp g``: one value a
key dim) has to lie along SUBLANES, the key dim's place in a matrix's tiles;
a (…, Dk, 1) plane would be padded 128-fold in HBM, more bytes than the
state.  The wrapper packs the three a group of heads as ``(B, G, Dk, 3 *
block)``, heads on lanes (2-3 % of a step's bytes); a head's column is one
lane of it, broadcast along the value dim in fast memory.  What adds to a
matrix's columns (``v``, ``beta``, ``q . k``: one value a value dim, or a
head) lies along lanes as ``(B, G, 3, block, Dv)``.

All arithmetic is float32 on the vector unit, as ``_step``'s: the sums over
the key dim add in another order, nothing else differs.  An inactive row's
block is copied as it lies, bit for bit, and its ``o`` is zeros.

:func:`supported` is the shape rule and :func:`head_block` the tile rule,
both from the state's shape alone; ``ops.kda.step`` adds what the call shows
(a backend that runs Pallas, no mesh).  ``interpret=True`` runs the same
kernel on the CPU (tests/test_delta_step_kernel.py).
"""
from __future__ import annotations

import functools

from ..obs.startup import pallas as _pallas

LANES, SUBLANES = 128, 8
# What a step's four state buffers (the block in and out, each twice for the
# pipeline) may take of fast memory: half of Mosaic's default scoped limit
# of 16 MiB, the rest left to the small operands and a head's temporaries.
# :func:`head_block` takes the most heads that fit.  Measured kernel alone on
# the chip (TPU v5 lite, jax 0.9.0, 96 rows, the operands' packing included;
# benchmarks/probe_delta_step.py, PR 58; ms a layer by heads a step):
#   30 heads of 96 x 192:   1 1.97, 2 1.17, 3 1.02, 5 0.959, 6 0.957,
#                           10 0.946, 15 0.943 (the rule's), 30 0.951
#   64 heads of 128 x 128:  1 7.46, 2 4.30, 4 2.40, 8 1.380, 16 1.335,
#                           32 1.343 (the rule's), 64 refused (16.8 MB of
#                           buffers: over the scoped limit)
# A step of a megabyte or more hides the grid's per-step cost behind its
# copies and every block from there to the limit reads within 1 % of the
# best; below it the step's cost shows (4.5 us a step at one head).
_STATE_BUFFERS = 8 << 20


def _head_bytes(dk, dv):
    """Bytes of one head's float32 matrix as fast memory (and HBM) hold it:
    whole (8, 128) tiles."""
    return (-(-dk // SUBLANES) * SUBLANES) * (-(-dv // LANES) * LANES) * 4


def _lane_waste(n):
    """What padding ``n`` to whole lane tiles adds, as a share of ``n``."""
    return (-n % LANES) / n


def supported(h, dk, dv, rows=None):
    """Whether the kernel tiles ``h`` heads of (``dk``, ``dv``) matrices: a
    key dim of whole sublane tiles (a head's column is cut from the packed
    plane at whole tiles), a value dim that fills at least half of its lane
    tiles (under that the padding's bytes outweigh the second pass), and one
    head's four buffers inside the budget.

    With ``rows``, the state's leading dim: also that XLA:TPU stores a
    (``rows``, ``h``, ``dk``, ``dv``) float32 array value dim minor, as the
    kernel's blocks read it.  The compiler lays a program's operand with
    whichever dim wastes the least in lane tiles on the lanes, the last dim
    on a tie (compiled for a described v5e, jax 0.9.0: 96 and 192 rows of 30
    x 96 x 192 stay value dim minor, 112, 120, 128 and 384 rows go rows
    minor, 128 heads heads minor, key dims of 128 key dim minor; a value dim
    of whole tiles never moves).  Under another layout the kernel costs a
    relayout of the state each way, more than the pass it saves: the
    elementwise step, which reads any layout as it lies, serves those
    (tests/test_pallas_decode.py holds this rule to the compiler's choice)."""
    if not (h >= 1 and dk >= SUBLANES and dk % SUBLANES == 0
            and 2 * dv >= -(-dv // LANES) * LANES
            and 4 * _head_bytes(dk, dv) <= _STATE_BUFFERS):
        return False
    return rows is None or all(_lane_waste(dv) <= _lane_waste(n)
                               for n in (rows, h, dk))


def head_block(h, dk, dv):
    """Heads a grid step: the most whose four buffers fit
    :data:`_STATE_BUFFERS`, then the fewest that keep the same number of
    steps a slot (30 heads of 96 x 192: 21 fit, two steps, 15 a step; 64 of
    128 x 128: 32)."""
    most = max(1, _STATE_BUFFERS // (4 * _head_bytes(dk, dv)))
    steps = -(-h // most)
    return -(-h // steps)


def _kernel(active_ref, cols_ref, rows_ref, s_ref, o_ref, out_ref, *, block):
    """Grid step (b, i): heads ``i * block ...`` of slot ``b``.  ``cols_ref``
    (1, 1, Dk, 3 * block): lanes [k | q | exp g] by head; ``rows_ref`` (1, 1,
    3, block, Dv): [v | beta | q . k] by head; ``s_ref`` and ``out_ref`` (1,
    block, Dk, Dv), the same HBM buffer; ``o_ref`` (1, 1, block, Dv)."""
    import jax.numpy as jnp
    pl = _pallas()[0]

    on = active_ref[pl.program_id(0)] != 0

    @pl.when(on)
    def _():
        cols = cols_ref[0, 0]
        for j in range(block):
            col = lambda n: cols[:, n * block + j:n * block + j + 1]
            row = lambda n: rows_ref[0, 0, n, j:j + 1, :]
            k = col(0)
            s = s_ref[0, j] * col(2)
            nu = row(1) * (row(0) - jnp.sum(k * s, axis=0, keepdims=True))
            o_ref[0, 0, j:j + 1, :] = jnp.sum(col(1) * s, axis=0,
                                              keepdims=True) + row(2) * nu
            out_ref[0, j] = s + k * nu

    @pl.when(jnp.logical_not(on))
    def _():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def delta_step(q, k, v, g, beta, s, active=None, block=None,
               interpret=False):
    """``(o (B, H, Dv) float32, S_new)``: ``ops.kda._step`` over the rows
    whose ``active`` is not 0, the others' matrices as they were.  ``q``
    (scaled), ``k`` (B, H, Dk), ``v`` (B, H, Dv), ``g`` (B, H, Dk) or (B, H,
    1) log-decays, ``beta`` (B, H), ``s`` (B, H, Dk, Dv) float32, ``active``
    (B,) or None (every row); ``block`` heads a grid step
    (:func:`head_block` where None).  ``S_new`` is written into ``s``'s
    buffer where the caller donates it.

    The launch is traced as ONE function a (shapes, ``block``): the delta
    layers of a decode program share a kernel shape."""
    import jax.numpy as jnp

    b, h, dk, dv = s.shape
    if s.dtype != jnp.float32:
        raise ValueError("delta_step: the state is %s, not float32"
                         % s.dtype)
    block = int(block or head_block(h, dk, dv))
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, beta = f32(q), f32(k), f32(v), f32(beta)
    decay = jnp.broadcast_to(jnp.exp(f32(g)), (b, h, dk))
    qk = jnp.sum(q * k, axis=-1)
    steps = -(-h // block)
    spare = steps * block - h
    # heads padded to whole groups in the small operands only: the last
    # group's spare heads are read past the state's edge and not written
    group = lambda x: jnp.pad(x, ((0, 0), (0, spare), (0, 0))).reshape(
        b, steps, block, x.shape[-1])
    cols = jnp.concatenate(
        [jnp.swapaxes(group(x), 2, 3) for x in (k, q, decay)], axis=-1)
    over = lambda x: jnp.broadcast_to(x[..., None], (b, h, dv))
    rows = jnp.stack([group(v), group(over(beta)), group(over(qk))], axis=2)
    if active is None:
        active = jnp.ones((b,), jnp.int32)
    o, s_new = _jitted()(jnp.asarray(active).reshape(b).astype(jnp.int32),
                         cols, rows, s, block=block,
                         interpret=bool(interpret))
    return o.reshape(b, steps * block, dv)[:, :h], s_new


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax

    return jax.jit(_launch, static_argnames=("block", "interpret"))


def _launch(active, cols, rows, s, *, block, interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pallas()

    b, h, dk, dv = s.shape
    steps = cols.shape[1]
    # Mosaic has no 64-bit integers: the kernel is traced with 32-bit
    # defaults whatever ``jax_enable_x64`` says (the tests set it)
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_kernel, block=block),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, steps),
                in_specs=[
                    pl.BlockSpec((1, 1, dk, 3 * block),
                                 lambda i, j, on: (i, j, 0, 0)),
                    pl.BlockSpec((1, 1, 3, block, dv),
                                 lambda i, j, on: (i, j, 0, 0, 0)),
                    pl.BlockSpec((1, block, dk, dv),
                                 lambda i, j, on: (i, j, 0, 0))],
                out_specs=[
                    pl.BlockSpec((1, 1, block, dv),
                                 lambda i, j, on: (i, j, 0, 0)),
                    pl.BlockSpec((1, block, dk, dv),
                                 lambda i, j, on: (i, j, 0, 0))]),
            out_shape=[
                jax.ShapeDtypeStruct((b, steps, block, dv), jnp.float32),
                jax.ShapeDtypeStruct(s.shape, jnp.float32)],
            # the state in (operand 3, the prefetched mask counted) is the
            # state out: one buffer
            input_output_aliases={3: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            cost_estimate=pl.CostEstimate(
                flops=8 * b * h * dk * dv, transcendentals=0,
                bytes_accessed=2 * b * h * _head_bytes(dk, dv)),
            name="delta_step",
            interpret=interpret,
        )(active, cols, rows, s)
