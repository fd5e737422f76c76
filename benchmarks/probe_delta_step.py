"""The delta rule's decode step alone on the chip, at the shapes of the two
cells that run it (``olmoh_serve_rollouts``: 96 rows of 30 heads of 96 x 192,
one decay a head; ``solar2_serve_agent``: 96 rows of 64 heads of 128 x 128, a
decay a channel): a layer's step through ``mix`` in both forms (``ops.
pallas_delta``'s kernel and ``ops.kda._step``'s two fusions), what the two
forms' results differ by on the chip, and the kernel alone over head-block
sizes (the sweep behind ``pallas_delta.head_block``).  ``probe_gdn_forms.py``
and ``probe_kda_forms.py`` print the same lines for their op after their
chunk forms; this prints both ops' and nothing else.  One process, the
chip's; nothing of the benchmark calls this.

    chiprun -- python3 benchmarks/probe_delta_step.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import attention as _attn
from mxnet_tpu.ops import pallas_delta

HBM = 819e9     # bytes a second, TPU v5e (chipbench/peaks.py)
ROUNDS = 20


def chip_or_exit(who):
    """``say(**fields)`` that names the device in every line; exits without
    a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("%s times the op on the chip: jax.devices()[0] is "
                         "%s (%s), not a TPU" % (who, dev.platform,
                                                 dev.device_kind))
    return lambda **kw: print(json.dumps(dict(
        kw, device={"platform": dev.platform, "kind": dev.device_kind})),
        flush=True)


def _rounds(fn, carried, *fixed):
    """ms a call of ``fn(*fixed, carried) -> (out, carried)``, the carried
    operand donated: one call to compile, then :data:`ROUNDS` back to back."""
    out, carried = fn(*fixed, carried)
    jax.block_until_ready(carried)
    began = time.perf_counter()
    for _ in range(ROUNDS):
        out, carried = fn(*fixed, carried)
    jax.block_until_ready(carried)
    return out, carried, 1e3 * (time.perf_counter() - began) / ROUNDS


def step_lines(say, mix, attrs, streams, weights, state, moved):
    """A layer's decode step through ``mix`` in both forms, every row
    active: a line a form (``hbm_util_pct`` over ``moved`` bytes, a matrix
    and a tail read once and written once), and what the forms' results
    differ by after the same first step."""
    on = jnp.ones((state[1].shape[0],), jnp.int32)
    backend, first = _attn._kernel_backend, {}
    for path, answer in (("kernel", backend),
                         ("elementwise", lambda: (False, False))):
        _attn._kernel_backend = answer
        try:
            step = jax.jit(lambda s, w, st: mix(attrs, *s, *w, state=st,
                                                active=on)[:2],
                           donate_argnums=(2,))
            first[path] = jax.tree_util.tree_map(
                jnp.copy, step(streams, weights,
                               jax.tree_util.tree_map(jnp.copy, state)))
            _, _, ms = _rounds(step, jax.tree_util.tree_map(jnp.copy, state),
                               streams, weights)
        finally:
            _attn._kernel_backend = backend
        say(form="step", path=path, rows=int(on.shape[0]), ms=ms,
            state_step_bytes=moved,
            hbm_util_pct=100 * moved / (ms / 1e3) / HBM)
    (o_k, (_, s_k)), (o_e, (_, s_e)) = first["kernel"], first["elementwise"]
    say(form="step", kernel_against_elementwise=dict(
        out_max=float(jnp.max(jnp.abs(o_k.astype(jnp.float32)
                                      - o_e.astype(jnp.float32)))),
        state_max=float(jnp.max(jnp.abs(s_k - s_e))),
        state_rms=float(jnp.sqrt(jnp.mean(s_e ** 2)))))


def _moved_only(s, block):
    """``delta_step``'s grid and state blocks with nothing computed: every
    block read, copied in fast memory and written back into the same buffer.
    What the pipeline's copies alone take: the kernel's ceiling."""
    from jax.experimental import pallas as pl

    b, h, dk, dv = s.shape
    spec = pl.BlockSpec((1, block, dk, dv), lambda i, j: (i, j, 0, 0))

    def body(s_ref, out_ref):
        out_ref[...] = s_ref[...]

    return None, pl.pallas_call(
        body, grid=(b, -(-h // block)), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(s.shape, s.dtype),
        input_output_aliases={0: 0}, name="delta_step_moved_only")(s)


def sweep(say, slots, h, dk, dv, per_head, blocks):
    """The kernel alone over ``slots`` rows by heads a grid step: ms a call
    and the share of the HBM peak over the matrices' logical bytes, read
    once and written once (``stored_``: over the bytes as the chip stores
    them, whole (8, 128) tiles); then the same blocks moved and nothing
    computed (``form="moved_only"``), at the rule's block."""
    ks = jax.random.split(jax.random.PRNGKey(58), 6)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)
    q, k, v = n(0, slots, h, dk) * dk ** -1.0, n(1, slots, h, dk) \
        * dk ** -0.5, n(2, slots, h, dv)
    g = -jnp.abs(0.1 * n(3, slots, h, 1 if per_head else dk))
    beta = 2 * jax.nn.sigmoid(n(4, slots, h))
    on = jnp.ones((slots,), jnp.int32)
    moved = 2 * slots * h * dk * dv * 4
    stored = 2 * slots * h * pallas_delta._head_bytes(dk, dv)
    for block in blocks:
        fn = jax.jit(lambda q, k, v, g, beta, s: pallas_delta.delta_step(
            q, k, v, g, beta, s, on, block=block), donate_argnums=(5,))
        try:
            _, _, ms = _rounds(fn, n(5, slots, h, dk, dv), q, k, v, g, beta)
        except Exception as e:      # what the chip's compiler refuses
            say(form="kernel", heads=h, dk=dk, dv=dv, block=block,
                refused=str(e).strip().splitlines()[-1][:200])
            continue
        say(form="kernel", heads=h, dk=dk, dv=dv, block=block,
            chosen=block == pallas_delta.head_block(h, dk, dv), ms=ms,
            hbm_util_pct=100 * moved / (ms / 1e3) / HBM,
            stored_hbm_util_pct=100 * stored / (ms / 1e3) / HBM)
    block = pallas_delta.head_block(h, dk, dv)
    fn = jax.jit(lambda s: _moved_only(s, block), donate_argnums=(0,))
    _, _, ms = _rounds(fn, n(5, slots, h, dk, dv))
    say(form="moved_only", heads=h, dk=dk, dv=dv, block=block, ms=ms,
        hbm_util_pct=100 * moved / (ms / 1e3) / HBM,
        stored_hbm_util_pct=100 * stored / (ms / 1e3) / HBM)


def step_and_sweep(say, probe, key, s_row=None):
    """A forms probe's step lines (``probe``: ``probe_gdn_forms`` or
    ``probe_kda_forms``, for its op's ``MIX``, ``ATTRS``, ``inputs``, sizes
    and ``BLOCKS``): 96 rows in place, the state donated, in both forms; then
    the kernel alone by heads a grid step.  ``s_row`` (1, H, Dk, Dv) is every
    row's matrices (drawn where None)."""
    h, dk, dv = probe.HEADS
    streams, weights = probe.inputs(key, probe.SLOTS, 1, "bfloat16")
    if s_row is None:
        s_row = 0.1 * jax.random.normal(key, (1, h, dk, dv), jnp.float32)
    conv = h * (2 * dk + dv)
    state = (jnp.zeros((probe.SLOTS, probe.K - 1, conv), jnp.bfloat16),
             jnp.tile(s_row, (probe.SLOTS, 1, 1, 1)))
    # a row's matrices and its bfloat16 tail, read once and written once
    moved = probe.SLOTS * 2 * (h * dk * dv * 4 + (probe.K - 1) * conv * 2)
    step_lines(say, probe.MIX, probe.ATTRS, streams, weights, state, moved)
    sweep(say, probe.SLOTS, h, dk, dv, probe.PER_HEAD, probe.BLOCKS)


def main():
    say = chip_or_exit("probe_delta_step")
    import probe_gdn_forms
    import probe_kda_forms

    for probe in (probe_gdn_forms, probe_kda_forms):
        step_and_sweep(say, probe, jax.random.PRNGKey(58))


if __name__ == "__main__":
    sys.exit(main())
