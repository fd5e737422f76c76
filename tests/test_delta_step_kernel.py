"""The delta rule's decode step as one Pallas kernel (``ops/pallas_delta.py``)
against ``ops.kda._step`` and the mask ``mix`` laid over it, in interpret mode
on the CPU (the same kernel Mosaic compiles on a TPU; its compiles for a
described v5e are in ``tests/test_pallas_decode.py``, the one file that
describes the chip).

Tolerance.  Everything is float32 on both sides and only the order of the two
sums over the key dim may differ, so ``o`` and ``S`` are held to ``RTOL`` 1e-5
of the reference's largest magnitude.  The planted faults (the two halves of
the rule in the wrong order, a sum taken from the undecayed state, ``nu``
without ``beta``, the ``(q . k) nu`` term dropped) each move a result by at
least a hundred times that, so the tolerance separates right from wrong.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import config, obs
from mxnet_tpu.ops import kda
from mxnet_tpu.ops import pallas_delta as pdl

RTOL = 1e-5
# (heads, Dk, Dv, one decay a head, heads a grid step): Kimi delta attention's
# proportions (a decay a channel, square) and Gated DeltaNet's (one decay a
# head, 96 x 192), each once with the rule's own block and once with a head
# count that is no multiple of the block (the last step reads past the
# state's edge)
SHAPES = {
    "kda_128x128": (4, 128, 128, False, None),
    "gdn_96x192": (3, 96, 192, True, None),
    "kda_5_heads_by_2": (5, 128, 128, False, 2),
    "gdn_7_heads_by_3": (7, 96, 192, True, 3),
}
CELLS = ["kda_128x128", "gdn_96x192"]
ACTIVE = (1, 0, 1)


def operands(shape, dtype="float32", seed=0, g=None, beta=None, norm=1.0):
    """``q`` (unit, scaled), ``k`` (unit), ``v``, ``g`` (log-decays of about
    -0.05 to -3), ``beta`` in (0, 2) and ``s`` for ``len(ACTIVE)`` rows."""
    h, dk, dv, per_head, _ = SHAPES[shape]
    b = len(ACTIVE)
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    q = kda._unit(f(b, h, dk), 1e-6) * dk ** -0.5
    k = kda._unit(f(b, h, dk), 1e-6)
    gs = (b, h, 1) if per_head else (b, h, dk)
    g = -jnp.exp(f(*gs) - 1.0) if g is None else jnp.full(gs, g, jnp.float32)
    beta = 2 * jax.nn.sigmoid(2 * f(b, h)) if beta is None \
        else jnp.full((b, h), beta, jnp.float32)
    s = f(b, h, dk, dv)
    s = s * norm / jnp.sqrt(jnp.sum(s * s, axis=(-2, -1), keepdims=True)) \
        if norm != 1.0 else s
    cast = lambda x: x.astype(dtype)
    return cast(q), cast(k), cast(f(b, h, dv)), cast(g), cast(beta), s


def reference(q, k, v, g, beta, s, active=ACTIVE, step=kda._step):
    """``step`` over float32 copies of the operands, then ``mix``'s mask."""
    f32 = lambda x: x.astype(jnp.float32)
    o, new = step(f32(q), f32(k), f32(v), f32(g), f32(beta), s)
    on = jnp.asarray(active, bool)
    return o, jnp.where(on[:, None, None, None], new, s)


def kernel(shape, ops, active=ACTIVE):
    return pdl.delta_step(*ops, jnp.asarray(active, jnp.int32),
                          block=SHAPES[shape][4], interpret=True)


def off(got, want, rows=None):
    """The largest difference as a share of the reference's largest value,
    over the active rows (every row where ``rows`` is given)."""
    on = np.asarray(ACTIVE if rows is None else rows, bool)
    got, want = np.asarray(got)[on], np.asarray(want)[on]
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_is_the_step_with_its_mask(shape, dtype):
    ops = operands(shape, dtype)
    (o, s), (o_want, s_want) = kernel(shape, ops), reference(*ops)
    assert o.dtype == jnp.float32 and s.dtype == jnp.float32
    assert off(o, o_want) < RTOL and off(s, s_want, (1, 1, 1)) < RTOL


@pytest.mark.parametrize("shape", CELLS)
def test_an_inactive_row_is_untouched_to_the_bit_whatever_its_operands(shape):
    """Row 1 is inactive and its q, k, v, g, beta are not numbers: its
    matrices come out bit for bit, and the rows beside it are the step's."""
    ops = operands(shape)
    junk = lambda x: x.at[1].set(jnp.nan)
    q, k, v, g, beta, s = ops
    o, new = kernel(shape, (junk(q), junk(k), junk(v), junk(g), junk(beta),
                            s))
    o_want, s_want = reference(*ops)
    assert np.array_equal(np.asarray(new[1]).view(np.uint32),
                          np.asarray(s[1]).view(np.uint32))
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(new)))
    assert off(o, o_want) < RTOL and off(new, s_want) < RTOL
    assert not np.array_equal(np.asarray(new[0]), np.asarray(s[0]))


@pytest.mark.parametrize("shape", CELLS)
def test_log_decays_of_minus_twenty_stay_finite(shape):
    ops = operands(shape, g=-20.0)
    (o, s), (o_want, s_want) = kernel(shape, ops), reference(*ops)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    assert off(o, o_want) < RTOL and off(s, s_want) < RTOL


@pytest.mark.parametrize("shape", CELLS)
def test_beta_near_two_over_a_state_of_norm_ten(shape):
    """A transition with an eigenvalue near -1 over matrices of Frobenius
    norm 10: nothing is lost to the order of the sums."""
    ops = operands(shape, beta=1.999, norm=10.0)
    (o, s), (o_want, s_want) = kernel(shape, ops), reference(*ops)
    assert off(o, o_want) < RTOL and off(s, s_want) < RTOL


def _decay_after_the_correction(q, k, v, g, beta, s):
    nu = beta[..., None] * (v - jnp.sum(k[..., :, None] * s, axis=-2))
    new = (s + k[..., :, None] * nu[..., None, :]) * jnp.exp(g)[..., :, None]
    return jnp.sum(q[..., :, None] * new, axis=-2), new


def _sum_over_the_undecayed_state(q, k, v, g, beta, s):
    sd = s * jnp.exp(g)[..., :, None]
    nu = beta[..., None] * (v - jnp.sum(k[..., :, None] * s, axis=-2))
    new = sd + k[..., :, None] * nu[..., None, :]
    return jnp.sum(q[..., :, None] * new, axis=-2), new


def _nu_without_beta(q, k, v, g, beta, s):
    return kda._step(q, k, v, g, jnp.ones_like(beta), s)


def _qk_nu_dropped(q, k, v, g, beta, s):
    sd = s * jnp.exp(g)[..., :, None]
    return jnp.sum(q[..., :, None] * sd, axis=-2), kda._step(q, k, v, g, beta,
                                                            s)[1]


FAULTS = [_decay_after_the_correction, _sum_over_the_undecayed_state,
          _nu_without_beta, _qk_nu_dropped]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("shape", CELLS)
def test_a_planted_fault_is_seen_at_the_tolerance(shape, fault):
    """The kernel is NOT the step with one of its pieces wrong: against each
    faulty step ``o`` or ``S`` is off by a hundred tolerances or more, where
    the sound step is inside one."""
    ops = operands(shape)
    o, s = kernel(shape, ops)
    o_bad, s_bad = reference(*ops, step=fault)
    assert max(off(o, o_bad), off(s, s_bad)) >= 100 * RTOL
    o_want, s_want = reference(*ops)
    assert off(o, o_want) < RTOL and off(s, s_want) < RTOL


@pytest.mark.parametrize("h,dk,dv,admitted", [
    (64, 128, 128, True),       # solar-open2-250b
    (30, 96, 192, True),        # olmo-hybrid-7b
    (2, 8, 64, True),           # half a lane tile of values
    (2, 8, 8, False),           # a sixteenth of one: the padding outweighs
    (2, 100, 128, False),       # a key dim of no whole sublane tiles
    (1, 1024, 1024, False),     # one head's buffers past the budget
])
def test_the_shape_rule(h, dk, dv, admitted):
    assert pdl.supported(h, dk, dv) is admitted


@pytest.mark.parametrize("h,dk,dv,block", [
    (64, 128, 128, 32), (30, 96, 192, 15), (5, 128, 128, 5),
    (33, 128, 128, 17), (2, 512, 512, 2), (3, 512, 512, 2)])
def test_the_tile_rule_follows_from_the_shape(h, dk, dv, block):
    """The most heads whose four buffers fit the budget, then the fewest
    that keep the number of steps."""
    assert pdl.head_block(h, dk, dv) == block
    assert 4 * block * pdl._head_bytes(dk, dv) <= pdl._STATE_BUFFERS


def _counted(op, path):
    return obs.registry.counter(
        "mx_delta_step_dispatch_total", "", labels=("op", "path")).labels(
            op=op, path=path).get()


def test_a_refused_shape_takes_the_elementwise_form():
    """8 x 8 matrices under the interpreter: ``kda.step`` counts and takes
    ``_step`` with the mask, to the bit."""
    r = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    ops = (f(3, 2, 8), f(3, 2, 8), f(3, 2, 8), -jnp.abs(f(3, 2, 8)),
           jnp.abs(f(3, 2)), f(3, 2, 8, 8))
    before = _counted("Probe", "elementwise")
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        o, s = kda.step(*ops, jnp.asarray(ACTIVE, jnp.int32), op="Probe")
    assert kda.STEP_PATH["last"] == "elementwise"
    assert _counted("Probe", "elementwise") == before + 1
    o_want, s_want = reference(*ops)
    assert np.array_equal(np.asarray(o), np.asarray(o_want))
    assert np.array_equal(np.asarray(s), np.asarray(s_want))


def test_the_state_has_to_be_float32():
    q, k, v, g, beta, s = operands("kda_128x128")
    with pytest.raises(ValueError, match="float32"):
        pdl.delta_step(q, k, v, g, beta, s.astype(jnp.bfloat16),
                       interpret=True)
