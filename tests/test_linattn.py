"""``ops.linattn`` (``LightningAttention``): the three forms of one
mathematics agree with the recurrence written out here, and the two points a
serving path leans on hold to the bit: padding and an inactive row do not
advance the state.

Tolerance 1e-5 on outputs of order 1: everything is float32 on the CPU, the
forms differ in the order of their sums (the chunked form multiplies decays
laid out as a matrix where the recurrence multiplies step by step).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import linattn
from mxnet_tpu.registry import get_op

ATOL = 1e-5
H, D, Q = 4, 8, 8
ATTRS = dict(num_heads=H, head_dim=D, chunk_size=Q, slope_scale=0.7,
             rope_theta=10000.0, eps=1e-6)


def weights(seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return 1.0 + 0.1 * f(D), 1.0 + 0.1 * f(D), 1.0 + 0.1 * f(H * D)


def streams(b, t, seed=1):
    r = np.random.default_rng(seed)
    return tuple(jnp.asarray(r.normal(size=(b, t, H * D)), jnp.float32)
                 for _ in range(4))


def carried(b, seed=2):
    return (jnp.asarray(np.random.default_rng(seed).normal(
        size=(b, H, D, D)), jnp.float32),)


def cut(xs, lo, hi):
    return tuple(x[:, lo:hi] for x in xs)


def by_token(xs, w, state, pos0):
    """The recurrence one token at a time through the decode form."""
    outs = []
    b = xs[0].shape[0]
    for i in range(xs[0].shape[1]):
        out, state, _ = linattn.mix(
            ATTRS, *cut(xs, i, i + 1), *w, state=state,
            pos0=jnp.full((b,), pos0 + i, jnp.int32),
            active=jnp.ones(b, jnp.int32))
        outs.append(out)
    return jnp.concatenate(outs, 1), state


def plain(xs, w):
    """The module docstring's equations written out, from zero state, in
    float64."""
    q, k, v, g = (np.asarray(x, np.float64) for x in xs)
    qg, kg, og = (np.asarray(x, np.float64) for x in w)
    b, t, _ = q.shape
    rms = lambda x, gain: x / np.sqrt(np.mean(x * x, -1, keepdims=True)
                                      + 1e-6) * gain
    half = D // 2
    inv = 10000.0 ** (-np.arange(half) / half)

    def turn(x):            # (b, t, H, D), position = the token's index
        ang = np.arange(t)[:, None] * inv[None, :]
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        a, c = x[..., :half], x[..., half:]
        return np.concatenate([a * cos - c * sin, c * cos + a * sin], -1)

    qh = turn(rms(q.reshape(b, t, H, D), qg))
    kh = turn(rms(k.reshape(b, t, H, D), kg))
    vh = v.reshape(b, t, H, D)
    lam = np.exp(-0.7 * 2.0 ** (-8.0 * np.arange(1, H + 1) / H))
    s = np.zeros((b, H, D, D))
    out = np.zeros((b, t, H, D))
    for i in range(t):
        s = lam[None, :, None, None] * s \
            + kh[:, i, :, :, None] * vh[:, i, :, None, :]
        out[:, i] = np.einsum("bhd,bhde->bhe", qh[:, i], s) / np.sqrt(D)
    out = rms(out.reshape(b, t, H * D), og)
    return out / (1.0 + np.exp(-g)), s


def test_the_decays_are_the_published_sequence():
    s = linattn.slopes(32)
    assert s[0] == pytest.approx(2.0 ** -0.25) and s[-1] == 2.0 ** -8
    assert np.allclose(s[1:] / s[:-1], 2.0 ** -0.25)
    # head h of 8: 2^-(h + 1); scaled by the layer's depth factor
    assert np.allclose(linattn.slopes(8), 2.0 ** -np.arange(1, 9))
    assert np.allclose(linattn.slopes(8, 0.5), 2.0 ** -np.arange(2, 10))
    # layer 9 of 32 as decoder_lm scales it
    assert np.allclose(linattn.slopes(32, 1 - 9 / 31 + 1e-5)[0],
                       2.0 ** -0.25 * (22 / 31 + 1e-5))


def test_a_whole_sequence_matches_the_equations_written_out():
    xs, w = streams(2, 21), weights()
    out, (state,), rows = linattn.mix(ATTRS, *xs, *w)
    want, want_state = plain(xs, w)
    assert np.abs(np.asarray(out) - want).max() < ATOL
    assert np.abs(np.asarray(state) - want_state).max() < ATOL
    assert int(rows) == 2 and state.dtype == jnp.float32


@pytest.mark.parametrize("t,nvalid", [(16, 16), (16, 11), (8, 3), (24, 17)])
def test_chunk_and_token_forms_agree_from_a_random_carried_state(t, nvalid):
    xs, w, state = streams(3, t), weights(), carried(3)
    pos0 = jnp.asarray([5, 40, 8], jnp.int32)
    out, new, _ = linattn.mix(ATTRS, *xs, *w, state=state, pos0=pos0,
                              nvalid=jnp.full((3,), nvalid, jnp.int32))
    # row by row: the token form takes one first position for all rows
    for r in range(3):
        one = tuple(x[r:r + 1] for x in xs)
        want, want_state = by_token(cut(one, 0, nvalid), w,
                                    (state[0][r:r + 1],), int(pos0[r]))
        assert np.abs(np.asarray(out[r:r + 1, :nvalid])
                      - np.asarray(want)).max() < ATOL
        assert np.abs(np.asarray(new[0][r:r + 1])
                      - np.asarray(want_state[0])).max() < ATOL


def test_a_sequence_is_its_chunks_with_the_state_carried():
    xs, w = streams(1, 24), weights()
    whole, (end,), _ = linattn.mix(ATTRS, *xs, *w)
    state = (jnp.ones((1, H, D, D), jnp.float32),)      # void at pos0 == 0
    outs = []
    for lo, width, n in ((0, 16, 16), (16, 16, 8)):
        pad = tuple(jnp.pad(x[:, lo:lo + n], ((0, 0), (0, width - n), (0, 0)),
                            constant_values=3.0) for x in xs)
        out, state, _ = linattn.mix(
            ATTRS, *pad, *w, state=state, pos0=jnp.asarray([lo], jnp.int32),
            nvalid=jnp.asarray([n], jnp.int32))
        outs.append(out[:, :n])
    assert np.abs(np.asarray(jnp.concatenate(outs, 1))
                  - np.asarray(whole)).max() < ATOL
    assert np.abs(np.asarray(state[0]) - np.asarray(end)).max() < ATOL


def test_padding_is_the_identity_on_the_state():
    xs, w, state = streams(2, 16), weights(), carried(2)
    pos0 = jnp.asarray([16, 32], jnp.int32)
    _, padded, _ = linattn.mix(ATTRS, *xs, *w, state=state, pos0=pos0,
                               nvalid=jnp.asarray([11, 16], jnp.int32))
    _, exact, _ = linattn.mix(ATTRS, *cut(xs, 0, 11), *w, state=state,
                              pos0=pos0,
                              nvalid=jnp.asarray([11, 11], jnp.int32))
    assert np.abs(np.asarray(padded[0][0]) - np.asarray(exact[0][0])).max() \
        < 1e-6
    # no real token: the state comes back as it went in
    _, same, _ = linattn.mix(ATTRS, *xs, *w, state=state, pos0=pos0,
                             nvalid=jnp.zeros((2,), jnp.int32))
    assert np.array_equal(np.asarray(same[0]), np.asarray(state[0]))


def test_a_chunk_at_position_zero_starts_from_zero_state():
    xs, w = streams(2, 8), weights()
    n = jnp.full((2,), 8, jnp.int32)
    dirty, _, _ = linattn.mix(ATTRS, *xs, *w, state=carried(2),
                              pos0=jnp.zeros((2,), jnp.int32), nvalid=n)
    clean, _, _ = linattn.mix(ATTRS, *xs, *w)
    assert np.abs(np.asarray(dirty) - np.asarray(clean)).max() < ATOL
    later, _, _ = linattn.mix(ATTRS, *xs, *w, state=carried(2),
                              pos0=jnp.asarray([0, 8], jnp.int32), nvalid=n)
    assert np.abs(np.asarray(later[1]) - np.asarray(clean[1])).max() > 1e-2


def test_an_inactive_row_comes_out_of_a_step_as_it_went_in():
    xs, w, state = streams(3, 1), weights(), carried(3)
    _, new, rows = linattn.mix(ATTRS, *xs, *w, state=state,
                               pos0=jnp.asarray([3, 9, 4], jnp.int32),
                               active=jnp.asarray([1, 0, 1], jnp.int32))
    assert int(rows) == 2
    assert np.array_equal(np.asarray(new[0][1]), np.asarray(state[0][1]))
    assert not np.array_equal(np.asarray(new[0][0]), np.asarray(state[0][0]))


def test_the_flags_take_their_inputs_away():
    xs, w = streams(1, 9), weights()
    bare = dict(ATTRS, qk_norm=False, output_norm=False, output_gate=False,
                rotary=False)
    out, (state,), _ = linattn.mix(bare, *xs[:3])
    q, k, v = (np.asarray(x, np.float64).reshape(1, 9, H, D)
               for x in xs[:3])
    lam = np.exp(-linattn.slopes(H, 0.7))
    s, want = np.zeros((1, H, D, D)), []
    for i in range(9):
        s = lam[None, :, None, None] * s \
            + k[:, i, :, :, None] * v[:, i, :, None, :]
        want.append(np.einsum("bhd,bhde->bhe", q[:, i], s) / np.sqrt(D))
    assert np.abs(np.asarray(out)
                  - np.stack(want, 1).reshape(1, 9, -1)).max() < ATOL
    op = get_op(linattn.OP_NAME)
    assert op.list_arguments(bare) == ["query", "key", "value"]
    assert op.list_arguments(ATTRS) == [
        "query", "key", "value", "gate", "q_norm_gamma", "k_norm_gamma",
        "out_norm_gamma"]


def test_the_registered_op_infers_its_shapes_and_differentiates():
    data = [mx.sym.Variable(n) for n in "qkvg"]
    node = mx.sym.LightningAttention(*data, name="lin", **ATTRS)
    assert node.list_arguments() == [
        "q", "k", "v", "g", "lin_q_norm_gamma", "lin_k_norm_gamma",
        "lin_out_norm_gamma"]
    shape = (2, 12, H * D)
    args, outs, _ = node.infer_shape(q=shape, k=shape, v=shape, g=shape)
    assert args == [shape] * 4 + [(D,), (D,), (H * D,)] and outs == [shape]
    xs, w = streams(2, 12), weights()
    loss = lambda q: jnp.sum(linattn.mix(ATTRS, q, *xs[1:], *w)[0] ** 2)
    grad = jax.jit(jax.grad(loss))(xs[0])
    assert grad.shape == shape and bool(jnp.all(jnp.isfinite(grad))) \
        and float(jnp.abs(grad).max()) > 0
