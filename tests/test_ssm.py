"""``ops.ssm`` (``SelectiveSSM``): the three forms of one mathematics agree
with a token-by-token recurrence written out here, and the two points a
serving path leans on hold to the bit: padding and an inactive row do not
advance the state.

Tolerance 2e-5 on outputs of order 1: everything is float32 on the CPU, the
forms differ in the order of their sums (the chunked scan multiplies decays
where the recurrence multiplies step by step).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import ssm
from mxnet_tpu.registry import get_op

ATOL = 2e-5
H, P, N, G, K, Q = 4, 8, 6, 2, 4, 8
ATTRS = dict(num_heads=H, head_dim=P, state_size=N, n_groups=G,
             conv_kernel=K, chunk_size=Q)
(_, _, _, _, _), (D_SSM, CONV_DIM, IN_DIM) = ssm.dims(ATTRS)


def weights(seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return (0.5 * f(CONV_DIM, K), 0.1 * f(CONV_DIM),
            jnp.asarray(r.uniform(-4.0, -1.0, H), jnp.float32),    # dt_bias
            jnp.asarray(r.uniform(0.0, 2.0, H), jnp.float32),      # A_log
            1.0 + 0.1 * f(H), 1.0 + 0.1 * f(D_SSM))


def stream(b, t, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(b, t, IN_DIM)),
                       jnp.float32)


def carried(b, seed=2):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(b, K - 1, CONV_DIM)), jnp.float32),
            jnp.asarray(r.normal(size=(b, H, P, N)), jnp.float32))


def by_token(data, w, state):
    """The recurrence one token at a time through the decode form."""
    outs = []
    for i in range(data.shape[1]):
        out, state, _ = ssm.mix(ATTRS, data[:, i:i + 1], *w, state=state,
                                active=jnp.ones(data.shape[0], jnp.int32))
        outs.append(out)
    return jnp.concatenate(outs, 1), state


def plain(data, w):
    """The module docstring's equations written out, from zero state."""
    conv_w, conv_b, dt_bias, a_log, d_skip, gamma = (np.asarray(x, np.float64)
                                                     for x in w)
    x = np.asarray(data, np.float64)
    b, t, _ = x.shape
    z, xbc, dt = x[..., :D_SSM], x[..., D_SSM:D_SSM + CONV_DIM], \
        x[..., D_SSM + CONV_DIM:]
    pad = np.concatenate([np.zeros((b, K - 1, CONV_DIM)), xbc], 1)
    xbc = sum(pad[:, i:i + t] * conv_w[:, i] for i in range(K)) + conv_b
    xbc = xbc / (1 + np.exp(-xbc))
    xs = xbc[..., :D_SSM].reshape(b, t, H, P)
    bm = xbc[..., D_SSM:D_SSM + G * N].reshape(b, t, G, N)
    cm = xbc[..., D_SSM + G * N:].reshape(b, t, G, N)
    dt = np.log1p(np.exp(dt + dt_bias))
    a = -np.exp(a_log)
    s = np.zeros((b, H, P, N))
    y = np.zeros((b, t, H, P))
    for i in range(t):
        for k in range(H):
            g = k // (H // G)
            s[:, k] = np.exp(dt[:, i, k] * a[k])[:, None, None] * s[:, k] \
                + dt[:, i, k, None, None] * xs[:, i, k, :, None] \
                * bm[:, i, g, None, :]
            y[:, i, k] = np.einsum("bpn,bn->bp", s[:, k], cm[:, i, g]) \
                + d_skip[k] * xs[:, i, k]
    v = y.reshape(b, t, D_SSM) * (z / (1 + np.exp(-z)))
    v = v.reshape(b, t, G, D_SSM // G)
    v = v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)
    return v.reshape(b, t, D_SSM) * gamma, s


def test_a_whole_sequence_matches_the_equations_written_out():
    w, data = weights(), stream(2, 21)          # 21: not a multiple of Q
    out, (tail, s), rows = ssm.mix(ATTRS, data, *w)
    want, want_s = plain(data, w)
    assert np.allclose(out, want, atol=ATOL)
    assert np.allclose(s, want_s, atol=ATOL)
    assert np.array_equal(tail, data[:, -3:, D_SSM:D_SSM + CONV_DIM])
    assert int(rows) == 2


@pytest.mark.parametrize("t,nvalid", [(16, 16), (16, 11), (8, 3), (24, 17)])
def test_chunk_and_token_forms_agree_from_a_random_carried_state(t, nvalid):
    """A chunk padded past ``nvalid`` gives, at its real positions and in
    the state it leaves, what the recurrence gives one token at a time over
    the real tokens alone."""
    w, data, state = weights(), stream(1, t), carried(1)
    out, new, _ = ssm.mix(ATTRS, data, *w, state=state,
                          pos0=jnp.asarray([5]), nvalid=jnp.asarray([nvalid]))
    want, want_state = by_token(data[:, :nvalid], w, state)
    assert np.allclose(out[:, :nvalid], want, atol=ATOL)
    assert np.allclose(new[1], want_state[1], atol=ATOL)
    # the tail is taken at the last real token, to the bit
    assert np.array_equal(new[0], want_state[0])


def test_padding_is_the_identity_on_the_state():
    """The same real tokens in a wider chunk leave the same state, bit for
    bit: at a padded position the decay is exp(0) and the input 0."""
    w, state = weights(), carried(1)
    data = stream(1, 24)
    pos0, n = jnp.asarray([9]), jnp.asarray([10])
    _, narrow, _ = ssm.mix(ATTRS, data[:, :16], *w, state=state, pos0=pos0,
                           nvalid=n)
    _, wide, _ = ssm.mix(ATTRS, data, *w, state=state, pos0=pos0, nvalid=n)
    for a, b in zip(narrow, wide):
        assert np.array_equal(a, b)


def test_a_chunk_at_position_zero_starts_from_zero_state():
    w, data = weights(), stream(1, 16)
    n = jnp.asarray([13])
    out, new, _ = ssm.mix(ATTRS, data, *w, state=carried(1),
                          pos0=jnp.asarray([0]), nvalid=n)
    zeros = tuple(jnp.zeros_like(a) for a in carried(1))
    want, want_new, _ = ssm.mix(ATTRS, data, *w, state=zeros,
                                pos0=jnp.asarray([3]), nvalid=n)
    assert np.array_equal(out[:, :13], want[:, :13])
    for a, b in zip(new, want_new):
        assert np.array_equal(a, b)


def test_an_inactive_row_comes_out_of_a_step_as_it_went_in():
    w, data, state = weights(), stream(3, 1), carried(3)
    active = jnp.asarray([1, 0, 1], jnp.int32)
    _, new, rows = jax.jit(lambda d, s, a: ssm.mix(
        ATTRS, d, *w, state=s, active=a))(data, state, active)
    assert int(rows) == 2
    for before, after in zip(state, new):
        assert np.array_equal(before[1], after[1])          # to the bit
        assert not np.array_equal(before[0], after[0])
        assert not np.array_equal(before[2], after[2])


def test_the_state_is_kept_in_the_type_the_node_states():
    attrs = dict(ATTRS, state_dtype="bfloat16")
    w, data = weights(), stream(2, 1)
    tail, s = carried(2)
    _, new, _ = ssm.mix(attrs, data, *w, state=(tail, s.astype(jnp.bfloat16)),
                        active=jnp.ones(2, jnp.int32))
    assert new[1].dtype == jnp.bfloat16 and new[0].dtype == jnp.float32
    assert [s for s, _ in ssm.state_avals(attrs, 5, jnp.float32)] \
        == [(5, K - 1, CONV_DIM), (5, H, P, N)]


def test_the_registered_op_infers_its_shapes_and_differentiates():
    op = get_op("SelectiveSSM")
    attrs = op.parse_attrs(ATTRS)
    net = mx.sym.SelectiveSSM(mx.sym.Variable("data"), name="m", **ATTRS)
    assert net.list_arguments() == [
        "data", "m_conv_weight", "m_conv_bias", "m_dt_bias", "m_A_log",
        "m_D", "m_norm_gamma"]
    args, outs, _ = net.infer_shape(data=(2, 9, IN_DIM))
    assert args[1:] == [(CONV_DIM, K), (CONV_DIM,), (H,), (H,), (H,),
                        (D_SSM,)] and outs == [(2, 9, D_SSM)]
    w, data = weights(), stream(2, 9)
    from mxnet_tpu.registry import OpContext

    f = lambda *xs: op.fcompute(attrs, list(xs), [], OpContext())[0][0].sum()
    grads = jax.jit(jax.grad(f, argnums=tuple(range(7))))(data, *w)
    assert all(np.isfinite(g).all() and float(jnp.abs(g).max()) > 0
               for g in grads)
    with pytest.raises(ValueError, match="input width"):
        ssm.mix(ATTRS, data[..., :-1], *w)
