"""``ops.kda`` (``KimiDeltaAttention``): the three forms of one mathematics
agree with the recurrence written out here a token at a time, and the points a
serving path leans on hold to the bit: padding, an inactive row and a slot's
first chunk do to the state and the convolution's tail exactly what they say.

Tolerance 2e-5 on outputs of order 1: everything is float32 on the CPU, the
forms differ in the order of their sums (the chunked form solves a triangular
system a block where the recurrence corrects the state token by token).
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, obs
from mxnet_tpu.ops import kda
from mxnet_tpu.registry import get_op

ATOL = 2e-5
H, D, K = 2, 8, 4
W = H * D
ATTRS = dict(num_heads=H, head_dim=D, conv_kernel=K, eps=1e-5)


def weights(seed=0):
    """conv_weight, A_log, dt_bias, out_norm_gamma: decays of about 0.3 to
    0.95 a step."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return 0.5 * f(3 * W, K), 0.5 * f(H), f(W) - 1.0, 1.0 + 0.1 * f(D)


def streams(b, t, seed=1):
    """query, key, value, decay, beta, gate; beta's pre-activation of std 2,
    so that 2 sigmoid(.) passes 1 on half the tokens."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return (f(b, t, W), f(b, t, W), f(b, t, W), 2 * f(b, t, W),
            2 * f(b, t, H), f(b, t, W))


def carried(b, seed=2):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(b, K - 1, 3 * W)), jnp.float32),
            jnp.asarray(r.normal(size=(b, H, D, D)), jnp.float32))


# ``kda.mix`` as ONE program a signature, as a serving program holds it (an
# eager call compiles each of its primitives apart at every new shape): for
# the tests that compare values with the recurrence at this file's 8 x 8
# heads.  Those that compare bits, patch the module or read what a call left
# behind (``STEP_PATH``, the dispatch counter) call ``kda.mix`` itself.
mix = jax.jit(functools.partial(kda.mix, ATTRS))


def cut(xs, lo, hi):
    return tuple(x[:, lo:hi] for x in xs)


def plain(xs, w, beta_scale=2.0):
    """The module docstring's equations written out, from zero state, a token
    at a time: ``(out, S_T)``."""
    q, k, v, decay, beta, gate = xs
    conv_w, a_log, dt_bias, gamma = w
    b, t, _ = q.shape

    def conv(x, wt):
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        return jax.nn.silu(sum(xp[:, i:i + t] * wt[:, i] for i in range(K)))

    qc, kc, vc = (conv(x, conv_w[i * W:(i + 1) * W]).reshape(b, t, H, D)
                  for i, x in enumerate((q, k, v)))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    qc, kc = unit(qc), unit(kc)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        decay + dt_bias).reshape(b, t, H, D)
    bt = jax.nn.sigmoid(beta) * beta_scale

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., :, None]
        nu = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))
        s = s + k_t[..., :, None] * nu[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s) / np.sqrt(D)

    with jax.default_matmul_precision("highest"):
        s, o = jax.lax.scan(step, jnp.zeros((b, H, D, D), jnp.float32),
                            tuple(jnp.moveaxis(x, 1, 0)
                                  for x in (qc, kc, vc, g, bt)))
    o = jnp.moveaxis(o, 0, 1)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * gamma
    return o.reshape(b, t, W) * jax.nn.sigmoid(gate), s


def by_chunks(xs, w, sizes, width, state=None):
    """The sequence as chunks of ``sizes`` real tokens, each padded to
    ``width``, the state and the tail carried from one to the next."""
    b = xs[0].shape[0]
    state = carried(b) if state is None else state  # void at pos0 == 0
    outs, pos = [], 0
    for n in sizes:
        part = tuple(jnp.pad(x[:, pos:pos + n],
                             ((0, 0), (0, width - n), (0, 0))) for x in xs)
        out, state, _ = mix(*part, *w, state=state,
                            pos0=jnp.full((b,), pos, jnp.int32),
                            nvalid=jnp.full((b,), n, jnp.int32))
        outs.append(out[:, :n])
        pos += n
    return jnp.concatenate(outs, 1), state


def by_token(xs, w, state, lo, hi):
    """Tokens ``lo .. hi`` one at a time through the decode form."""
    outs, b = [], xs[0].shape[0]
    for i in range(lo, hi):
        out, state, rows = kda.mix(ATTRS, *cut(xs, i, i + 1), *w,
                                   state=state, active=jnp.ones(b, jnp.int32))
        assert int(rows) == b
        outs.append(out)
    return jnp.concatenate(outs, 1), state


def close(a, b, atol=ATOL):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) < atol


@pytest.mark.parametrize("t", [
    2 * kda.BLOCK + 13,     # two whole blocks and a ragged one
    2 * kda.BLOCK,          # the sequence ends on a block's edge
    kda.SUB,                # one sub-block, shorter than a block
    kda.BLOCK + 1,          # one token past a block's edge
    1,                      # one token from nothing
    kda.SUB + 5,            # a sub-block's edge inside a ragged block
])
def test_a_whole_sequence_is_the_recurrence(t):
    xs, w = streams(2, t), weights()
    want, s = plain(xs, w)
    got, (tail, state), rows = mix(*xs, *w)
    assert close(got, want) and close(state, s) and int(rows) == 2
    assert float(jnp.max(jnp.abs(want))) > 0.5
    # the tail is the last K - 1 rows of [query | key | value], zeros before
    # the sequence
    raw = jnp.pad(jnp.concatenate(xs[:3], -1), ((0, 0), (K - 1, 0), (0, 0)))
    assert np.array_equal(np.asarray(tail), np.asarray(raw[:, -(K - 1):]))


def test_beta_passes_one_and_matters(monkeypatch):
    xs, w = streams(2, 40), weights()
    assert kda.BETA_SCALE == 2.0
    assert float(jnp.mean(2 * jax.nn.sigmoid(xs[4]) > 1.0)) > 0.3
    doubled, _, _ = kda.mix(ATTRS, *xs, *w)
    assert close(doubled, plain(xs, w)[0])
    monkeypatch.setattr(kda, "BETA_SCALE", 1.0)
    got, _, _ = kda.mix(ATTRS, *xs, *w)
    assert close(got, plain(xs, w, 1.0)[0])
    assert not close(got, doubled, 1e-2)


@pytest.mark.parametrize("sizes,width", [
    ((64, 20, 25), 64),     # a whole chunk of one block, then two padded
    ((3, 2, 1, 30), 32),    # chunks shorter than the convolution's kernel
    ((77,), 96),            # one padded chunk holds it all, over a block's edge
    ((128, 13), 128),       # a chunk of two whole blocks
])
def test_chunks_carry_state_and_tail_across_their_edges(sizes, width):
    """Chunked prefill equals the whole sequence's pass: the state AND the
    convolution's tail cross every edge, padding is the identity for both,
    and a chunk at position 0 voids what the slot held."""
    t = sum(sizes)
    xs, w = streams(2, t), weights()
    want, s = plain(xs, w)
    got, (tail, state) = by_chunks(xs, w, sizes, width)
    assert close(got, want) and close(state, s)
    whole_tail = mix(*xs, *w)[1][0]
    assert np.array_equal(np.asarray(tail), np.asarray(whole_tail))
    # a tail not carried shows at once: the second chunk from a zero tail
    if len(sizes) > 1:
        first = sizes[0]
        _, st, _ = mix(*cut(xs, 0, first), *w)
        nxt = cut(xs, first, first + sizes[1])
        args = dict(pos0=jnp.full((2,), first, jnp.int32),
                    nvalid=jnp.full((2,), sizes[1], jnp.int32))
        kept, _, _ = mix(*nxt, *w, state=st, **args)
        lost, _, _ = mix(*nxt, *w,
                         state=(jnp.zeros_like(st[0]), st[1]), **args)
        assert close(kept, want[:, first:first + sizes[1]])
        assert not close(lost[:, :1], kept[:, :1], 1e-3)


def test_padding_is_the_identity_to_the_bit():
    xs, w, state = streams(2, 32), weights(), carried(2)
    pos0 = jnp.asarray([5, 9], jnp.int32)
    padded = kda.mix(ATTRS, *xs, *w, state=state, pos0=pos0,
                     nvalid=jnp.asarray([11, 32], jnp.int32))
    exact = kda.mix(ATTRS, *cut(xs, 0, 11), *w, state=state, pos0=pos0,
                    nvalid=jnp.asarray([11, 11], jnp.int32))
    assert close(padded[0][0, :11], exact[0][0], 1e-6)
    assert close(padded[1][1][0], exact[1][1][0], 1e-6)
    assert np.array_equal(np.asarray(padded[1][0][0]),
                          np.asarray(exact[1][0][0]))
    # no real token: the state and the tail come back as they went in
    _, same, _ = kda.mix(ATTRS, *xs, *w, state=state, pos0=pos0,
                         nvalid=jnp.zeros((2,), jnp.int32))
    assert np.array_equal(np.asarray(same[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(same[1]), np.asarray(state[1]))


def test_a_chunk_at_position_zero_starts_from_nothing():
    xs, w = streams(2, 8), weights()
    n = jnp.full((2,), 8, jnp.int32)
    dirty, _, _ = kda.mix(ATTRS, *xs, *w, state=carried(2),
                          pos0=jnp.zeros((2,), jnp.int32), nvalid=n)
    clean, _, _ = kda.mix(ATTRS, *xs, *w)
    assert close(dirty, clean)
    later, _, _ = kda.mix(ATTRS, *xs, *w, state=carried(2),
                          pos0=jnp.asarray([0, 8], jnp.int32), nvalid=n)
    assert close(later[0], clean[0]) and not close(later[1], clean[1], 1e-2)


@pytest.fixture(params=["elementwise", "kernel"])
def step_form(request, monkeypatch):
    """The decode step's two forms: as the CPU takes it at this file's 8 x 8
    heads, and ``ops.pallas_delta``'s kernel through the interpreter at heads
    of 64 x 64, a width it tiles."""
    if request.param == "elementwise":
        yield request.param
        return
    me = sys.modules[__name__]
    monkeypatch.setattr(me, "D", 64)
    monkeypatch.setattr(me, "W", H * 64)
    monkeypatch.setattr(me, "ATTRS", dict(ATTRS, head_dim=64))
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield request.param


def test_steps_continue_a_chunk(step_form):
    xs, w = streams(2, 50), weights()
    want, s = plain(xs, w)
    _, state, _ = kda.mix(ATTRS, *cut(xs, 0, 37), *w)
    got, state = by_token(xs, w, state, 37, 50)
    assert kda.STEP_PATH["last"] == step_form
    assert close(got, want[:, 37:]) and close(state[1], s)


def test_an_inactive_row_comes_out_of_a_step_as_it_went_in(step_form):
    xs, w, state = streams(3, 1), weights(), carried(3)
    _, new, rows = kda.mix(ATTRS, *xs, *w, state=state,
                           active=jnp.asarray([1, 0, 1], jnp.int32))
    assert kda.STEP_PATH["last"] == step_form
    assert int(rows) == 2
    for leaf, old in zip(new, state):
        assert np.array_equal(np.asarray(leaf[1]), np.asarray(old[1]))
        assert not np.array_equal(np.asarray(leaf[0]), np.asarray(old[0]))


def _step_counts(op):
    counter = obs.registry.counter("mx_delta_step_dispatch_total",
                                   labels=("op", "path"))
    return {path: counter.labels(op=op, path=path).get()
            for path in ("kernel", "elementwise")}


def test_the_step_is_dispatched_by_backend_mesh_and_shape(monkeypatch):
    """``mx_delta_step_dispatch_total{op, path}``: the CPU as it is and a
    sharded executor count ``elementwise``, the interpreter counts
    ``kernel`` (8 x 8 heads ``elementwise`` there too), and a decode
    program's recorded steps say what its three delta layers took."""
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.test_utils import delta_toy_lm

    me = sys.modules[__name__]
    xs8, w8, state8 = streams(2, 1), weights(), carried(2)
    monkeypatch.setattr(me, "D", 64)
    monkeypatch.setattr(me, "W", H * 64)
    attrs = dict(ATTRS, head_dim=64)
    xs, w, state = streams(2, 1), weights(), carried(2)
    on = jnp.ones(2, jnp.int32)

    def took(interpret, attrs, xs, w, state):
        before = _step_counts(kda.OP_NAME)
        with config.overrides(MXNET_PALLAS_INTERPRET=interpret):
            kda.mix(attrs, *xs, *w, state=state, active=on)
        after = _step_counts(kda.OP_NAME)
        return {p: after[p] - before[p] for p in after}

    assert took("0", attrs, xs, w, state) == {"kernel": 0, "elementwise": 1}
    assert took("1", attrs, xs, w, state) == {"kernel": 1, "elementwise": 0}
    before = _step_counts(kda.OP_NAME)
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        kda.step(*(jnp.zeros(s) for s in (
            (2, H, 64), (2, H, 64), (2, H, 64), (2, H, 64), (2, H),
            (2, H, 64, 64))), mesh_active=True)
    assert _step_counts(kda.OP_NAME)["elementwise"] \
        == before["elementwise"] + 1
    assert took("1", ATTRS, xs8, w8, state8) \
        == {"kernel": 0, "elementwise": 1}
    for interpret, path in (("1", "kernel"), ("0", "elementwise")):
        with config.overrides(MXNET_PALLAS_INTERPRET=interpret):
            pred = DecodePredictor(
                *delta_toy_lm("kda"), cache_len=64, temperature=0.0,
                paged=True, page_tokens=4, prefill_chunk=8)
            art = pred.decode_artifact(pred.paged_batch_state(2))
        assert art.meta["delta_steps"] == [path] * 3
        assert ("pallas_call" in art.jaxpr_text) == (path == "kernel")


@pytest.mark.parametrize("form", ["sequence", "chunks", "steps"])
def test_log_decays_of_minus_twenty_a_step_stay_finite(form):
    """exp(A_log) softplus(.) = 20 a channel a step: every exponent the
    chunked form takes is a difference of running sums, <= 0; nothing
    overflows, and the outputs are the recurrence's."""
    xs, w = list(streams(2, 70)), list(weights())
    xs[3] = jnp.full_like(xs[3], 20.0)              # softplus(20) = 20
    w[1], w[2] = jnp.zeros((H,), jnp.float32), jnp.zeros((W,), jnp.float32)
    want, s = plain(xs, w)
    if form == "sequence":
        got, (_, state), _ = mix(*xs, *w)
    elif form == "chunks":
        got, (_, state) = by_chunks(xs, w, (64, 6), 64)
    else:
        _, st, _ = kda.mix(ATTRS, *cut(xs, 0, 33), *w)
        got, (_, state) = by_token(xs, w, st, 33, 70)
        want = want[:, 33:]
    assert bool(jnp.all(jnp.isfinite(got))) \
        and bool(jnp.all(jnp.isfinite(state)))
    assert close(got, want) and close(state, s)


def test_the_tail_keeps_its_type_whatever_the_streams():
    """A float32 stream over a bfloat16 tail (a chunk program whose residual
    stream was widened upstream): the row goes back as it is carried."""
    xs, w = streams(1, 8), weights()
    state = (jnp.zeros((1, K - 1, 3 * W), jnp.bfloat16),
             jnp.zeros((1, H, D, D), jnp.float32))
    _, new, _ = kda.mix(ATTRS, *xs, *w, state=state,
                        pos0=jnp.asarray([4], jnp.int32),
                        nvalid=jnp.asarray([8], jnp.int32))
    assert new[0].dtype == jnp.bfloat16 and new[1].dtype == jnp.float32


def test_the_registered_op_infers_its_shapes_and_differentiates():
    data = [mx.sym.Variable(n) for n in ("q", "k", "v", "f", "b", "g")]
    node = mx.sym.KimiDeltaAttention(*data, name="kda", **ATTRS)
    assert node.list_arguments() == [
        "q", "k", "v", "f", "b", "g", "kda_conv_weight", "kda_A_log",
        "kda_dt_bias", "kda_out_norm_gamma"]
    assert get_op(kda.OP_NAME).list_arguments(ATTRS) == kda.ARGUMENTS
    shape, beta = (2, 12, W), (2, 12, H)
    args, outs, _ = node.infer_shape(q=shape, k=shape, v=shape, f=shape,
                                     b=beta, g=shape)
    assert args == [shape] * 4 + [beta, shape, (3 * W, K), (H,), (W,), (D,)]
    assert outs == [shape]
    xs, w = streams(2, 12), weights()
    loss = lambda q: jnp.sum(kda.mix(ATTRS, q, *xs[1:], *w)[0] ** 2)
    grad = jax.jit(jax.grad(loss))(xs[0])
    assert grad.shape == shape and bool(jnp.all(jnp.isfinite(grad))) \
        and float(jnp.abs(grad).max()) > 0
    with pytest.raises(ValueError, match="not \\(B, T"):
        kda.mix(ATTRS, *xs[:4], xs[4][..., :1], xs[5], *w)
