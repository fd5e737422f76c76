"""``ops.gdn`` (``GatedDeltaNet``): the three forms of one mathematics agree
with the recurrence written out here a token at a time, the matrix chunk form
agrees with ``ops.kda``'s channel form fed the same decay broadcast over a
head's channels, and the points a serving path leans on hold to the bit:
padding, an inactive row and a slot's first chunk do to the state and the
convolution's tail exactly what they say.

Tolerance 2e-5 on outputs of order 1: everything is float32 on the CPU, the
forms differ in the order of their sums (the chunked form solves a triangular
system a block where the recurrence corrects the state token by token).
"""
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, obs
from mxnet_tpu.ops import gdn, kda
from mxnet_tpu.registry import get_op

ATOL = 2e-5
H, K = 2, 4


class Dims:
    """One case's sizes: Olmo-Hybrid's (96, 192) a head, or a square one."""

    def __init__(self, dk, dv):
        self.dk, self.dv = dk, dv
        self.kw, self.vw = H * dk, H * dv
        self.conv = 2 * self.kw + self.vw
        self.attrs = dict(num_heads=H, key_head_dim=dk, value_head_dim=dv,
                          conv_kernel=K, eps=1e-6)


@pytest.fixture(params=[(96, 192), (8, 8)], ids=["96x192", "8x8"])
def d(request):
    return Dims(*request.param)


def weights(d, seed=0):
    """conv_weight, A_log, dt_bias, out_norm_gamma: decays of about 0.3 to
    0.95 a step."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return 0.5 * f(d.conv, K), 0.5 * f(H), f(H) - 1.0, 1.0 + 0.1 * f(d.dv)


def streams(d, b, t, seed=1):
    """query, key, value, decay, beta, gate; beta's pre-activation of std 2,
    so that 2 sigmoid(.) passes 1 on half the tokens."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return (f(b, t, d.kw), f(b, t, d.kw), f(b, t, d.vw), 2 * f(b, t, H),
            2 * f(b, t, H), f(b, t, d.vw))


def carried(d, b, seed=2):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(b, K - 1, d.conv)), jnp.float32),
            jnp.asarray(r.normal(size=(b, H, d.dk, d.dv)), jnp.float32))


_PROGRAMS = {}


def mix(d, *args, **kw):
    """``gdn.mix`` as ONE program a signature, as a serving program holds it
    (an eager call compiles each of its primitives apart at every new
    shape): for the tests that compare values with the recurrence.  Those
    that compare bits, patch the module or read what a call left behind
    (``STEP_PATH``, the dispatch counter) call ``gdn.mix`` itself."""
    key = (d.dk, d.dv)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(functools.partial(gdn.mix, d.attrs))
    return _PROGRAMS[key](*args, **kw)


def cut(xs, lo, hi):
    return tuple(x[:, lo:hi] for x in xs)


def plain(d, xs, w, beta_scale=2.0):
    """The module docstring's equations written out, from zero state, a token
    at a time: ``(out, S_T)``."""
    q, k, v, decay, beta, gate = xs
    conv_w, a_log, dt_bias, gamma = w
    b, t, _ = q.shape

    def conv(x, wt):
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        return jax.nn.silu(sum(xp[:, i:i + t] * wt[:, i] for i in range(K)))

    qc = conv(q, conv_w[:d.kw]).reshape(b, t, H, d.dk)
    kc = conv(k, conv_w[d.kw:2 * d.kw]).reshape(b, t, H, d.dk)
    vc = conv(v, conv_w[2 * d.kw:]).reshape(b, t, H, d.dv)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    qc, kc = unit(qc), unit(kc)
    g = -jnp.exp(a_log) * jax.nn.softplus(decay + dt_bias)     # (b, t, H)
    bt = jax.nn.sigmoid(beta) * beta_scale

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        nu = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))
        s = s + k_t[..., :, None] * nu[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s) / np.sqrt(d.dk)

    with jax.default_matmul_precision("highest"):
        s, o = jax.lax.scan(step, jnp.zeros((b, H, d.dk, d.dv), jnp.float32),
                            tuple(jnp.moveaxis(x, 1, 0)
                                  for x in (qc, kc, vc, g, bt)))
    o = jnp.moveaxis(o, 0, 1)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * gamma
    return o.reshape(b, t, d.vw) * jax.nn.silu(gate), s


def by_chunks(d, xs, w, sizes, width, state=None):
    """The sequence as chunks of ``sizes`` real tokens, each padded to
    ``width``, the state and the tail carried from one to the next."""
    b = xs[0].shape[0]
    state = carried(d, b) if state is None else state   # void at pos0 == 0
    outs, pos = [], 0
    for n in sizes:
        part = tuple(jnp.pad(x[:, pos:pos + n],
                             ((0, 0), (0, width - n), (0, 0))) for x in xs)
        out, state, _ = mix(d, *part, *w, state=state,
                            pos0=jnp.full((b,), pos, jnp.int32),
                            nvalid=jnp.full((b,), n, jnp.int32))
        outs.append(out[:, :n])
        pos += n
    return jnp.concatenate(outs, 1), state


def by_token(d, xs, w, state, lo, hi):
    """Tokens ``lo .. hi`` one at a time through the decode form."""
    outs, b = [], xs[0].shape[0]
    for i in range(lo, hi):
        out, state, rows = gdn.mix(d.attrs, *cut(xs, i, i + 1), *w,
                                   state=state, active=jnp.ones(b, jnp.int32))
        assert int(rows) == b
        outs.append(out)
    return jnp.concatenate(outs, 1), state


def close(a, b, atol=ATOL):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) < atol


@pytest.mark.parametrize("t", [
    2 * gdn.BLOCK + 13,     # two whole blocks and a ragged one: 64 does not
                            # divide T
    2 * gdn.BLOCK,          # the sequence ends on a block's edge
    gdn.BLOCK + 1,          # one token past a block's edge
    1,                      # one token from nothing
])
def test_a_whole_sequence_is_the_recurrence(d, t):
    xs, w = streams(d, 2, t), weights(d)
    want, s = plain(d, xs, w)
    got, (tail, state), rows = mix(d, *xs, *w)
    assert close(got, want) and close(state, s) and int(rows) == 2
    assert float(jnp.max(jnp.abs(want))) > 0.5
    assert state.shape == (2, H, d.dk, d.dv) and state.dtype == jnp.float32
    # the tail is the last K - 1 rows of [query | key | value], zeros before
    # the sequence
    raw = jnp.pad(jnp.concatenate(xs[:3], -1), ((0, 0), (K - 1, 0), (0, 0)))
    assert np.array_equal(np.asarray(tail), np.asarray(raw[:, -(K - 1):]))


def test_beta_passes_one_and_matters(d, monkeypatch):
    xs, w = streams(d, 2, 40), weights(d)
    assert gdn.BETA_SCALE == 2.0
    doubled, _, _ = gdn.mix(d.attrs, *xs, *w)
    assert close(doubled, plain(d, xs, w)[0])
    monkeypatch.setattr(gdn, "BETA_SCALE", 1.0)
    got, _, _ = gdn.mix(d.attrs, *xs, *w)
    assert close(got, plain(d, xs, w, 1.0)[0])
    assert not close(got, doubled, 1e-2)


@pytest.mark.parametrize("sizes,width", [
    ((64, 20, 25), 64),     # a whole chunk of one block, then two padded
    ((3, 2, 1, 30), 32),    # chunks shorter than the convolution's kernel
    ((77,), 96),            # one padded chunk, over a block's edge
    ((128, 13), 128),       # a chunk of two whole blocks
])
def test_chunks_carry_state_and_tail_across_their_edges(d, sizes, width):
    """Chunked prefill equals the whole sequence's pass: the state AND the
    convolution's tail cross every edge, padding is the identity for both,
    and a chunk at position 0 voids what the slot held."""
    t = sum(sizes)
    xs, w = streams(d, 2, t), weights(d)
    want, s = plain(d, xs, w)
    got, (tail, state) = by_chunks(d, xs, w, sizes, width)
    assert close(got, want) and close(state, s)
    whole_tail = mix(d, *xs, *w)[1][0]
    assert np.array_equal(np.asarray(tail), np.asarray(whole_tail))
    # a tail not carried shows at once: the second chunk from a zero tail
    if len(sizes) > 1:
        first = sizes[0]
        _, st, _ = mix(d, *cut(xs, 0, first), *w)
        nxt = cut(xs, first, first + sizes[1])
        args = dict(pos0=jnp.full((2,), first, jnp.int32),
                    nvalid=jnp.full((2,), sizes[1], jnp.int32))
        kept, _, _ = mix(d, *nxt, *w, state=st, **args)
        lost, _, _ = mix(d, *nxt, *w,
                         state=(jnp.zeros_like(st[0]), st[1]), **args)
        assert close(kept, want[:, first:first + sizes[1]])
        assert not close(lost[:, :1], kept[:, :1], 1e-3)


def test_padding_is_the_identity_to_the_bit(d):
    xs, w, state = streams(d, 2, 32), weights(d), carried(d, 2)
    pos0 = jnp.asarray([5, 9], jnp.int32)
    padded = gdn.mix(d.attrs, *xs, *w, state=state, pos0=pos0,
                     nvalid=jnp.asarray([11, 32], jnp.int32))
    exact = gdn.mix(d.attrs, *cut(xs, 0, 11), *w, state=state, pos0=pos0,
                    nvalid=jnp.asarray([11, 11], jnp.int32))
    assert close(padded[0][0, :11], exact[0][0], 1e-6)
    assert close(padded[1][1][0], exact[1][1][0], 1e-6)
    assert np.array_equal(np.asarray(padded[1][0][0]),
                          np.asarray(exact[1][0][0]))
    # no real token: the state and the tail come back as they went in
    _, same, _ = gdn.mix(d.attrs, *xs, *w, state=state, pos0=pos0,
                         nvalid=jnp.zeros((2,), jnp.int32))
    assert np.array_equal(np.asarray(same[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(same[1]), np.asarray(state[1]))


def test_a_chunk_at_position_zero_starts_from_nothing(d):
    xs, w = streams(d, 2, 8), weights(d)
    n = jnp.full((2,), 8, jnp.int32)
    dirty, _, _ = gdn.mix(d.attrs, *xs, *w, state=carried(d, 2),
                          pos0=jnp.zeros((2,), jnp.int32), nvalid=n)
    clean, _, _ = gdn.mix(d.attrs, *xs, *w)
    assert close(dirty, clean)
    later, _, _ = gdn.mix(d.attrs, *xs, *w, state=carried(d, 2),
                          pos0=jnp.asarray([0, 8], jnp.int32), nvalid=n)
    assert close(later[0], clean[0]) and not close(later[1], clean[1], 1e-2)


@pytest.fixture(params=["elementwise", "kernel"])
def step_form(request, d):
    """The form the decode step takes: as the CPU takes it, or with the
    interpreter running ``ops.pallas_delta``'s kernel where it tiles the
    heads (96 x 192; 8 x 8 stays elementwise)."""
    if request.param == "elementwise":
        yield request.param
        return
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield "kernel" if d.dk == 96 else "elementwise"


def test_steps_continue_a_chunk(d, step_form):
    xs, w = streams(d, 2, 50), weights(d)
    want, s = plain(d, xs, w)
    _, state, _ = gdn.mix(d.attrs, *cut(xs, 0, 37), *w)
    got, state = by_token(d, xs, w, state, 37, 50)
    assert kda.STEP_PATH["last"] == step_form
    assert close(got, want[:, 37:]) and close(state[1], s)


def test_an_inactive_row_comes_out_of_a_step_as_it_went_in(d, step_form):
    xs, w, state = streams(d, 3, 1), weights(d), carried(d, 3)
    _, new, rows = gdn.mix(d.attrs, *xs, *w, state=state,
                           active=jnp.asarray([1, 0, 1], jnp.int32))
    assert kda.STEP_PATH["last"] == step_form
    assert int(rows) == 2
    for leaf, old in zip(new, state):
        assert np.array_equal(np.asarray(leaf[1]), np.asarray(old[1]))
        assert not np.array_equal(np.asarray(leaf[0]), np.asarray(old[0]))


def test_the_step_is_dispatched_by_backend_mesh_and_shape(d):
    """``mx_delta_step_dispatch_total{op, path}``: the CPU as it is and a
    sharded executor count ``elementwise``, the interpreter counts ``kernel``
    where the heads tile, and a decode program's recorded steps say what its
    three Gated DeltaNet layers took."""
    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.test_utils import delta_toy_lm

    counter = obs.registry.counter("mx_delta_step_dispatch_total",
                                   labels=("op", "path"))
    counts = lambda: {p: counter.labels(op=gdn.OP_NAME, path=p).get()
                      for p in ("kernel", "elementwise")}
    xs, w, state = streams(d, 2, 1), weights(d), carried(d, 2)

    def took(interpret, step=None):
        before = counts()
        with config.overrides(MXNET_PALLAS_INTERPRET=interpret):
            (step or (lambda: gdn.mix(d.attrs, *xs, *w, state=state,
                                      active=jnp.ones(2, jnp.int32))))()
        return {p: n - before[p] for p, n in counts().items()}

    tiled = d.dk == 96
    assert took("0") == {"kernel": 0, "elementwise": 1}
    assert took("1") == {"kernel": int(tiled), "elementwise": int(not tiled)}
    sharded = lambda: kda.step(*(jnp.zeros(s) for s in (
        (2, H, d.dk), (2, H, d.dk), (2, H, d.dv), (2, H, 1), (2, H),
        (2, H, d.dk, d.dv))), op=gdn.OP_NAME, mesh_active=True)
    assert took("1", sharded) == {"kernel": 0, "elementwise": 1}
    if not tiled:
        return
    for interpret, path in (("1", "kernel"), ("0", "elementwise")):
        with config.overrides(MXNET_PALLAS_INTERPRET=interpret):
            pred = DecodePredictor(
                *delta_toy_lm("gdn"), cache_len=64, temperature=0.0,
                paged=True, page_tokens=4, prefill_chunk=8)
            art = pred.decode_artifact(pred.paged_batch_state(2))
        assert art.meta["delta_steps"] == [path] * 3
        assert ("pallas_call" in art.jaxpr_text) == (path == "kernel")


@pytest.mark.parametrize("form", ["sequence", "chunks", "steps"])
def test_log_decays_of_minus_twenty_a_step_stay_finite(d, form):
    """exp(A_log) softplus(.) = 20 a head a step: every exponent the chunked
    form takes is a difference of running sums, <= 0; nothing overflows, and
    the outputs are the recurrence's."""
    xs, w = list(streams(d, 2, 70)), list(weights(d))
    xs[3] = jnp.full_like(xs[3], 20.0)              # softplus(20) = 20
    w[1], w[2] = jnp.zeros((H,), jnp.float32), jnp.zeros((H,), jnp.float32)
    want, s = plain(d, xs, w)
    if form == "sequence":
        got, (_, state), _ = mix(d, *xs, *w)
    elif form == "chunks":
        got, (_, state) = by_chunks(d, xs, w, (64, 6), 64)
    else:
        _, st, _ = gdn.mix(d.attrs, *cut(xs, 0, 33), *w)
        got, (_, state) = by_token(d, xs, w, st, 33, 70)
        want = want[:, 33:]
    assert bool(jnp.all(jnp.isfinite(got))) \
        and bool(jnp.all(jnp.isfinite(state)))
    assert close(got, want) and close(state, s)


def test_the_tail_keeps_its_type_whatever_the_streams(d):
    """A float32 stream over a bfloat16 tail (a chunk program whose residual
    stream was widened upstream): the row goes back as it is carried."""
    xs, w = streams(d, 1, 8), weights(d)
    state = (jnp.zeros((1, K - 1, d.conv), jnp.bfloat16),
             jnp.zeros((1, H, d.dk, d.dv), jnp.float32))
    _, new, _ = gdn.mix(d.attrs, *xs, *w, state=state,
                        pos0=jnp.asarray([4], jnp.int32),
                        nvalid=jnp.asarray([8], jnp.int32))
    assert new[0].dtype == jnp.bfloat16 and new[1].dtype == jnp.float32


@pytest.mark.parametrize("t", [gdn.BLOCK, 2 * gdn.BLOCK + 13])
def test_the_matrix_chunk_form_is_kdas_channel_form_at_one_decay_a_head(t):
    """The two delta rules tied together: ``ops.kda._chunked`` (a decay a
    CHANNEL, elementwise sub-blocks) fed this module's decay broadcast over a
    head's key channels gives what the matrix form gives, outputs and end
    state, from a carried state, at Dk = Dv."""
    r = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    b, dd = 2, 16
    q, k = kda._unit(f(b, t, H, dd), 1e-6), kda._unit(f(b, t, H, dd), 1e-6)
    v, s0 = f(b, t, H, dd), f(b, H, dd, dd)
    g = -jax.nn.softplus(f(b, t, H) - 1.0)
    beta = 2 * jax.nn.sigmoid(2 * f(b, t, H))
    o_m, s_m = jax.jit(gdn._chunked)(q, k, v, g, beta, s0)
    o_c, s_c = jax.jit(kda._chunked)(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, s0)
    assert close(o_m, o_c) and close(s_m, s_c)
    assert float(jnp.max(jnp.abs(o_m))) > 0.5


def test_the_chunk_form_takes_no_product_a_channel():
    """The delta rule's chunk is matrix products: no call of kda's
    ``_decayed_products``, and no tensor of (block, block, key dim) in the
    program."""
    assert "_decayed_products" not in inspect.getsource(gdn)
    d = Dims(96, 192)
    xs, w = streams(d, 1, 2 * gdn.BLOCK), weights(d)
    text = str(jax.make_jaxpr(lambda *a: gdn.mix(d.attrs, *a)[0])(*xs, *w))
    assert "%d,%d,%d]" % (gdn.BLOCK, gdn.BLOCK, d.dk) not in text
    assert "%d,%d]" % (gdn.BLOCK, gdn.BLOCK) in text
    assert text.count("triangular_solve") == 1


def test_the_registered_op_infers_its_shapes_and_differentiates(d):
    data = [mx.sym.Variable(n) for n in ("q", "k", "v", "a", "b", "g")]
    node = mx.sym.GatedDeltaNet(*data, name="gdn", **d.attrs)
    assert node.list_arguments() == [
        "q", "k", "v", "a", "b", "g", "gdn_conv_weight", "gdn_A_log",
        "gdn_dt_bias", "gdn_out_norm_gamma"]
    assert get_op(gdn.OP_NAME).list_arguments(d.attrs) == gdn.ARGUMENTS
    key, val, head = (2, 12, d.kw), (2, 12, d.vw), (2, 12, H)
    args, outs, _ = node.infer_shape(q=key, k=key, v=val, a=head, b=head,
                                     g=val)
    assert args == [key, key, val, head, head, val, (d.conv, K), (H,), (H,),
                    (d.dv,)]
    assert outs == [val]
    # jax.grad through the chunk form's solve: finite and not nothing
    xs, w = streams(d, 2, 12), weights(d)
    loss = lambda q: jnp.sum(gdn.mix(d.attrs, q, *xs[1:], *w)[0] ** 2)
    grad = jax.jit(jax.grad(loss))(xs[0])
    assert grad.shape == key and bool(jnp.all(jnp.isfinite(grad))) \
        and float(jnp.abs(grad).max()) > 0
    with pytest.raises(ValueError, match="not \\(B, T"):
        gdn.mix(d.attrs, *xs[:4], xs[4][..., :1], xs[5], *w)
