"""Systematic operator sweep: every registered op gets a numpy-reference
forward check, and every differentiable op a finite-difference gradient
check (the reference's test strategy at test_operator.py scale, SURVEY §4).

Structure: table-driven sweeps per op family + a coverage meta-test that
fails when a newly registered op is not claimed by any sweep/test file.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_forward)


def _rng(seed=0):
    return np.random.RandomState(seed)


# ---------------------------------------------------------------------------
# Unary elementwise: (numpy reference, input transform to keep the domain
# valid and away from non-differentiable kinks, grad-checkable)
# ---------------------------------------------------------------------------
UNARY = {
    "abs":        (np.abs,            lambda x: x + np.sign(x) * 0.3, True),
    "arccos":     (np.arccos,         lambda x: np.clip(x, -0.9, 0.9), True),
    "arccosh":    (np.arccosh,        lambda x: np.abs(x) + 1.1, True),
    "arcsin":     (np.arcsin,         lambda x: np.clip(x, -0.9, 0.9), True),
    "arcsinh":    (np.arcsinh,        None, True),
    "arctan":     (np.arctan,         None, True),
    "arctanh":    (np.arctanh,        lambda x: np.clip(x, -0.9, 0.9), True),
    "cbrt":       (np.cbrt,           lambda x: np.abs(x) + 0.2, True),
    "ceil":       (np.ceil,           lambda x: x + 0.25, False),
    "cos":        (np.cos,            None, True),
    "cosh":       (np.cosh,           None, True),
    "degrees":    (np.degrees,        None, True),
    "erf":        (lambda x: np.vectorize(__import__("math").erf)(x).astype(np.float32),
                   None, True),
    "exp":        (np.exp,            None, True),
    "expm1":      (np.expm1,          None, True),
    "fix":        (np.fix,            lambda x: x + 0.25, False),
    "floor":      (np.floor,          lambda x: x + 0.25, False),
    "gamma":      (lambda x: np.vectorize(__import__("math").gamma)(x).astype(np.float32),
                   lambda x: np.abs(x) + 1.0, True),
    "gammaln":    (lambda x: np.vectorize(__import__("math").lgamma)(x).astype(np.float32),
                   lambda x: np.abs(x) + 1.0, True),
    "identity":   (lambda x: x,       None, True),
    "log":        (np.log,            lambda x: np.abs(x) + 0.5, True),
    "log10":      (np.log10,          lambda x: np.abs(x) + 0.5, True),
    "log1p":      (np.log1p,          lambda x: np.abs(x), True),
    "log2":       (np.log2,           lambda x: np.abs(x) + 0.5, True),
    "logical_not": (lambda x: (x == 0).astype(np.float32),
                    lambda x: np.round(x), False),
    "negative":   (np.negative,       None, True),
    "radians":    (np.radians,        None, True),
    "rcbrt":      (lambda x: 1.0 / np.cbrt(x), lambda x: np.abs(x) + 0.5, True),
    "reciprocal": (np.reciprocal,     lambda x: np.abs(x) + 0.5, True),
    "relu":       (lambda x: np.maximum(x, 0), lambda x: x + np.sign(x) * 0.3, True),
    "rint":       (np.rint,           lambda x: x + 0.25, False),
    "round":      (np.round,          lambda x: x + 0.25, False),
    "rsqrt":      (lambda x: 1.0 / np.sqrt(x), lambda x: np.abs(x) + 0.5, True),
    "sigmoid":    (lambda x: 1 / (1 + np.exp(-x)), None, True),
    "sign":       (np.sign,           lambda x: x + np.sign(x) * 0.3, False),
    "sin":        (np.sin,            None, True),
    "sinh":       (np.sinh,           None, True),
    "softrelu":   (lambda x: np.log1p(np.exp(x)), None, True),
    "softsign":   (lambda x: x / (1 + np.abs(x)), lambda x: x + np.sign(x) * 0.3, True),
    "sqrt":       (np.sqrt,           lambda x: np.abs(x) + 0.2, True),
    "square":     (np.square,         None, True),
    "tan":        (np.tan,            lambda x: np.clip(x, -1.2, 1.2), True),
    "tanh":       (np.tanh,           None, True),
    "trunc":      (np.trunc,          lambda x: x + 0.25, False),
}


@pytest.mark.parametrize("op_name", sorted(UNARY))
def test_unary_forward_and_grad(op_name):
    np_fn, domain, diff = UNARY[op_name]
    x = _rng(hash(op_name) % 1000).uniform(-2, 2, size=(3, 4)).astype(np.float32)
    if domain is not None:
        x = domain(x).astype(np.float32)

    out = getattr(nd, op_name)(nd.array(x)).asnumpy()
    assert_almost_equal(out, np_fn(x).astype(np.float32), rtol=1e-4, atol=1e-5)

    if diff:
        s = getattr(sym, op_name)(sym.Variable("x"))
        check_numeric_gradient(s, {"x": x}, rtol=0.05, atol=1e-2)


# ---------------------------------------------------------------------------
# Binary elementwise + broadcast + comparison
# ---------------------------------------------------------------------------
BINARY = {
    "_add": np.add, "_plus": np.add, "_sub": np.subtract, "_minus": np.subtract,
    "_mul": np.multiply, "_div": np.divide, "_mod": np.mod,
    "_power": lambda a, b: np.power(np.abs(a) + 0.5, b),
    "_hypot": np.hypot, "_maximum": np.maximum, "_minimum": np.minimum,
    "_equal": lambda a, b: (a == b).astype(np.float32),
    "_not_equal": lambda a, b: (a != b).astype(np.float32),
    "_greater": lambda a, b: (a > b).astype(np.float32),
    "_greater_equal": lambda a, b: (a >= b).astype(np.float32),
    "_lesser": lambda a, b: (a < b).astype(np.float32),
    "_lesser_equal": lambda a, b: (a <= b).astype(np.float32),
}


@pytest.mark.parametrize("op_name", sorted(BINARY))
def test_binary_forward(op_name):
    np_fn = BINARY[op_name]
    rng = _rng(3)
    a = rng.uniform(0.5, 2, size=(3, 4)).astype(np.float32)
    b = rng.uniform(0.5, 2, size=(3, 4)).astype(np.float32)
    if "power" in op_name:
        a = np.abs(a) + 0.5
        ref = np.power(a, b)
    else:
        ref = np_fn(a, b)
    out = getattr(nd, op_name)(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(out, ref.astype(np.float32), rtol=1e-4, atol=1e-5)


BROADCAST = ["add", "plus", "sub", "minus", "mul", "div", "mod", "power",
             "hypot", "maximum", "minimum", "equal", "not_equal", "greater",
             "greater_equal", "lesser", "lesser_equal"]


@pytest.mark.parametrize("suffix", BROADCAST)
def test_broadcast_binary_forward(suffix):
    np_fns = {
        "add": np.add, "plus": np.add, "sub": np.subtract,
        "minus": np.subtract, "mul": np.multiply, "div": np.divide,
        "mod": np.mod, "power": np.power, "hypot": np.hypot,
        "maximum": np.maximum, "minimum": np.minimum,
        "equal": lambda a, b: (a == b).astype(np.float32),
        "not_equal": lambda a, b: (a != b).astype(np.float32),
        "greater": lambda a, b: (a > b).astype(np.float32),
        "greater_equal": lambda a, b: (a >= b).astype(np.float32),
        "lesser": lambda a, b: (a < b).astype(np.float32),
        "lesser_equal": lambda a, b: (a <= b).astype(np.float32),
    }
    rng = _rng(5)
    a = rng.uniform(0.5, 2, size=(2, 3, 4)).astype(np.float32)
    b = rng.uniform(0.5, 2, size=(1, 3, 1)).astype(np.float32)
    out = getattr(nd, "broadcast_" + suffix)(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(out, np_fns[suffix](a, b).astype(np.float32),
                        rtol=1e-4, atol=1e-5)


SCALAR = {
    "_plus_scalar": lambda x, s: x + s,
    "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x,
    "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s,
    "_rdiv_scalar": lambda x, s: s / x,
    "_mod_scalar": lambda x, s: np.mod(x, s),
    "_rmod_scalar": lambda x, s: np.mod(s, x),
    "_power_scalar": lambda x, s: np.power(x, s),
    "_rpower_scalar": lambda x, s: np.power(s, x),
    "_hypot_scalar": lambda x, s: np.hypot(x, s),
    "_maximum_scalar": lambda x, s: np.maximum(x, s),
    "_minimum_scalar": lambda x, s: np.minimum(x, s),
    "_equal_scalar": lambda x, s: (x == s).astype(np.float32),
    "_not_equal_scalar": lambda x, s: (x != s).astype(np.float32),
    "_greater_scalar": lambda x, s: (x > s).astype(np.float32),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(np.float32),
    "_lesser_scalar": lambda x, s: (x < s).astype(np.float32),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(np.float32),
}


@pytest.mark.parametrize("op_name", sorted(SCALAR))
def test_scalar_ops_forward(op_name):
    np_fn = SCALAR[op_name]
    x = _rng(7).uniform(0.5, 2, size=(3, 4)).astype(np.float32)
    s = 1.5
    out = getattr(nd, op_name)(nd.array(x), scalar=s).asnumpy()
    assert_almost_equal(out, np_fn(x, s).astype(np.float32),
                        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------
REDUCE = {
    "sum": np.sum, "mean": np.mean, "prod": np.prod,
    "nansum": np.nansum, "nanprod": np.nanprod,
    "max": np.max, "min": np.min,
}


@pytest.mark.parametrize("op_name", sorted(REDUCE))
@pytest.mark.parametrize("axis,keepdims", [(None, False), (0, False),
                                           (1, True), ((0, 2), False)])
def test_reduce_forward(op_name, axis, keepdims):
    x = _rng(11).uniform(0.5, 1.5, size=(2, 3, 4)).astype(np.float32)
    if op_name.startswith("nan"):
        x.flat[::5] = np.nan
    kwargs = {"keepdims": keepdims}
    if axis is not None:
        kwargs["axis"] = axis
    out = getattr(nd, op_name)(nd.array(x), **kwargs).asnumpy()
    ref = REDUCE[op_name](x, axis=axis, keepdims=keepdims)
    assert_almost_equal(np.asarray(out), np.asarray(ref, np.float32),
                        rtol=1e-4, atol=1e-5)


def test_reduce_grads():
    x = _rng(13).uniform(0.5, 1.5, size=(3, 4)).astype(np.float32)
    for name in ("sum", "mean", "prod"):
        s = getattr(sym, name)(sym.Variable("x"), axis=1)
        check_numeric_gradient(s, {"x": x}, rtol=0.05, atol=1e-2)


def test_arg_reductions():
    x = _rng(17).uniform(-1, 1, size=(3, 5)).astype(np.float32)
    assert_almost_equal(nd.argmax(nd.array(x), axis=1).asnumpy(),
                        np.argmax(x, axis=1).astype(np.float32))
    assert_almost_equal(nd.argmin(nd.array(x), axis=1).asnumpy(),
                        np.argmin(x, axis=1).astype(np.float32))
    assert_almost_equal(nd.argmax_channel(nd.array(x)).asnumpy(),
                        np.argmax(x, axis=1).astype(np.float32))
    # norm: full-array Frobenius
    assert_almost_equal(nd.norm(nd.array(x)).asnumpy(),
                        np.array(np.linalg.norm(x), np.float32), rtol=1e-4)


def test_sum_axis_aliases():
    x = _rng(19).uniform(size=(2, 3, 4)).astype(np.float32)
    assert_almost_equal(nd.sum_axis(nd.array(x), axis=1).asnumpy(),
                        x.sum(axis=1), rtol=1e-4)
    assert_almost_equal(nd.max_axis(nd.array(x), axis=2).asnumpy(),
                        x.max(axis=2), rtol=1e-4)
    assert_almost_equal(nd.min_axis(nd.array(x), axis=0).asnumpy(),
                        x.min(axis=0), rtol=1e-4)
    assert_almost_equal(nd.broadcast_axis(nd.array(x[:, :1]), axis=1, size=3)
                        .asnumpy(), np.broadcast_to(x[:, :1], (2, 3, 4)),
                        rtol=1e-6)
    assert_almost_equal(nd.broadcast_axes(nd.array(x[:, :1]), axis=1, size=3)
                        .asnumpy(), np.broadcast_to(x[:, :1], (2, 3, 4)),
                        rtol=1e-6)
    assert_almost_equal(nd.broadcast_to(nd.array(x[:1]), shape=(2, 3, 4))
                        .asnumpy(), np.broadcast_to(x[:1], (2, 3, 4)),
                        rtol=1e-6)


# ---------------------------------------------------------------------------
# Matrix / shape ops
# ---------------------------------------------------------------------------
def test_dot_variants():
    rng = _rng(23)
    a = rng.normal(size=(3, 4)).astype(np.float32)
    b = rng.normal(size=(4, 5)).astype(np.float32)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)).asnumpy(), a @ b,
                        rtol=1e-4)
    assert_almost_equal(
        nd.dot(nd.array(a.T), nd.array(b), transpose_a=True).asnumpy(),
        a @ b, rtol=1e-4)
    assert_almost_equal(
        nd.dot(nd.array(a), nd.array(b.T), transpose_b=True).asnumpy(),
        a @ b, rtol=1e-4)
    s = sym.dot(sym.Variable("a"), sym.Variable("b"))
    check_numeric_gradient(s, {"a": a, "b": b}, rtol=0.05, atol=1e-2)


def test_batch_dot_transpose_flags():
    rng = _rng(29)
    a = rng.normal(size=(2, 3, 4)).astype(np.float32)
    b = rng.normal(size=(2, 4, 5)).astype(np.float32)
    ref = np.einsum("bij,bjk->bik", a, b)
    assert_almost_equal(nd.batch_dot(nd.array(a), nd.array(b)).asnumpy(),
                        ref, rtol=1e-4)
    at = np.transpose(a, (0, 2, 1))
    assert_almost_equal(
        nd.batch_dot(nd.array(at), nd.array(b), transpose_a=True).asnumpy(),
        ref, rtol=1e-4)


def test_shape_ops():
    rng = _rng(31)
    x = rng.normal(size=(2, 3, 4)).astype(np.float32)
    assert nd.expand_dims(nd.array(x), axis=1).shape == (2, 1, 3, 4)
    assert_almost_equal(nd.swapaxes(nd.array(x), dim1=0, dim2=2).asnumpy(),
                        np.swapaxes(x, 0, 2))
    assert_almost_equal(nd.flip(nd.array(x), axis=1).asnumpy(),
                        np.flip(x, axis=1))
    assert_almost_equal(nd.slice_axis(nd.array(x), axis=2, begin=1, end=3)
                        .asnumpy(), x[:, :, 1:3])
    assert_almost_equal(nd.slice(nd.array(x), begin=(0, 1, 0), end=(2, 3, 2))
                        .asnumpy(), x[0:2, 1:3, 0:2])
    assert_almost_equal(nd.tile(nd.array(x), reps=(1, 2, 1)).asnumpy(),
                        np.tile(x, (1, 2, 1)))
    assert_almost_equal(nd.repeat(nd.array(x), repeats=2, axis=1).asnumpy(),
                        np.repeat(x, 2, axis=1))
    assert_almost_equal(nd.reverse(nd.array(x), axis=1).asnumpy(),
                        np.flip(x, axis=1))


def test_init_like_ops():
    x = nd.array(_rng(37).normal(size=(2, 3)).astype(np.float32))
    assert_almost_equal(nd.zeros_like(x).asnumpy(), np.zeros((2, 3)))
    assert_almost_equal(nd.ones_like(x).asnumpy(), np.ones((2, 3)))
    assert_almost_equal(nd._zeros(shape=(2, 2)).asnumpy(), np.zeros((2, 2)))
    assert_almost_equal(nd._ones(shape=(2, 2)).asnumpy(), np.ones((2, 2)))
    assert_almost_equal(nd._arange(start=1, stop=7, step=2).asnumpy(),
                        np.arange(1, 7, 2, dtype=np.float32))


def test_copy_grad_add_identity():
    x = _rng(41).normal(size=(3,)).astype(np.float32)
    y = _rng(42).normal(size=(3,)).astype(np.float32)
    assert_almost_equal(nd._copy(nd.array(x)).asnumpy(), x)
    assert_almost_equal(nd._grad_add(nd.array(x), nd.array(y)).asnumpy(),
                        x + y, rtol=1e-6)
    assert_almost_equal(
        nd._identity_with_attr_like_rhs(nd.array(x), nd.array(y)).asnumpy(),
        x)
    assert_almost_equal(nd.stop_gradient(nd.array(x)).asnumpy(), x)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------
def test_softmax_ops():
    x = _rng(43).normal(size=(3, 5)).astype(np.float32)
    e = np.exp(x - x.max(axis=1, keepdims=True))
    p = e / e.sum(axis=1, keepdims=True)
    assert_almost_equal(nd.softmax(nd.array(x)).asnumpy(), p, rtol=1e-4)
    assert_almost_equal(nd.log_softmax(nd.array(x)).asnumpy(), np.log(p),
                        rtol=1e-4)
    assert_almost_equal(nd.SoftmaxActivation(nd.array(x)).asnumpy(), p,
                        rtol=1e-4)
    check_numeric_gradient(sym.softmax(sym.Variable("x")), {"x": x},
                           rtol=0.05, atol=1e-2)

    label = np.array([0, 2, 4], np.float32)
    out = nd.softmax_cross_entropy(nd.array(x), nd.array(label)).asnumpy()
    ref = -np.log(p[np.arange(3), label.astype(int)]).sum()
    assert_almost_equal(np.asarray(out).ravel(),
                        np.array([ref], np.float32), rtol=1e-4)


# ---------------------------------------------------------------------------
# Sampling: moments + determinism under fixed seed
# ---------------------------------------------------------------------------
SAMPLERS = {
    "uniform": dict(low=0.0, high=1.0, mean=0.5, std=np.sqrt(1 / 12.)),
    "normal": dict(loc=0.0, scale=1.0, mean=0.0, std=1.0),
    "random_uniform": dict(low=0.0, high=1.0, mean=0.5, std=np.sqrt(1 / 12.)),
    "random_normal": dict(loc=0.0, scale=1.0, mean=0.0, std=1.0),
    "random_exponential": dict(lam=1.0, mean=1.0, std=1.0),
    "random_gamma": dict(alpha=4.0, beta=1.0, mean=4.0, std=2.0),
    "random_poisson": dict(lam=4.0, mean=4.0, std=2.0),
    "random_negative_binomial": dict(k=8, p=0.5, mean=8.0, std=4.0),
    "random_generalized_negative_binomial":
        dict(mu=4.0, alpha=0.25, mean=4.0, std=np.sqrt(4 + 0.25 * 16)),
}


@pytest.mark.parametrize("op_name", sorted(SAMPLERS))
def test_sampler_moments(op_name):
    cfg = dict(SAMPLERS[op_name])
    mean, std = cfg.pop("mean"), cfg.pop("std")
    mx.random.seed(7)
    draw = getattr(nd, op_name)(shape=(40000,), **cfg).asnumpy()
    assert abs(draw.mean() - mean) < 5 * std / np.sqrt(draw.size) + 0.02
    assert abs(draw.std() - std) < 0.1 * std + 0.02
    mx.random.seed(7)
    again = getattr(nd, op_name)(shape=(40000,), **cfg).asnumpy()
    np.testing.assert_array_equal(draw, again)


@pytest.mark.parametrize("op_name", ["_sample_uniform", "_sample_normal",
                                     "_sample_exponential", "_sample_gamma",
                                     "_sample_poisson",
                                     "_sample_negative_binomial",
                                     "_sample_generalized_negative_binomial"])
def test_multisample_per_distribution_params(op_name):
    """_sample_* draw per-row samples from per-element distribution params."""
    mx.random.seed(11)
    if op_name == "_sample_uniform":
        out = nd._sample_uniform(nd.array(np.float32([0, 10])),
                                 nd.array(np.float32([1, 20])), shape=(4000,))
        arr = out.asnumpy()
        assert arr.shape == (2, 4000)
        assert 0 <= arr[0].min() and arr[0].max() <= 1
        assert 10 <= arr[1].min() and arr[1].max() <= 20
    elif op_name == "_sample_normal":
        out = nd._sample_normal(nd.array(np.float32([0, 5])),
                                nd.array(np.float32([1, 2])), shape=(4000,))
        arr = out.asnumpy()
        assert abs(arr[0].mean()) < 0.1 and abs(arr[1].mean() - 5) < 0.2
    elif op_name == "_sample_exponential":
        arr = nd._sample_exponential(nd.array(np.float32([1, 4])),
                                     shape=(4000,)).asnumpy()
        assert abs(arr[0].mean() - 1.0) < 0.1
        assert abs(arr[1].mean() - 0.25) < 0.05
    elif op_name == "_sample_gamma":
        arr = nd._sample_gamma(nd.array(np.float32([2, 9])),
                               nd.array(np.float32([1, 0.5])),
                               shape=(4000,)).asnumpy()
        assert abs(arr[0].mean() - 2.0) < 0.2
        assert abs(arr[1].mean() - 4.5) < 0.3
    elif op_name == "_sample_poisson":
        arr = nd._sample_poisson(nd.array(np.float32([1, 8])),
                                 shape=(4000,)).asnumpy()
        assert abs(arr[0].mean() - 1.0) < 0.15
        assert abs(arr[1].mean() - 8.0) < 0.3
    elif op_name == "_sample_negative_binomial":
        arr = nd._sample_negative_binomial(nd.array(np.float32([8])),
                                           nd.array(np.float32([0.5])),
                                           shape=(4000,)).asnumpy()
        assert abs(arr[0].mean() - 8.0) < 0.5
    else:
        arr = nd._sample_generalized_negative_binomial(
            nd.array(np.float32([4.0])), nd.array(np.float32([0.25])),
            shape=(4000,)).asnumpy()
        assert abs(arr[0].mean() - 4.0) < 0.4


# ---------------------------------------------------------------------------
# Fused optimizer update kernels vs numpy reference updates
# ---------------------------------------------------------------------------
def test_sgd_update_kernel():
    rng = _rng(47)
    w = rng.normal(size=(5,)).astype(np.float32)
    g = rng.normal(size=(5,)).astype(np.float32)
    lr, wd = 0.1, 0.01
    out = nd.sgd_update(nd.array(w), nd.array(g), lr=lr, wd=wd).asnumpy()
    assert_almost_equal(out, w - lr * (g + wd * w), rtol=1e-5)


def test_sgd_mom_update_kernel():
    rng = _rng(53)
    w = rng.normal(size=(5,)).astype(np.float32)
    g = rng.normal(size=(5,)).astype(np.float32)
    m = rng.normal(size=(5,)).astype(np.float32)
    lr, wd, mom = 0.1, 0.01, 0.9
    m_ref = mom * m - lr * (g + wd * w)
    new_w, new_m = nd.sgd_mom_update(nd.array(w), nd.array(g), nd.array(m),
                                     lr=lr, wd=wd, momentum=mom)
    assert_almost_equal(new_w.asnumpy(), w + m_ref, rtol=1e-5)
    assert_almost_equal(new_m.asnumpy(), m_ref, rtol=1e-5)


def test_adam_update_kernel():
    rng = _rng(59)
    w = rng.normal(size=(5,)).astype(np.float32)
    g = rng.normal(size=(5,)).astype(np.float32)
    m = np.zeros(5, np.float32)
    v = np.zeros(5, np.float32)
    lr, b1, b2, eps, wd = 0.01, 0.9, 0.999, 1e-8, 0.0
    g_ref = g + wd * w
    m_ref = b1 * m + (1 - b1) * g_ref
    v_ref = b2 * v + (1 - b2) * g_ref ** 2
    ref = w - lr * m_ref / (np.sqrt(v_ref) + eps)
    new_w, new_m, new_v = nd.adam_update(
        nd.array(w), nd.array(g), nd.array(m), nd.array(v),
        lr=lr, beta1=b1, beta2=b2, epsilon=eps, wd=wd)
    assert_almost_equal(new_w.asnumpy(), ref, rtol=1e-5)
    assert_almost_equal(new_m.asnumpy(), m_ref, rtol=1e-5)
    assert_almost_equal(new_v.asnumpy(), v_ref, rtol=1e-5)


def test_rmsprop_update_kernels():
    rng = _rng(61)
    w = rng.normal(size=(5,)).astype(np.float32)
    g = rng.normal(size=(5,)).astype(np.float32)
    n = np.abs(rng.normal(size=(5,))).astype(np.float32)
    lr, rho, eps = 0.01, 0.95, 1e-8
    n_ref = rho * n + (1 - rho) * g ** 2
    ref = w - lr * g / np.sqrt(n_ref + eps)
    new_w, new_n = nd.rmsprop_update(nd.array(w), nd.array(g), nd.array(n),
                                     lr=lr, gamma1=rho, epsilon=eps)
    assert_almost_equal(new_w.asnumpy(), ref, rtol=1e-4)
    assert_almost_equal(new_n.asnumpy(), n_ref, rtol=1e-4)

    # alex-smola variant carries g (first moment) and delta states
    gs = np.zeros(5, np.float32)
    d = np.zeros(5, np.float32)
    n2 = rho * n + (1 - rho) * g ** 2
    g2 = rho * gs + (1 - rho) * g
    d2 = 0.9 * d - lr * g / np.sqrt(n2 - g2 ** 2 + eps)
    outs = nd.rmspropalex_update(nd.array(w), nd.array(g), nd.array(n),
                                 nd.array(gs), nd.array(d), lr=lr,
                                 gamma1=rho, gamma2=0.9, epsilon=eps)
    assert_almost_equal(outs[0].asnumpy(), w + d2, rtol=1e-4)


# ---------------------------------------------------------------------------
# Signal / quantization
# ---------------------------------------------------------------------------
def test_fft_ifft_roundtrip():
    rng = _rng(67)
    x = rng.normal(size=(2, 8)).astype(np.float32)
    f = nd.fft(nd.array(x))
    assert f.shape == (2, 16)            # interleaved re/im
    ref = np.fft.fft(x, axis=1)
    inter = np.empty((2, 16), np.float32)
    inter[:, 0::2] = ref.real
    inter[:, 1::2] = ref.imag
    assert_almost_equal(f.asnumpy(), inter, rtol=1e-3, atol=1e-4)
    # ifft is UN-normalized, matching contrib/ifft.cc: roundtrip scales by d
    back = nd.ifft(f).asnumpy()
    assert_almost_equal(back / 8.0, x, rtol=1e-3, atol=1e-4)
    # contrib aliases
    assert_almost_equal(nd._contrib_fft(nd.array(x)).asnumpy(), inter,
                        rtol=1e-3, atol=1e-4)
    assert_almost_equal(nd._contrib_ifft(f).asnumpy() / 8.0, x, rtol=1e-3,
                        atol=1e-4)


def test_quantize_dequantize_roundtrip():
    x = np.linspace(0, 4, 12, dtype=np.float32).reshape(3, 4)
    lo, hi = nd.array(np.float32([0])), nd.array(np.float32([4]))
    q, qlo, qhi = nd.quantize(nd.array(x), lo, hi)
    dq = nd.dequantize(q, qlo, qhi).asnumpy()
    assert_almost_equal(dq, x, rtol=0.02, atol=0.02)
    q2, _, _ = nd._contrib_quantize(nd.array(x), lo, hi)
    np.testing.assert_array_equal(q.asnumpy(), q2.asnumpy())
    dq2 = nd._contrib_dequantize(q, qlo, qhi).asnumpy()
    assert_almost_equal(dq2, x, rtol=0.02, atol=0.02)


# ---------------------------------------------------------------------------
# Layer ops not already covered in test_operator.py
# ---------------------------------------------------------------------------
def test_instance_norm():
    rng = _rng(71)
    x = rng.normal(size=(2, 3, 4, 5)).astype(np.float32)
    g = rng.normal(size=(3,)).astype(np.float32)
    b = rng.normal(size=(3,)).astype(np.float32)
    eps = 1e-3
    out = nd.InstanceNorm(nd.array(x), nd.array(g), nd.array(b),
                          eps=eps).asnumpy()
    mu = x.mean(axis=(2, 3), keepdims=True)
    var = x.var(axis=(2, 3), keepdims=True)
    ref = (x - mu) / np.sqrt(var + eps) * g[None, :, None, None] \
        + b[None, :, None, None]
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


def test_rms_norm():
    rng = _rng(72)
    x = rng.normal(size=(2, 3, 8)).astype(np.float32)
    g = rng.normal(size=(8,)).astype(np.float32)
    eps = 1e-5
    out = nd.RMSNorm(nd.array(x), nd.array(g), eps=eps).asnumpy()
    ref = x / np.sqrt((x ** 2).mean(axis=-1, keepdims=True) + eps) * g
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_l2_normalization():
    rng = _rng(73)
    x = rng.normal(size=(2, 3, 4)).astype(np.float32)
    out = nd.L2Normalization(nd.array(x), mode="instance").asnumpy()
    ref = x / np.sqrt((x ** 2).sum(axis=(1, 2), keepdims=True) + 1e-10)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)
    out_c = nd.L2Normalization(nd.array(x), mode="channel").asnumpy()
    ref_c = x / np.sqrt((x ** 2).sum(axis=1, keepdims=True) + 1e-10)
    assert_almost_equal(out_c, ref_c, rtol=1e-4, atol=1e-5)


def test_lrn():
    rng = _rng(79)
    x = rng.uniform(0.5, 1.5, size=(1, 5, 3, 3)).astype(np.float32)
    alpha, beta, knorm, nsize = 1e-4, 0.75, 2.0, 3
    out = nd.LRN(nd.array(x), alpha=alpha, beta=beta, knorm=knorm,
                 nsize=nsize).asnumpy()
    sq = x ** 2
    acc = np.zeros_like(x)
    half = nsize // 2
    for c in range(5):
        lo, hi = max(0, c - half), min(5, c + half + 1)
        acc[:, c] = sq[:, lo:hi].sum(axis=1)
    # reference scales alpha by the window size (lrn-inl.h:62 salpha)
    ref = x / (knorm + (alpha / nsize) * acc) ** beta
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_svm_output():
    rng = _rng(83)
    x = rng.normal(size=(4, 3)).astype(np.float32)
    label = np.array([0, 1, 2, 1], np.float32)
    out = nd.SVMOutput(nd.array(x), nd.array(label)).asnumpy()
    np.testing.assert_array_equal(out, x)   # forward is identity (scores)
    # backward: hinge-loss gradient through a bound executor
    s = sym.SVMOutput(sym.Variable("data"), sym.Variable("label"),
                      margin=1.0, name="svm")
    ex = s.simple_bind(mx.cpu(), data=(4, 3), label=(4,), grad_req="write")
    ex.arg_dict["data"]._set_data(np.asarray(x))
    ex.arg_dict["label"]._set_data(np.asarray(label))
    ex.forward(is_train=True)
    ex.backward()
    grad = ex.grad_dict["data"].asnumpy()
    assert grad.shape == x.shape and np.abs(grad).sum() > 0


def test_identity_attach_kl_sparse_reg():
    x = _rng(89).uniform(0.1, 0.9, size=(3, 4)).astype(np.float32)
    out = nd.IdentityAttachKLSparseReg(nd.array(x)).asnumpy()
    np.testing.assert_array_equal(out, x)


def test_correlation_shape():
    rng = _rng(97)
    a = rng.normal(size=(1, 2, 6, 6)).astype(np.float32)
    b = rng.normal(size=(1, 2, 6, 6)).astype(np.float32)
    out = nd.Correlation(nd.array(a), nd.array(b), kernel_size=1,
                         max_displacement=2, stride1=1, stride2=1)
    arr = out.asnumpy()
    assert arr.shape[0] == 1 and arr.shape[1] == 25  # (2*2+1)^2 displacements


def test_makeloss_grad_scale():
    x = _rng(101).uniform(0.5, 1.5, size=(3,)).astype(np.float32)
    s = sym.MakeLoss(sym.square(sym.Variable("x")), grad_scale=2.0)
    ex = s.simple_bind(mx.cpu(), x=(3,), grad_req="write")
    ex.arg_dict["x"]._set_data(np.asarray(x))
    ex.forward(is_train=True)
    ex.backward()
    assert_almost_equal(ex.grad_dict["x"].asnumpy(), 2.0 * 2.0 * x,
                        rtol=1e-4)


def test_elementwise_sum_alias():
    xs = [_rng(103 + i).normal(size=(2, 2)).astype(np.float32)
          for i in range(3)]
    ref = sum(xs)
    out = nd.ElementWiseSum(*[nd.array(x) for x in xs]).asnumpy()
    assert_almost_equal(out, ref, rtol=1e-5)
    out2 = nd.elemwise_sum(*[nd.array(x) for x in xs]).asnumpy()
    assert_almost_equal(out2, ref, rtol=1e-5)
    out3 = nd.add_n(*[nd.array(x) for x in xs]).asnumpy()
    assert_almost_equal(out3, ref, rtol=1e-5)


def test_crop_op():
    x = _rng(107).normal(size=(1, 2, 6, 6)).astype(np.float32)
    out = nd.crop(nd.array(x), begin=(0, 0, 1, 1), end=(1, 2, 5, 5)).asnumpy()
    np.testing.assert_array_equal(out, x[:, :, 1:5, 1:5])


def test_sort_argsort_forward():
    x = _rng(109).normal(size=(3, 5)).astype(np.float32)
    assert_almost_equal(nd.sort(nd.array(x), axis=1).asnumpy(),
                        np.sort(x, axis=1))
    assert_almost_equal(nd.argsort(nd.array(x), axis=1).asnumpy(),
                        np.argsort(x, axis=1).astype(np.float32))
    vals = nd.topk(nd.array(x), k=2, axis=1, ret_typ="value").asnumpy()
    ref = np.sort(x, axis=1)[:, ::-1][:, :2]
    assert_almost_equal(vals, ref)


def test_ctc_loss_matches_contrib():
    rng = _rng(113)
    # (seq_len, batch, vocab) activations; labels padded with 0
    acts = rng.uniform(size=(5, 2, 4)).astype(np.float32)
    labels = np.array([[1, 2], [2, 3]], np.float32)
    a = nd.ctc_loss(nd.array(acts), nd.array(labels)).asnumpy()
    b = nd._contrib_CTCLoss(nd.array(acts), nd.array(labels)).asnumpy()
    c = nd.CTCLoss(nd.array(acts), nd.array(labels)).asnumpy()
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    assert (a > 0).all()                  # negative log-likelihoods


# ---------------------------------------------------------------------------
# Coverage meta-test: every registered op must be claimed somewhere
# ---------------------------------------------------------------------------
TESTED_HERE = (set(UNARY) | set(BINARY) | set(SCALAR) | set(REDUCE)
               | {"broadcast_" + s for s in BROADCAST}
               | set(SAMPLERS)
               | {"_sample_uniform", "_sample_normal", "_sample_exponential",
                  "_sample_gamma", "_sample_poisson",
                  "_sample_negative_binomial",
                  "_sample_generalized_negative_binomial",
                  "argmax", "argmin", "argmax_channel", "norm", "sum_axis",
                  "max_axis", "min_axis", "broadcast_axis", "broadcast_axes",
                  "broadcast_to", "dot", "batch_dot", "expand_dims",
                  "swapaxes", "flip", "slice_axis", "slice", "tile", "repeat",
                  "reverse", "zeros_like", "ones_like", "_zeros", "_ones",
                  "_arange", "_copy", "_grad_add",
                  "_identity_with_attr_like_rhs", "stop_gradient", "softmax",
                  "log_softmax", "SoftmaxActivation", "softmax_cross_entropy",
                  "sgd_update", "sgd_mom_update", "adam_update",
                  "rmsprop_update", "rmspropalex_update", "fft", "ifft",
                  "_contrib_fft", "_contrib_ifft", "quantize", "dequantize",
                  "_contrib_quantize", "_contrib_dequantize", "InstanceNorm",
                  "RMSNorm", "L2Normalization", "LRN", "SVMOutput",
                  "IdentityAttachKLSparseReg", "Correlation", "MakeLoss",
                  "ElementWiseSum", "elemwise_sum", "add_n", "crop", "sort",
                  "argsort", "topk", "ctc_loss", "_contrib_CTCLoss",
                  "CTCLoss"})

# ops exercised by other test files (file named so drift is auditable)
TESTED_ELSEWHERE = {
    "Activation": "test_operator.py", "BatchNorm": "test_operator.py",
    "BilinearSampler": "test_spatial_contrib.py",
    "BlockGrad": "test_operator.py", "Cast": "test_operator.py",
    "Concat": "test_operator.py", "Convolution": "test_operator.py",
    "Crop": "test_spatial_contrib.py", "Custom": "test_spatial_contrib.py",
    "Deconvolution": "test_operator.py", "Dropout": "test_operator.py",
    "Embedding": "test_operator.py", "Flatten": "test_operator.py",
    "FullyConnected": "test_operator.py",
    "GridGenerator": "test_spatial_contrib.py",
    "LeakyReLU": "test_operator.py",
    "LinearRegressionOutput": "test_operator.py",
    "LogisticRegressionOutput": "test_operator.py",
    "MAERegressionOutput": "test_operator.py",
    "MultiBoxDetection": "test_spatial_contrib.py",
    "MultiBoxPrior": "test_spatial_contrib.py",
    "MultiBoxTarget": "test_spatial_contrib.py",
    "Pad": "test_operator.py", "Pooling": "test_operator.py",
    "Proposal": "test_spatial_contrib.py", "RNN": "test_rnn.py",
    "ROIPooling": "test_spatial_contrib.py", "Reshape": "test_operator.py",
    "SequenceLast": "test_operator.py", "SequenceMask": "test_operator.py",
    "SequenceReverse": "test_operator.py",
    "SliceChannel": "test_operator.py", "Softmax": "test_operator.py",
    "SoftmaxOutput": "test_operator.py",
    "SpatialTransformer": "test_spatial_contrib.py",
    "SwapAxis": "test_operator.py", "UpSampling": "test_operator.py",
    "_contrib_MultiBoxDetection": "test_spatial_contrib.py",
    "_contrib_MultiBoxPrior": "test_spatial_contrib.py",
    "_contrib_MultiBoxTarget": "test_spatial_contrib.py",
    "_contrib_Proposal": "test_spatial_contrib.py",
    "_add": "test_ndarray.py", "_sub": "test_ndarray.py",
    "_mul": "test_ndarray.py", "_div": "test_ndarray.py",
    "_rnn_begin_state": "test_rnn.py",
    "abs": "test_operator.py", "cast": "test_operator.py",
    "clip": "test_operator.py", "concat": "test_operator.py",
    "flatten": "test_operator.py", "make_loss": "test_operator.py",
    "one_hot": "test_operator.py", "pad": "test_operator.py",
    "pick": "test_operator.py", "reshape": "test_operator.py",
    "smooth_l1": "test_operator.py", "split": "test_operator.py",
    "take": "test_operator.py", "batch_take": "test_operator.py",
    "transpose": "test_operator.py", "where": "test_operator.py",
    "exp": "test_operator.py", "log": "test_operator.py",
    "relu": "test_operator.py", "sigmoid": "test_operator.py",
    "tanh": "test_operator.py", "sqrt": "test_operator.py",
    "square": "test_operator.py", "sin": "test_operator.py",
    "cos": "test_operator.py",
    "mean": "test_operator.py", "max": "test_operator.py",
    "min": "test_operator.py", "prod": "test_operator.py",
    "sum": "test_operator.py", "nansum": "test_operator.py",
    "nanprod": "test_operator.py",
    "normal": "test_random.py", "uniform": "test_random.py",
    "random_normal": "test_random.py", "random_uniform": "test_random.py",
    "_sum": "test_operator.py",   # registry alias of sum
    "dot_product_attention": "test_seq_parallel.py",
    "_contrib_DotProductAttention": "test_seq_parallel.py",
    "MoEFFN": "test_moe.py", "_contrib_MoEFFN": "test_moe.py",
    "SelectiveSSM": "test_ssm.py",
    "LightningAttention": "test_linattn.py",
    "KimiDeltaAttention": "test_kda.py",
    "GatedDeltaNet": "test_gdn.py",
    "LatentAttention": "test_latent_attention.py",
    "count_sketch": "test_spatial_contrib.py",
    "_contrib_count_sketch": "test_spatial_contrib.py",
    "_slice_assign": "test_reference_parity.py",
    "_crop_assign": "test_reference_parity.py",
    "_crop_assign_scalar": "test_reference_parity.py",
    "_slice_assign_scalar": "test_reference_parity.py",
    "elemwise_add": "test_reference_parity.py",
    "elemwise_sub": "test_reference_parity.py",
    "elemwise_mul": "test_reference_parity.py",
    "elemwise_div": "test_reference_parity.py",
}


def test_every_registered_op_is_covered():
    """Coverage tripwire: registering a new op without a test fails here.

    User-registered runtime kernels (mx.rtc.register_pallas_op, e.g. the
    ops tests/test_rtc.py installs at collection) are out of scope — the
    tripwire guards first-party registry coverage."""
    from mxnet_tpu import registry

    covered = TESTED_HERE | set(TESTED_ELSEWHERE)
    missing = [op for op in registry.list_ops()
               if op not in covered
               and not registry.get_op(op).user_defined]
    assert not missing, (
        "ops registered but untested (add to a sweep table or claim in "
        "TESTED_ELSEWHERE): %s" % sorted(missing))
