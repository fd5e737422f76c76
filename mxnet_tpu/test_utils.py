"""Numerics-testing toolkit.

Capability parity with the reference's ``python/mxnet/test_utils.py``
(SURVEY §4): finite-difference gradient checks, forward/backward checks
against numpy references, and cross-context consistency.  The TPU twist:
"interpret-mode vs compiled-XLA" and "1-chip vs N-chip" stand in for the
reference's "CPU vs GPU" oracle pair.

Design differences from the reference implementation:

* ``numeric_grad`` is built around a single ``objective()`` closure and a
  central-difference probe loop over flattened coordinates — state
  save/restore happens once per argument, not once per element.
* ``check_numeric_gradient`` projects multi-output symbols to a scalar with
  an explicit random-projection head composed via the symbol API.
* consistency checking compares every context against an explicit oracle
  (highest-precision context) with per-dtype tolerances.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from . import ndarray as nd
from . import symbol as sym_mod
from .context import current_context

_rng = np.random.RandomState(1234)

# -- basic helpers ----------------------------------------------------------


def default_context():
    return current_context()


def default_dtype():
    return np.float32


def random_arrays(*shapes):
    """Random float32 arrays (a scalar np.float32 for 0-d shapes)."""
    out = [_rng.standard_normal(s).astype(default_dtype()) if s
           else np.float32(_rng.standard_normal()) for s in shapes]
    return out[0] if len(out) == 1 else out


def rand_ndarray(shape, dtype=np.float32):
    return nd.array(_rng.standard_normal(shape).astype(dtype))


def rand_shape_2d(dim0=10, dim1=10):
    return tuple(_rng.randint(1, d + 1) for d in (dim0, dim1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return tuple(_rng.randint(1, d + 1) for d in (dim0, dim1, dim2))


def np_reduce(dat, axis, keepdims, numpy_reduce_func):
    """Apply a numpy reduction with mxnet-style axis/keepdims semantics."""
    axes = ((axis,) if isinstance(axis, int)
            else tuple(axis) if axis is not None
            else tuple(range(dat.ndim)))
    out = numpy_reduce_func(dat, axis=axes)
    if keepdims:
        shape = tuple(1 if i in axes else s for i, s in enumerate(dat.shape))
        out = np.asarray(out).reshape(shape)
    return out


def same(a, b):
    return np.array_equal(a, b)


def reldiff(a, b):
    """L1 relative difference in [0, 1]."""
    num = np.abs(a - b).sum()
    den = np.abs(a).sum() + np.abs(b).sum()
    return 0.0 if num == 0 else float(num / den)


def _to_numpy(x):
    return x.asnumpy() if isinstance(x, nd.NDArray) else np.asarray(x)


def almost_equal(a, b, rtol=1e-5, atol=1e-20):
    return np.allclose(_to_numpy(a), _to_numpy(b), rtol=rtol, atol=atol)


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-20, names=("a", "b")):
    """np.allclose with an error report locating the worst element."""
    a, b = _to_numpy(a), _to_numpy(b)
    if np.allclose(a, b, rtol=rtol, atol=atol):
        return
    err = np.abs(a - b)
    worst = np.unravel_index(int(np.argmax(err)), err.shape) if err.ndim \
        else ()
    raise AssertionError(
        "%s and %s differ beyond rtol=%g atol=%g: max |diff| = %g at %s "
        "(%s=%s, %s=%s)" % (names[0], names[1], rtol, atol, err.max(),
                            worst, names[0], a[worst], names[1], b[worst]))


# -- argument marshalling ---------------------------------------------------


def _named_arrays(names, values, ctx, what):
    """Normalize a dict-or-sequence of inputs into {name: NDArray}."""
    if values is None:
        return None
    if isinstance(values, dict):
        if set(values) != set(names):
            raise ValueError("%s mismatch: symbol wants %s, got %s"
                             % (what, sorted(names), sorted(values)))
        pairs = values.items()
    else:
        pairs = zip(names, values)
    return {k: v if isinstance(v, nd.NDArray) else nd.array(v, ctx=ctx)
            for k, v in pairs}


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    """One forward pass on numpy inputs; numpy output(s)."""
    ctx = ctx or default_context()
    args = {k: nd.array(v, ctx=ctx) for k, v in inputs.items()}
    outs = [o.asnumpy()
            for o in sym.bind(ctx, args=args,
                              grad_req="null").forward(is_train=is_train)]
    return outs[0] if len(outs) == 1 else outs


# -- finite differences -----------------------------------------------------


def numeric_grad(executor, location, aux_states=None, eps=1e-4,
                 use_forward_train=True):
    """Central-difference gradient of ``sum(outputs[0])`` w.r.t. each float
    input.

    Evaluates the executor as a black-box objective; each coordinate gets a
    symmetric probe (±eps/2), and the argument buffer is restored once after
    its coordinate sweep.
    """
    aux_states = aux_states or {}

    def objective(name, perturbed):
        executor.arg_dict[name][:] = perturbed
        for aux_name, aux_val in aux_states.items():
            executor.aux_dict[aux_name][:] = aux_val
        executor.forward(is_train=use_forward_train)
        return float(executor.outputs[0].asnumpy().sum())

    # seed all buffers with the base point first
    for name, value in location.items():
        executor.arg_dict[name][:] = value

    grads = {}
    for name, value in location.items():
        base = np.asarray(value, dtype=np.float64).reshape(-1)
        grads[name] = np.zeros(np.shape(value), np.float32)
        if np.asarray(value).dtype.kind != "f":
            continue
        flat_grad = grads[name].reshape(-1)
        shape = np.shape(value)
        for i in range(base.size):
            probe = base.copy()
            probe[i] += eps / 2.0
            hi = objective(name, probe.reshape(shape))
            probe[i] -= eps
            lo = objective(name, probe.reshape(shape))
            flat_grad[i] = (hi - lo) / eps
        executor.arg_dict[name][:] = value  # restore the base point
    return grads


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           rtol=1e-2, atol=None, grad_nodes=None,
                           use_forward_train=True, ctx=None):
    """Assert symbolic backward matches central differences.

    The symbol's (possibly tensor-valued) output is reduced to a scalar by
    an elementwise product with a fixed random projection, so every output
    element influences the objective.
    """
    ctx = ctx or default_context()
    atol = atol if atol is not None else 1e-4

    location = _named_arrays(sym.list_arguments(), location, ctx, "location")
    aux_states = _named_arrays(sym.list_auxiliary_states(), aux_states, ctx,
                               "aux_states")
    host_location = {k: v.asnumpy() for k, v in location.items()}
    host_aux = {k: v.asnumpy() for k, v in aux_states.items()} \
        if aux_states else None

    if grad_nodes is None:
        grad_req = {k: "write" for k in sym.list_arguments()}
    elif isinstance(grad_nodes, dict):
        grad_req = dict(grad_nodes)
    else:
        grad_req = {k: "write" for k in grad_nodes}

    # scalar objective: sum(output * random_projection).  The projection and
    # seed grads draw from a per-call generator so results do not depend on
    # which tests ran earlier in the session (global-RNG order flakiness).
    _, out_shapes, _ = sym.infer_shape(
        **{k: v.shape for k, v in location.items()})
    call_rng = np.random.RandomState(1234)
    proj_value = call_rng.uniform(0.1, 1.1, out_shapes[0])
    scalar = sym_mod.MakeLoss(
        sym_mod.sum(sym * sym_mod.Variable("__random_proj")))

    bind_args = dict(location)
    bind_args["__random_proj"] = nd.array(proj_value, ctx=ctx)
    seed_grads = {k: call_rng.normal(0, 0.01, bind_args[k].shape)
                  for k in list(grad_req) + ["__random_proj"]}
    exe = scalar.bind(ctx, args=bind_args,
                      args_grad={k: nd.array(v, ctx=ctx)
                                 for k, v in seed_grads.items()},
                      grad_req=grad_req, aux_states=aux_states)
    exe.forward(is_train=True)
    exe.backward()

    fd = numeric_grad(exe, host_location, host_aux, eps=numeric_eps,
                      use_forward_train=use_forward_train)
    for name, req in grad_req.items():
        got = exe.grad_dict[name].asnumpy()
        if req == "null":
            assert_almost_equal(seed_grads[name], got, rtol, atol)
        elif req == "add":
            assert_almost_equal(fd[name], got - seed_grads[name], rtol, atol,
                                ("NUMERIC_%s" % name, "SYMBOLIC_%s" % name))
        elif req == "write":
            assert_almost_equal(fd[name], got, rtol, atol,
                                ("NUMERIC_%s" % name, "SYMBOLIC_%s" % name))
        else:
            raise ValueError("unknown grad_req %r for %s" % (req, name))


# -- numpy-reference checks -------------------------------------------------


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=None,
                           aux_states=None, ctx=None):
    """Assert forward outputs match expected numpy arrays."""
    ctx = ctx or default_context()
    location = _named_arrays(sym.list_arguments(), location, ctx, "location")
    aux_states = _named_arrays(sym.list_auxiliary_states(), aux_states, ctx,
                               "aux_states")
    if isinstance(expected, dict):
        expected = [expected[k] for k in sym.list_outputs()]

    exe = sym.bind(ctx, args=location,
                   args_grad={k: nd.zeros(v.shape, ctx=ctx)
                              for k, v in location.items()},
                   aux_states=aux_states)
    exe.forward()
    for name, want, got in zip(sym.list_outputs(), expected, exe.outputs):
        assert_almost_equal(want, got, rtol, atol if atol is not None
                            else 1e-5,
                            ("EXPECTED_%s" % name, "FORWARD_%s" % name))
    return exe.outputs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None):
    """Assert backward gradients match expected numpy arrays."""
    ctx = ctx or default_context()
    atol = atol if atol is not None else 1e-8
    location = _named_arrays(sym.list_arguments(), location, ctx, "location")
    aux_states = _named_arrays(sym.list_auxiliary_states(), aux_states, ctx,
                               "aux_states")
    if not isinstance(expected, dict):
        expected = dict(zip(sym.list_arguments(), expected))
    if isinstance(grad_req, str):
        grad_req = {k: grad_req for k in location}
    elif not isinstance(grad_req, dict):
        grad_req = dict(zip(location, grad_req))

    seed = {k: _rng.standard_normal(location[k].shape) for k in expected}
    exe = sym.bind(ctx, args=location,
                   args_grad={k: nd.array(v, ctx=ctx)
                              for k, v in seed.items()},
                   aux_states=aux_states, grad_req=grad_req)
    exe.forward(is_train=True)
    if isinstance(out_grads, dict):
        out_grads = [out_grads[k] for k in sym.list_outputs()]
    if isinstance(out_grads, (list, tuple)):
        out_grads = [g if isinstance(g, nd.NDArray) else nd.array(g, ctx=ctx)
                     for g in out_grads]
    exe.backward(out_grads)

    for name, want in expected.items():
        got = exe.grad_dict[name].asnumpy()
        req = grad_req[name]
        if req == "null":
            assert_almost_equal(seed[name], got, rtol, atol)
        elif req == "add":
            assert_almost_equal(want, got - seed[name], rtol, atol,
                                ("EXPECTED_%s" % name, "BACKWARD_%s" % name))
        elif req == "write":
            assert_almost_equal(want, got, rtol, atol,
                                ("EXPECTED_%s" % name, "BACKWARD_%s" % name))
        else:
            raise ValueError("unknown grad_req %r for %s" % (req, name))
    return exe.grad_arrays


# -- timing + cross-context oracle ------------------------------------------


def check_speed(sym, location=None, ctx=None, N=20, grad_req="write",
                typ="whole", **kwargs):
    """Mean seconds per forward (+backward when typ='whole') over N runs,
    after one warmup (compilation) pass."""
    ctx = ctx or default_context()
    shapes = kwargs if location is None \
        else {k: v.shape for k, v in location.items()}
    exe = sym.simple_bind(ctx=ctx, grad_req=grad_req, **shapes)
    if location is None:
        location = {k: _rng.standard_normal(arr.shape)
                    for k, arr in exe.arg_dict.items()}
    for name, value in location.items():
        exe.arg_dict[name][:] = np.asarray(value).astype(
            exe.arg_dict[name].dtype)

    train = typ == "whole"
    if typ not in ("whole", "forward"):
        raise ValueError("typ must be 'whole' or 'forward'")

    def one_pass():
        exe.forward(is_train=train)
        if train:
            exe.backward()

    one_pass()          # warmup: jit compile
    nd.waitall()
    start = time.time()
    for _ in range(N):
        one_pass()
    nd.waitall()
    return (time.time() - start) / N


def _consistency_tol():
    tol = {np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
           np.dtype(np.float64): 1e-5, np.dtype(np.uint8): 0,
           np.dtype(np.int32): 0}
    try:
        import ml_dtypes

        # bf16: 7-bit mantissa (coarser than fp16's 10); 1e-1 is generous
        tol[np.dtype(ml_dtypes.bfloat16)] = 1e-1
    except ImportError:
        pass
    return tol


_CONSISTENCY_TOL = _consistency_tol()


def check_consistency(sym, ctx_list, scale=1.0, grad_req="write",
                      arg_params=None, aux_params=None, tol=None,
                      raise_on_err=True, ground_truth=None):
    """Run the same symbol in several context/dtype configurations and
    compare every output and gradient against the highest-precision run.

    Each element of ``ctx_list`` is a simple_bind kwargs dict (``ctx`` plus
    input shapes, optionally ``type_dict``).  The oracle is whichever
    configuration produced the widest output dtype, or ``ground_truth``.
    """
    if tol is None:
        tol = dict(_CONSISTENCY_TOL)
    elif isinstance(tol, float):
        tol = {dt: tol for dt in _CONSISTENCY_TOL}

    syms = list(sym) if isinstance(sym, (list, tuple)) \
        else [sym] * len(ctx_list)
    assert len(syms) == len(ctx_list) >= 2
    out_names = syms[0].list_outputs()
    arg_names = syms[0].list_arguments()

    exes = [s.simple_bind(grad_req=grad_req, **cfg)
            for s, cfg in zip(syms, ctx_list)]

    # one shared random parameter set, cast per-executor
    arg_params = dict(arg_params or {})
    for name, arr in exes[0].arg_dict.items():
        arg_params.setdefault(name,
                              _rng.normal(size=arr.shape, scale=scale))
    aux_params = dict(aux_params or {})
    for name in exes[0].aux_dict:
        aux_params.setdefault(name, 0)
    for exe in exes:
        for name, arr in exe.arg_dict.items():
            val = arg_params[name]
            arr[:] = val.astype(arr.dtype) if isinstance(val, np.ndarray) \
                else val
        for name, arr in exe.aux_dict.items():
            arr[:] = aux_params[name]

    def compare(collect, oracle):
        for i, exe in enumerate(exes):
            if i == oracle_idx and ground_truth is None:
                continue
            bound = tol[dtypes[i]]
            for name, got in collect(exe).items():
                if name not in oracle:
                    continue
                try:
                    assert_almost_equal(got, oracle[name].astype(dtypes[i]),
                                        rtol=bound, atol=bound,
                                        names=("ctx%d_%s" % (i, name),
                                               "oracle_%s" % name))
                except AssertionError:
                    if raise_on_err:
                        raise
                    import traceback

                    logging.warning("check_consistency mismatch (ctx %d, "
                                    "%s):\n%s", i, name,
                                    traceback.format_exc())

    def collect_outputs(exe):
        return {n: o.asnumpy() for n, o in zip(out_names, exe.outputs)}

    def collect_all(exe):
        named = dict(zip(out_names, exe.outputs))
        named.update({n: g for n, g in zip(arg_names, exe.grad_arrays)
                      if g is not None})
        return {k: v.asnumpy() for k, v in named.items()}

    # phase 1: eval-mode forward — catches inference-path divergence and
    # keeps train-only randomness (dropout masks) out of the comparison
    for exe in exes:
        exe.forward(is_train=False)
    dtypes = [np.dtype(exe.outputs[0].dtype) for exe in exes]
    oracle_idx = int(np.argmax(dtypes))
    oracle = ground_truth or collect_outputs(exes[oracle_idx])
    compare(collect_outputs, oracle)

    # phase 2: train-mode forward+backward — outputs and gradients
    if grad_req != "null":
        for exe in exes:
            exe.forward(is_train=True)
            exe.backward()
        oracle = ground_truth or collect_all(exes[oracle_idx])
        compare(collect_all, oracle)
    return oracle


# -- telemetry helpers ------------------------------------------------------


def assert_chrome_trace(payload, required_names=()):
    """Validate a Chrome-trace export (``obs.timeline.export`` /
    ``profiler.dump_profile`` payload): the ``traceEvents`` schema every
    viewer (chrome://tracing, Perfetto) relies on, plus presence of
    ``required_names`` — so tests can pin that a real fit / serve /
    elastic run actually landed its spans and instant events."""
    assert isinstance(payload, dict) and "traceEvents" in payload, payload
    events = payload["traceEvents"]
    assert isinstance(events, list) and events
    for e in events:
        # real jax.profiler captures carry float-microsecond timestamps
        # and phases beyond our own (flow s/t/f, B/E pairs, counters) —
        # require only what every viewer requires, and the full contract
        # on the phases this framework emits itself
        assert isinstance(e, dict)
        ph = e.get("ph")
        assert isinstance(ph, str) and ph, e
        assert isinstance(e.get("ts", 0), (int, float)), e
        if ph == "X":
            assert isinstance(e.get("name"), str) and "pid" in e \
                and "tid" in e, e
            assert e.get("dur", 0) >= 0, e
        if ph == "i":
            assert isinstance(e.get("name"), str), e
            assert e.get("s") in ("t", "p", "g"), e
    names = {e.get("name") for e in events}
    missing = set(required_names) - names
    assert not missing, ("missing trace events %s (have %d events)"
                        % (sorted(missing), len(events)))
    return names


# -- serving-loop helpers ---------------------------------------------------


def serve_reading_first(server):
    """Drain a paged ``DecodeServer``'s queue in a fresh session with every
    tick's step read at that tick's end (``serve_results`` after each
    ``serve_tick``): the order the loop had before it read one tick behind,
    and the oracle for the order it has now.  Returns ``{rid: tokens}``."""
    server.serve_reset()
    server.serve_open()
    while server.has_work:
        server.serve_tick()
        server.serve_results(clear=False)
    return server.serve_results(clear=True)


def serve_tick_counts(since=None):
    """``{"behind": n, "first": n, "dropped": n}``: the process's
    ``mx_serve_ticks_total`` by ``read`` and ``mx_serve_dropped_rows_total``,
    less an earlier reading ``since`` (tests count across one drive)."""
    from . import obs

    ticks = obs.registry.get("mx_serve_ticks_total")
    dropped = obs.registry.get("mx_serve_dropped_rows_total")
    out = {read: int(ticks.labels(read=read).get()) if ticks else 0
           for read in ("behind", "first")}
    out["dropped"] = int(dropped.get()) if dropped else 0
    return {k: v - since[k] for k, v in out.items()} if since else out


def check_reading_behind(make_server, prompts, caps, with_eos):
    """The serving loop reads one tick behind; its oracle is the same loop
    made to read first.  ``make_server(eos_id)`` builds a fresh paged
    ``DecodeServer``; ``prompts`` with ``caps`` go through it both ways.
    Without an EOS every request must have as many tokens as its cap and no
    row may be dropped; ``with_eos`` picks a token some answer reaches after
    two others at least, so that answer ends in its middle, a step late."""
    def serve(eos_id, drive):
        server = make_server(eos_id)
        rids = [server.submit(p, max_new_tokens=c)
                for p, c in zip(prompts, caps)]
        out = drive(server)
        return [out[r] for r in rids]

    before = serve_tick_counts()
    behind = serve(None, lambda server: server.run())
    moved = serve_tick_counts(before)
    assert moved["behind"] > 3 * moved["first"] and not moved["dropped"], \
        moved
    assert [len(t) for t in behind] == list(caps)
    eos_id = None
    if with_eos:
        plain = behind
        eos_id = next(int(t[j]) for t in plain for j in range(2, len(t))
                      if t[j] not in t[:j])
        before = serve_tick_counts()
        behind = serve(eos_id, lambda server: server.run())
        assert serve_tick_counts(before)["dropped"] > 0
        assert any(len(b) < len(t) for b, t in zip(behind, plain))
    first = serve(eos_id, serve_reading_first)
    for a, b in zip(behind, first):
        np.testing.assert_array_equal(a, b)


def delta_toy_lm(kind, dk=None, dv=None, seed=5, vocab=64):
    """``(symbol, {name: float32 array})``: a toy ``models.decoder_lm`` with
    three delta layers before one attention layer, 2 delta heads of ``dk`` x
    ``dv``, widths ``ops.pallas_delta`` tiles by default: ``"kda"`` Kimi delta
    attention (``solar_open2``'s keys; 64 x 64, ``dv`` = ``dk``), ``"gdn"``
    Gated DeltaNet (``olmo_hybrid``'s; 8 x 64).  For tests of the decode
    step's dispatch and of the decode program that holds its kernel."""
    from .models import decoder_lm

    if kind == "kda":
        keys = dict(linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=int(dk or 64), num_heads=2,
            num_kv_heads=None), gqa_layers=(3,), kda_allow_neg_eigval=True)
    elif kind == "gdn":
        keys = dict(layer_types=("linear_attention",) * 3
                    + ("full_attention",), linear_num_key_heads=2,
                    linear_num_value_heads=2,
                    linear_key_head_dim=int(dk or 8),
                    linear_value_head_dim=int(dv or 64),
                    linear_allow_neg_eigval=True)
    else:
        raise ValueError("delta_toy_lm: kind %r is neither 'kda' nor 'gdn'"
                         % (kind,))
    sym = decoder_lm.get_symbol(
        vocab_size=vocab, hidden_size=64, num_layers=4,
        num_attention_heads=2, head_dim=16, intermediate_size=64, **keys)
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 16), softmax_label=(1, 16))
    params = {n: (1.0 + 0.1 * rng.randn(*s) if len(s) == 1
                  else rng.normal(0, 0.08, s)).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, params
