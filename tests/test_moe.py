"""Mixture-of-Experts op + expert parallelism (virtual 8-CPU mesh).

Leapfrogs SURVEY §2.5 "Tensor/expert parallelism: not present in any form":
MoEFFN is a switch-routed expert FFN whose (E, ...) weights shard on the
'expert' mesh axis.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import DataBatch, NDArrayIter
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def _np_moe(x, wg, w1, b1, w2, b2):
    n, d = x.shape
    e = wg.shape[1]
    logits = x @ wg
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    choice = probs.argmax(-1)
    gate = probs[np.arange(n), choice]
    y = np.zeros_like(x)
    for i in range(n):
        c = choice[i]
        h = np.maximum(x[i] @ w1[c] + b1[c], 0.0)
        y[i] = (h @ w2[c] + b2[c]) * gate[i]
    frac = np.zeros(e)
    for c in choice:
        frac[c] += 1.0 / n
    aux = (frac * probs.mean(0)).sum() * e
    return y, aux


def _weights(rng, d, e, h):
    return (rng.normal(0, 0.5, (d, e)).astype(np.float32),
            rng.normal(0, 0.5, (e, d, h)).astype(np.float32),
            rng.normal(0, 0.1, (e, h)).astype(np.float32),
            rng.normal(0, 0.5, (e, h, d)).astype(np.float32),
            rng.normal(0, 0.1, (e, d)).astype(np.float32))


def test_moe_forward_matches_numpy():
    rng = np.random.RandomState(0)
    n, d, e, h = 12, 6, 4, 10
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    out = nd.MoEFFN(nd.array(x), nd.array(wg), nd.array(w1), nd.array(b1),
                    nd.array(w2), nd.array(b2), num_experts=e,
                    hidden_size=h)
    ref, aux_ref = _np_moe(x, wg, w1, b1, w2, b2)
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)

    # the aux term itself matches the numpy reference
    from mxnet_tpu.ops.moe import _moe_forward

    _, aux = _moe_forward(*[np.asarray(a) for a in
                            (x, wg, w1, b1, w2, b2)], num_experts=e)
    assert_almost_equal(np.asarray(aux), np.float32(aux_ref), rtol=1e-4)


def test_moe_grad():
    rng = np.random.RandomState(1)
    n, d, e, h = 6, 4, 3, 5
    loc = {"data": rng.normal(size=(n, d)).astype(np.float32)}
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    # coeff=0: the finite-difference oracle only sees y, so the aux-loss
    # injection must be off for this comparison
    s = sym.MoEFFN(sym.Variable("data"), num_experts=e, hidden_size=h,
                   aux_loss_coeff=0.0, name="moe")
    loc.update({"moe_gate_weight": wg, "moe_expert1_weight": w1,
                "moe_expert1_bias": b1, "moe_expert2_weight": w2,
                "moe_expert2_bias": b2})
    # routing argmax is piecewise-constant; finite differences are valid
    # away from routing boundaries — the fixed seed keeps margins wide
    check_numeric_gradient(s, loc, rtol=0.06, atol=2e-2)


def test_moe_aux_loss_gradient_injection():
    """The op's backward is EXACTLY the gradient of sum(y) + coeff*aux —
    the Switch balance loss reaches the router with no loss-head plumbing."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.moe import _moe_forward

    rng = np.random.RandomState(4)
    n, d, e, h = 10, 6, 4, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    coeff = 0.5

    # op gradient via the executor's backward
    s = sym.MoEFFN(sym.Variable("data"), num_experts=e, hidden_size=h,
                   aux_loss_coeff=coeff, name="moe")
    ex = s.simple_bind(mx.cpu(), data=(n, d), grad_req="write")
    names = ["data", "moe_gate_weight", "moe_expert1_weight",
             "moe_expert1_bias", "moe_expert2_weight", "moe_expert2_bias"]
    for name, val in zip(names, (x, wg, w1, b1, w2, b2)):
        ex.arg_dict[name]._set_data(np.asarray(val))
    ex.forward(is_train=True)
    ex.backward(out_grads=nd.ones((n, d)))

    # ground truth: d(sum(y) + coeff*aux)/dtheta on the raw kernel
    def total(*args):
        y, aux = _moe_forward(*args, num_experts=e)
        return y.sum() + coeff * aux

    grads = jax.grad(total, argnums=tuple(range(6)))(
        *[jnp.asarray(a) for a in (x, wg, w1, b1, w2, b2)])
    for name, g in zip(names, grads):
        assert_almost_equal(ex.grad_dict[name].asnumpy(), np.asarray(g),
                            rtol=1e-4, atol=1e-5, names=(name, name + "_ref"))
    # and the router term is genuinely nonzero (balancing pressure exists)
    assert np.abs(ex.grad_dict["moe_gate_weight"].asnumpy()).max() > 0


def test_moe_symbol_names_and_shapes():
    s = sym.MoEFFN(sym.Variable("data"), num_experts=4, hidden_size=8,
                   name="moe")
    args = s.list_arguments()
    assert "moe_expert1_weight" in args and "moe_gate_weight" in args
    arg_shapes, out_shapes, _ = s.infer_shape(data=(10, 6))
    shapes = dict(zip(args, arg_shapes))
    assert shapes["moe_expert1_weight"] == (4, 6, 8)
    assert shapes["moe_expert2_weight"] == (4, 8, 6)
    assert out_shapes[0] == (10, 6)


def test_expert_parallel_matches_single_device():
    """(data=2, expert=4) mesh output == one device; expert weights are
    actually sharded on the 'expert' axis."""
    rng = np.random.RandomState(2)
    n, d, e, h = 8, 6, 4, 10
    data = sym.Variable("data")
    net = sym.MoEFFN(data, num_experts=e, hidden_size=h, name="moe")
    net = sym.FullyConnected(net, num_hidden=3, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod1 = mx.mod.Module(net, context=mx.cpu(0))
    mod1.bind(data_shapes=[("data", (n, d))],
              label_shapes=[("softmax_label", (n,))])
    mod1.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    arg_params, aux_params = mod1.get_params()

    modN = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                         mesh_config=MeshConfig(data=2, expert=4))
    modN.bind(data_shapes=[("data", (n, d))],
              label_shapes=[("softmax_label", (n,))])
    modN.init_params(arg_params=arg_params, aux_params=aux_params)

    group = modN._exec_group
    spec = tuple(group.exec_.arg_dict["moe_expert1_weight"].data.sharding.spec)
    assert spec and spec[0] == "expert", spec

    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.randint(0, 3, size=(n,)).astype(np.float32)
    batch = DataBatch([nd.array(x)], [nd.array(y)])
    mod1.forward(batch, is_train=True)
    modN.forward(batch, is_train=True)
    assert_almost_equal(modN.get_outputs()[0].asnumpy(),
                        mod1.get_outputs()[0].asnumpy(), rtol=1e-4,
                        atol=1e-5)

    mod1.backward()
    modN.backward()
    for name, a, b in zip(mod1._exec_group.param_names,
                          mod1._exec_group.grad_arrays,
                          modN._exec_group.grad_arrays):
        if a is None:
            continue
        assert_almost_equal(b.asnumpy(), a.asnumpy(), rtol=1e-3, atol=1e-4,
                            names=(name + "_N", name + "_1"))


def test_moe_trains():
    """A tiny MoE classifier learns a cluster task end to end (fused path
    on the expert mesh)."""
    rng = np.random.RandomState(3)
    n, d = 256, 8
    centers = rng.normal(0, 3, size=(4, d)).astype(np.float32)
    y = rng.randint(0, 4, size=n).astype(np.float32)
    x = centers[y.astype(int)] + rng.normal(0, 0.5, (n, d)).astype(np.float32)

    data = sym.Variable("data")
    net = sym.MoEFFN(data, num_experts=4, hidden_size=16, name="moe")
    net = sym.FullyConnected(net, num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                        mesh_config=MeshConfig(data=2, expert=4))
    it = NDArrayIter(x, y, batch_size=32)
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 5e-3},
            initializer=mx.initializer.Xavier(), num_epoch=10)
    score = dict(mod.score(it, "acc"))
    assert score["accuracy"] >= 0.9, score


# ---------------------------------------------------------------------------
# sparse capacity-based dispatch (capacity_factor > 0)
# ---------------------------------------------------------------------------
def test_moe_sparse_matches_dense_at_ample_capacity():
    """capacity_factor = E guarantees no token drops even if one expert
    takes everything — sparse output must equal the dense oracle."""
    from mxnet_tpu.ops.moe import _moe_forward, _moe_forward_sparse

    rng = np.random.RandomState(4)
    n, d, e, h = 32, 8, 4, 16
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    yd, auxd = _moe_forward(x, wg, w1, b1, w2, b2, e)
    ys, auxs = _moe_forward_sparse(x, wg, w1, b1, w2, b2, e, float(e))
    assert_almost_equal(np.asarray(ys), np.asarray(yd), rtol=1e-5,
                        atol=1e-6)
    assert_almost_equal(np.asarray(auxs), np.asarray(auxd), rtol=1e-5)


def test_moe_sparse_drops_overflow_tokens():
    """Past-capacity tokens emit zeros (Switch semantics: the residual
    connection carries them)."""
    from mxnet_tpu.ops.moe import _moe_forward_sparse

    rng = np.random.RandomState(5)
    n, d, e, h = 32, 8, 4, 16
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    # cf=0.5 -> total capacity n/2: at least half the tokens must drop
    ys, _ = _moe_forward_sparse(x, wg, w1, b1, w2, b2, e, 0.5)
    zero_rows = int((np.asarray(ys) == 0).all(-1).sum())
    assert zero_rows >= n // 2, zero_rows
    # and the kept rows are NOT zero
    assert zero_rows < n


def test_moe_sparse_flops_flat_in_num_experts():
    """The sparse point: per-step FLOPs must not scale with E (dense pays
    E times the expert FFN compute)."""
    import jax

    from mxnet_tpu.ops.moe import _moe_forward, _moe_forward_sparse

    rng = np.random.RandomState(6)
    n, d, h = 256, 32, 64
    x = rng.normal(size=(n, d)).astype(np.float32)

    def flops(e, cf):
        wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
        if cf:
            f = jax.jit(lambda *a: _moe_forward_sparse(*a, e, cf)[0])
        else:
            f = jax.jit(lambda *a: _moe_forward(*a, e)[0])
        ca = f.lower(x, wg, w1, b1, w2, b2).compile().cost_analysis()
        return (ca[0] if isinstance(ca, list) else ca)["flops"]

    s2, s8 = flops(2, 1.5), flops(8, 1.5)
    d2, d8 = flops(2, 0.0), flops(8, 0.0)
    assert s8 / s2 < 1.6, (s2, s8)       # router-only growth
    assert d8 / d2 > 2.5, (d2, d8)       # dense scales with E
    assert s8 < d8 / 2, (s8, d8)


def test_moe_topk_dense_matches_numpy():
    """Top-2 routing with gate renormalization on the dense path: each
    token mixes its two best experts with gates renormalized to one."""
    rng = np.random.RandomState(8)
    n, d, e, h, k = 10, 6, 4, 8, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    out = nd.MoEFFN(nd.array(x), nd.array(wg), nd.array(w1), nd.array(b1),
                    nd.array(w2), nd.array(b2), num_experts=e,
                    hidden_size=h, num_experts_per_tok=k)

    logits = x @ wg
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = np.zeros_like(x)
    for i in range(n):
        top = np.argsort(-probs[i])[:k]
        gates = probs[i][top] / probs[i][top].sum()
        for c, g in zip(top, gates):
            hh = np.maximum(x[i] @ w1[c] + b1[c], 0.0)
            ref[i] += g * (hh @ w2[c] + b2[c])
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)


def test_moe_topk_sparse_grad_fd():
    """Finite differences vs the custom-VJP backward on the top-k
    capacity path (renormalized gates differentiate through the chosen
    probabilities; routing is piecewise-constant, so FD is valid away
    from routing boundaries — the fixed seed keeps margins wide)."""
    rng = np.random.RandomState(9)
    n, d, e, h = 6, 4, 3, 5
    loc = {"data": rng.normal(size=(n, d)).astype(np.float32)}
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    s = sym.MoEFFN(sym.Variable("data"), num_experts=e, hidden_size=h,
                   num_experts_per_tok=2, capacity_factor=float(e),
                   aux_loss_coeff=0.0, name="moe")
    loc.update({"moe_gate_weight": wg, "moe_expert1_weight": w1,
                "moe_expert1_bias": b1, "moe_expert2_weight": w2,
                "moe_expert2_bias": b2})
    check_numeric_gradient(s, loc, rtol=0.06, atol=2e-2)


def test_moe_sparse_group_quota_semantics():
    """num_groups splits the capacity accounting into independent
    per-group quotas (group g of the reference IS device g of the
    sharded all-to-all path): the grouped reference must equal the
    ungrouped reference applied per token group, and dropless must keep
    every token at any capacity factor."""
    from mxnet_tpu.ops.moe import _moe_forward_sparse

    rng = np.random.RandomState(10)
    n, d, e, h, g, k = 32, 6, 4, 8, 4, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)
    cf = 0.5  # tight: forces drops inside each group

    yg, _ = _moe_forward_sparse(x, wg, w1, b1, w2, b2, e, cf,
                                num_experts_per_tok=k, num_groups=g)
    parts = [np.asarray(_moe_forward_sparse(
        x[i * (n // g):(i + 1) * (n // g)], wg, w1, b1, w2, b2, e, cf,
        num_experts_per_tok=k, num_groups=1)[0]) for i in range(g)]
    assert_almost_equal(np.asarray(yg), np.concatenate(parts), rtol=1e-5,
                        atol=1e-6)
    assert (np.asarray(yg) == 0).all(-1).sum() > 0, "no drops exercised"

    # dropless: per-group capacity stretches to the worst case — the
    # same tight cf drops nothing and matches the ample-capacity result
    yd, _ = _moe_forward_sparse(x, wg, w1, b1, w2, b2, e, cf,
                                num_experts_per_tok=k, num_groups=g,
                                dropless=True)
    ya, _ = _moe_forward_sparse(x, wg, w1, b1, w2, b2, e, float(e),
                                num_experts_per_tok=k, num_groups=g)
    assert (np.asarray(yd) == 0).all(-1).sum() == 0
    assert_almost_equal(np.asarray(yd), np.asarray(ya), rtol=1e-5,
                        atol=1e-6)


def test_moe_sharded_parity_composed_mesh():
    """The explicit all-to-all dispatch on the composed
    (data=2, expert=2, model=2) mesh is token-identical — outputs, drop
    set AND gradients — to the single-device sparse reference evaluated
    at the matching group structure (num_groups = data*expert), with the
    expert stacks actually sharded on 'expert'."""
    import jax

    from mxnet_tpu.ops.moe import MOE_PATH, _moe_forward_sparse
    from mxnet_tpu.parallel.hlo_stats import collective_stats

    rng = np.random.RandomState(11)
    n, d, e, h, k = 32, 8, 4, 12, 2
    cf = 0.75  # tight enough to drop within at least one group
    coeff = 0.5
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)

    s = sym.MoEFFN(sym.Variable("data"), num_experts=e, hidden_size=h,
                   capacity_factor=cf, num_experts_per_tok=k,
                   aux_loss_coeff=coeff, name="moe")
    mod = mx.mod.Module(s, context=[mx.cpu(i) for i in range(8)],
                        mesh_config=MeshConfig(data=2, expert=2, model=2))
    mod.bind(data_shapes=[("data", (n, d))], for_training=True,
             inputs_need_grad=True)
    mod.init_params(arg_params={
        "moe_gate_weight": nd.array(wg),
        "moe_expert1_weight": nd.array(w1),
        "moe_expert1_bias": nd.array(b1),
        "moe_expert2_weight": nd.array(w2),
        "moe_expert2_bias": nd.array(b2)})

    # the expert stacks are genuinely sharded on the 'expert' axis
    group = mod._exec_group
    for wname in ("moe_expert1_weight", "moe_expert2_weight"):
        spec = tuple(group.exec_.arg_dict[wname].data.sharding.spec)
        assert spec and spec[0] == "expert", (wname, spec)

    MOE_PATH["last"] = None
    mod.forward(DataBatch([nd.array(x)], []), is_train=True)
    ys = mod.get_outputs()[0].asnumpy()
    assert MOE_PATH["last"] == "sparse_a2a", MOE_PATH

    # reference at the matching group structure: 4 = data(2) x expert(2)
    yr, aux_r = _moe_forward_sparse(x, wg, w1, b1, w2, b2, e, cf,
                                    num_experts_per_tok=k, num_groups=4)
    yr = np.asarray(yr)
    drop_s, drop_r = (ys == 0).all(-1), (yr == 0).all(-1)
    assert drop_r.sum() > 0, "capacity never bound; parity is vacuous"
    assert (drop_s == drop_r).all(), "drop sets differ"
    assert_almost_equal(ys, yr, rtol=1e-4, atol=1e-5)

    # grads: the op backward is d(sum(y) + coeff*aux) through the
    # shard_map region — the reversed exchanges — and must match the
    # grouped reference's vjp
    out_g = nd.ones((n, d))
    group._place(out_g, sharded=True)   # head grads live on the mesh
    mod.backward(out_grads=[out_g])

    def total(*args):
        y, aux = _moe_forward_sparse(*args, e, cf, num_experts_per_tok=k,
                                     num_groups=4)
        return y.sum() + coeff * aux

    import jax.numpy as jnp

    grads = jax.grad(total, argnums=tuple(range(6)))(
        *[jnp.asarray(a) for a in (x, wg, w1, b1, w2, b2)])
    names = ["moe_gate_weight", "moe_expert1_weight", "moe_expert1_bias",
             "moe_expert2_weight", "moe_expert2_bias"]
    got = {nm: ga for nm, ga in zip(group.param_names, group.grad_arrays)
           if ga is not None}
    for nm, ref in zip(names, grads[1:]):
        assert_almost_equal(got[nm].asnumpy(), np.asarray(ref), rtol=1e-3,
                            atol=1e-4, names=(nm, nm + "_ref"))
    assert_almost_equal(mod.get_input_grads()[0].asnumpy(),
                        np.asarray(grads[0]), rtol=1e-3, atol=1e-4)

    # the compiled forward program carries the explicit exchange
    st = collective_stats(group.exec_.compiled_hlo())
    assert st.get("all-to-all", {"count": 0})["count"] > 0, st


def test_moe_sparse_expert_parallel_all_to_all():
    """On a (data, expert) mesh the sparse dispatch's expert-major
    resharding compiles to all-to-all collectives, and the mesh output
    matches a single device."""
    from mxnet_tpu.parallel.hlo_stats import collective_stats

    rng = np.random.RandomState(7)
    n, d, e, h = 64, 16, 4, 32
    data = sym.Variable("data")
    net = sym.MoEFFN(data, num_experts=e, hidden_size=h,
                     capacity_factor=float(e), name="moe")
    net = sym.FullyConnected(net, num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod1 = mx.mod.Module(net, context=mx.cpu(0))
    mod1.bind(data_shapes=[("data", (n, d))],
              label_shapes=[("softmax_label", (n,))])
    mod1.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    arg_params, aux_params = mod1.get_params()

    modN = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                         mesh_config=MeshConfig(data=2, expert=4))
    modN.bind(data_shapes=[("data", (n, d))],
              label_shapes=[("softmax_label", (n,))])
    modN.init_params(arg_params=arg_params, aux_params=aux_params)

    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.randint(0, 4, size=(n,)).astype(np.float32)
    batch = DataBatch([nd.array(x)], [nd.array(y)])
    mod1.forward(batch, is_train=True)
    modN.forward(batch, is_train=True)
    assert_almost_equal(modN.get_outputs()[0].asnumpy(),
                        mod1.get_outputs()[0].asnumpy(), rtol=1e-4,
                        atol=1e-5)
    modN.backward()
    st = collective_stats(modN._exec_group.exec_.compiled_hlo())
    assert st.get("all-to-all", {"count": 0})["count"] > 0, st


# ---------------------------------------------------------------------------
# dispatch algorithm (MXNET_MOE_DISPATCH): sort-based vs one-hot cumsum
# ---------------------------------------------------------------------------
def _slot_assign_both(choice, e, cap):
    """(pos, keep, slot) under each dispatch algorithm, with the
    MOE_DISPATCH tripwire checked per trace.  Fresh jit closures per
    mode: the knob is read at TRACE time, and jax's cache would
    otherwise hand back the first mode's program."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import config
    from mxnet_tpu.ops.moe import MOE_DISPATCH, _slot_assign

    out = {}
    for algo in ("sort", "onehot"):
        with config.overrides(MXNET_MOE_DISPATCH=algo):
            MOE_DISPATCH["last"] = None
            fn = jax.jit(lambda c: _slot_assign(c, e, cap))
            out[algo] = tuple(np.asarray(v)
                              for v in fn(jnp.asarray(choice)))
            assert MOE_DISPATCH["last"] == algo, MOE_DISPATCH
    return out["sort"], out["onehot"]


@pytest.mark.parametrize("n,k,e,cap", [(64, 2, 4, 9), (33, 1, 8, 3),
                                       (128, 4, 2, 70), (16, 2, 4, 1)])
def test_moe_dispatch_sort_equals_onehot(n, k, e, cap):
    """The dispatch contract: both algorithms produce BIT-identical
    (pos, keep, slot) for the same routing — including overflow (the
    drop set is `pos >= cap`), rank-priority ties (every rank-0 choice
    outranks every rank-1) and single-expert pile-ups."""
    rng = np.random.RandomState(n + k)
    choice = rng.randint(0, e, size=(n, k)).astype(np.int32)
    s, o = _slot_assign_both(choice, e, cap)
    for name, a, b in zip(("pos", "keep", "slot"), s, o):
        assert np.array_equal(a, b), name
    # GShard rank-major priority really holds in the shared result:
    # among same-expert choices, every rank-0 position precedes rank-1
    pos, keep, _ = s
    if k > 1:
        for ex in range(e):
            r0 = pos[:, 0][choice[:, 0] == ex]
            r1 = pos[:, 1][choice[:, 1] == ex]
            if len(r0) and len(r1):
                assert r0.max(initial=-1) < len(r0), ex
                assert (r1 >= len(r0)).all(), ex


def test_moe_dispatch_one_expert_takes_all():
    """Degenerate routing (every token to expert 0) keeps positions
    dense 0..n-1 under both algorithms."""
    choice = np.zeros((24, 1), np.int32)
    s, o = _slot_assign_both(choice, 4, 30)
    assert np.array_equal(s[0][:, 0], np.arange(24))
    assert np.array_equal(s[0], o[0])


def test_moe_dispatch_invalid_knob_raises():
    import jax.numpy as jnp

    from mxnet_tpu import config
    from mxnet_tpu.ops.moe import _slot_assign

    with config.overrides(MXNET_MOE_DISPATCH="radix"):
        with pytest.raises(ValueError, match="MXNET_MOE_DISPATCH"):
            _slot_assign(jnp.zeros((4, 1), jnp.int32), 2, 2)


def test_moe_sparse_outputs_grads_identical_across_dispatch():
    """One training-shaped fwd+bwd of the sparse MoE module under each
    dispatch algorithm: outputs, input grads and weight grads must be
    BIT-identical (the algorithms may differ only in what they
    materialize, never in which token lands in which slot)."""
    from mxnet_tpu import config

    rng = np.random.RandomState(23)
    # cf tight enough that some token loses BOTH experts (all-zero row:
    # the drop set must be visible, or the identity check is vacuous)
    n, d, e, h, k, cf = 48, 8, 4, 12, 2, 0.2
    x = rng.normal(size=(n, d)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, d, e, h)

    def run(algo):
        with config.overrides(MXNET_MOE_DISPATCH=algo):
            s = sym.MoEFFN(sym.Variable("data"), num_experts=e,
                           hidden_size=h, capacity_factor=cf,
                           num_experts_per_tok=k, aux_loss_coeff=0.3,
                           name="moe")
            mod = mx.mod.Module(s, context=mx.cpu(0))
            mod.bind(data_shapes=[("data", (n, d))], for_training=True,
                     inputs_need_grad=True)
            mod.init_params(arg_params={
                "moe_gate_weight": nd.array(wg),
                "moe_expert1_weight": nd.array(w1),
                "moe_expert1_bias": nd.array(b1),
                "moe_expert2_weight": nd.array(w2),
                "moe_expert2_bias": nd.array(b2)})
            mod.forward(DataBatch([nd.array(x)], []), is_train=True)
            y = mod.get_outputs()[0].asnumpy()
            mod.backward(out_grads=[nd.ones((n, d))])
            grads = {nm: ga.asnumpy() for nm, ga in
                     zip(mod._exec_group.param_names,
                         mod._exec_group.grad_arrays) if ga is not None}
            return y, mod.get_input_grads()[0].asnumpy(), grads

    ys, dxs, gs = run("sort")
    yo, dxo, go = run("onehot")
    drop = (ys == 0).all(-1)
    assert drop.sum() > 0, "capacity never bound; identity is vacuous"
    assert np.array_equal(ys, yo), "outputs diverge"
    assert np.array_equal(dxs, dxo), "input grads diverge"
    for nm in gs:
        assert np.array_equal(gs[nm], go[nm]), nm


def test_moe_dispatch_sort_prices_differently():
    """The two algorithms must NOT price identically: the sort path
    carries stablehlo.sort/scatter intermediates the analysis
    accounting now prices (hlo_parse.stablehlo_sort_scatter_stats);
    the one-hot pack has none of either."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import config
    from mxnet_tpu.analysis.cost import program_cost
    from mxnet_tpu.ops.moe import _slot_assign

    choice = jax.ShapeDtypeStruct((64, 2), jnp.int32)

    def price(algo):
        with config.overrides(MXNET_MOE_DISPATCH=algo):
            fn = jax.jit(lambda c: _slot_assign(c, 4, 9))
            return program_cost(fn, (choice,))

    s, o = price("sort"), price("onehot")
    assert s["sort_scatter_bytes"] > 0, s
    assert o["sort_scatter_bytes"] == 0, o
    assert s["bytes"] != o["bytes"]


# ---------------------------------------------------------------------------
# the gated layer at one chip's share (gated=True): sigmoid scores with a
# selection-only bias, SwiGLU experts, num_held / first_held, no drops
# ---------------------------------------------------------------------------

def _np_gated(x, wr, b, wg, wu, wd, k, first=0, held=None):
    """The uncut layer in numpy, one token and one expert at a time; with
    ``first`` / ``held`` the part that experts [first, first + held) add."""
    e = wr.shape[1]
    held = e if held is None else held
    s = 1.0 / (1.0 + np.exp(-(x @ wr)))
    y = np.zeros_like(x)
    for i in range(x.shape[0]):
        chosen = np.argsort(-(s[i] + b), kind="stable")[:k]
        w = s[i, chosen] / (s[i, chosen].sum() + 1e-20)
        for c, wc in zip(chosen, w):
            if first <= c < first + held:
                g = x[i] @ wg[c]
                y[i] += wc * ((g / (1.0 + np.exp(-g)) * (x[i] @ wu[c]))
                              @ wd[c])
    return y


def _gated_weights(rng, d, e, h):
    return (rng.normal(0, 0.5, (d, e)).astype(np.float32),
            rng.normal(0, 0.3, (e,)).astype(np.float32),
            rng.normal(0, 0.5, (e, d, h)).astype(np.float32),
            rng.normal(0, 0.5, (e, d, h)).astype(np.float32),
            rng.normal(0, 0.5, (e, h, d)).astype(np.float32))


def _gated(x, wr, b, wg, wu, wd, k, first=0, held=0):
    e = wr.shape[1]
    sl = slice(first, first + (held or e))
    return nd.MoEFFN(nd.array(x), nd.array(wr), nd.array(b),
                     nd.array(wg[sl]), nd.array(wu[sl]), nd.array(wd[sl]),
                     num_experts=e, hidden_size=wg.shape[2], gated=True,
                     score_func="sigmoid", score_bias=True,
                     num_experts_per_tok=k, num_held=held,
                     first_held=first).asnumpy()


def _np_softmax_shared(x, wr, wg, wu, wd, shared, k, first=0, held=None):
    """Softmax over all the experts, the k largest, their weights
    renormalised to sum 1, no selection bias, beside one always-on gated
    MLP ``shared`` (gate, up, down); with ``first`` / ``held`` what a chip
    holding experts [first, first + held) computes: its experts' part and
    the shared expert whole."""
    e = wr.shape[1]
    held = e if held is None else held
    z = x @ wr
    s = np.exp(z - z.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    silu = lambda g: g / (1.0 + np.exp(-g))
    y = (silu(x @ shared[0]) * (x @ shared[1])) @ shared[2]
    for i in range(x.shape[0]):
        chosen = np.argsort(-s[i], kind="stable")[:k]
        w = s[i, chosen] / (s[i, chosen].sum() + 1e-20)
        for c, wc in zip(chosen, w):
            if first <= c < first + held:
                y[i] += wc * ((silu(x[i] @ wg[c]) * (x[i] @ wu[c])) @ wd[c])
    return y


def _softmax_shared(x, wr, wg, wu, wd, shared, k, first=0, held=0):
    e = wr.shape[1]
    sl = slice(first, first + (held or e))
    return nd.MoEFFN(nd.array(x), nd.array(wr), nd.array(wg[sl]),
                     nd.array(wu[sl]), nd.array(wd[sl]),
                     *(nd.array(m) for m in shared),
                     num_experts=e, hidden_size=wg.shape[2], gated=True,
                     score_func="softmax", score_bias=False, norm_topk=True,
                     n_shared_experts=1, num_experts_per_tok=k,
                     num_held=held, first_held=first).asnumpy()


@pytest.mark.parametrize("held", [4, 8])
def test_gated_shares_add_up_softmax_beside_a_shared_expert(held):
    """Softmax routing, greedy top-k, no bias, a shared expert on every chip:
    each share is what the numpy layer says of it, and the shares, with the
    shared expert counted once, add up to the uncut layer."""
    rng = np.random.RandomState(11)
    n, d, e, h, k = 24, 8, 16, 6, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    wr, _, wg, wu, wd = _gated_weights(rng, d, e, h)
    shared = (rng.normal(0, 0.5, (d, h)).astype(np.float32),
              rng.normal(0, 0.5, (d, h)).astype(np.float32),
              rng.normal(0, 0.5, (h, d)).astype(np.float32))
    w = (wr, wg, wu, wd, shared)
    whole = _np_softmax_shared(x, *w, k=k)
    alone = _np_softmax_shared(x, *w, k=k, first=0, held=0)   # the shared
    firsts = range(0, e, held)
    parts = [_softmax_shared(x, *w, k=k, first=f, held=held)
             for f in firsts]
    for f, part in zip(firsts, parts):
        assert_almost_equal(part, _np_softmax_shared(x, *w, k=k, first=f,
                                                     held=held),
                            rtol=1e-4, atol=1e-5)
    assert_almost_equal(sum(parts) - (len(parts) - 1) * alone, whole,
                        rtol=1e-4, atol=1e-5)
    assert_almost_equal(_softmax_shared(x, *w, k=k), whole, rtol=1e-4,
                        atol=1e-5)


@pytest.mark.parametrize("held", [4, 8])
def test_gated_shares_add_up(held):
    """The outputs of the 4 (or 2) shares of a 16-expert layer sum to the
    uncut layer's output: nothing is lost or counted twice at a share's
    edge, and the weights are normalised over all the chosen, not the
    held."""
    rng = np.random.RandomState(7)
    n, d, e, h, k = 24, 8, 16, 6, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = _gated_weights(rng, d, e, h)
    whole = _np_gated(x, *w, k=k)
    firsts = range(0, e, held)
    parts = [_gated(x, *w, k=k, first=f, held=held) for f in firsts]
    for f, part in zip(firsts, parts):
        assert_almost_equal(part, _np_gated(x, *w, k=k, first=f, held=held),
                            rtol=1e-4, atol=1e-5)
    assert_almost_equal(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert_almost_equal(_gated(x, *w, k=k), whole, rtol=1e-4, atol=1e-5)


def test_gated_bias_selects_and_is_not_in_the_weight():
    rng = np.random.RandomState(8)
    n, d, e, h, k = 10, 8, 8, 6, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    wr, b, wg, wu, wd = _gated_weights(rng, d, e, h)
    b = np.zeros(e, np.float32)
    b[3] = 10.0                     # expert 3 is always chosen...
    out = _gated(x, wr, b, wg, wu, wd, k=k)
    assert_almost_equal(out, _np_gated(x, wr, b, wg, wu, wd, k=k),
                        rtol=1e-4, atol=1e-5)
    # ... and with its plain score as weight: a weight of score + 10 would
    # leave the other chosen expert next to nothing
    alone = _np_gated(x, wr, b, wg, wu, wd, k=k, first=3, held=1)
    assert np.abs(out - alone).max() > 0.1


@pytest.mark.parametrize("first", [4, 8])
def test_gated_routing_is_dropless_under_a_skewed_router(first):
    """Every token to the same two experts, one of them held by either
    share: a capacity dispatch would drop most of them; here none is."""
    rng = np.random.RandomState(9)
    n, d, e, h, k = 40, 8, 16, 6, 2
    x = np.abs(rng.normal(size=(n, d))).astype(np.float32)
    wr, b, wg, wu, wd = _gated_weights(rng, d, e, h)
    wr[:] = 0.0
    b[:] = 0.0
    b[5], b[9] = 5.0, 4.0           # all tokens choose 5 and 9
    out = _gated(x, wr, b, wg, wu, wd, k=k, first=first, held=4)
    want = _np_gated(x, wr, b, wg, wu, wd, k=k, first=first, held=4)
    assert np.abs(want).min(axis=1).max() > 0       # every token has a part
    assert_almost_equal(out, want, rtol=1e-4, atol=1e-5)


def _counting_run(attrs, w, sl, traces):
    """A jitted call of the gated op that returns its output and what it
    counted over the rows ``real`` marks."""
    import jax

    from mxnet_tpu.ops import moe
    from mxnet_tpu.registry import OpContext, get_op

    op = get_op("MoEFFN")
    attrs = op.parse_attrs(attrs)
    wr, _, wg, wu, wd = w

    @jax.jit
    def run(x, bias, real):
        traces.append(1)
        with moe.collecting(real=real) as rows:
            out, _ = op.fcompute(
                attrs, [x, wr, bias, wg[sl], wu[sl], wd[sl]], [],
                OpContext())
        return out[0], rows[0]

    return run


def test_gated_row_count_is_static_as_routing_changes():
    """One trace serves every routing: the work is a static held x n rows
    whatever the tokens chose."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    rng = np.random.RandomState(10)
    n, d, e, h, k = 12, 8, 16, 6, 4
    w = _gated_weights(rng, d, e, h)
    traces = []
    run = _counting_run(dict(
        num_experts=e, hidden_size=h, gated=True, score_func="sigmoid",
        score_bias=True, num_experts_per_tok=k, num_held=4, first_held=8),
        w, slice(8, 12), traces)
    seen = set()
    for seed in range(4):
        x = np.random.RandomState(seed).normal(size=(n, d)).astype(np.float32)
        bias = np.roll(w[1], seed)
        out, rows = run(jnp.asarray(x), jnp.asarray(bias),
                        jnp.ones((n,), jnp.int32))
        assert_almost_equal(
            np.asarray(out),
            _np_gated(x, w[0], bias, *w[2:], k=k, first=8, held=4),
            rtol=1e-4, atol=1e-5)
        held_rows, elsewhere, visits = (int(v) for v in np.asarray(rows))
        assert held_rows + elsewhere == n * k and 0 <= visits <= 4
        seen.add(held_rows)
    assert len(traces) == 1 and moe.MOE_PATH["last"] == "held_dense"
    assert len(seen) > 1            # the routing did change


def test_gated_counts_leave_out_the_rows_that_are_no_tokens():
    """Idle slots and a chunk's padding are computed like any row (static
    shapes) and counted nowhere: the counts over the first 5 of 12 rows are
    the counts of a call of those 5 alone, and the output is untouched."""
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    n, d, e, h, k = 12, 8, 16, 6, 4
    w = _gated_weights(rng, d, e, h)
    attrs = dict(num_experts=e, hidden_size=h, gated=True,
                 score_func="sigmoid", score_bias=True,
                 num_experts_per_tok=k, num_held=4, first_held=4)
    x = rng.normal(size=(n, d)).astype(np.float32)
    bias = jnp.asarray(w[1])
    run = _counting_run(attrs, w, slice(4, 8), [])
    real = (np.arange(n) < 5).astype(np.int32)
    out_all, rows_all = run(jnp.asarray(x), bias, jnp.ones((n,), jnp.int32))
    out_5, rows_5 = run(jnp.asarray(x), bias, jnp.asarray(real))
    _, rows_alone = run(jnp.asarray(x[:5]), bias, jnp.ones((5,), jnp.int32))
    assert_almost_equal(np.asarray(out_5), np.asarray(out_all))
    assert list(np.asarray(rows_5)) == list(np.asarray(rows_alone))
    assert int(rows_5[0] + rows_5[1]) == 5 * k
    assert int(rows_all[0]) >= int(rows_5[0])
    assert int(rows_all[0] + rows_all[1]) == n * k


def test_gated_attributes_belong_to_the_gated_layer():
    rng = np.random.RandomState(12)
    x = rng.normal(size=(4, 6)).astype(np.float32)
    wg, w1, b1, w2, b2 = _weights(rng, 6, 4, 10)
    with pytest.raises(Exception, match="gated"):
        nd.MoEFFN(nd.array(x), nd.array(wg), nd.array(w1), nd.array(b1),
                  nd.array(w2), nd.array(b2), num_experts=4, hidden_size=10,
                  score_func="sigmoid")
    wr, b, eg, eu, ed = _gated_weights(rng, 6, 8, 5)
    with pytest.raises(Exception, match="not among"):
        nd.MoEFFN(nd.array(x), nd.array(wr), nd.array(b), nd.array(eg[:4]),
                  nd.array(eu[:4]), nd.array(ed[:4]), num_experts=8,
                  hidden_size=5, gated=True, score_bias=True, num_held=4,
                  first_held=6)


def test_gated_symbol_names_and_shapes():
    s = sym.MoEFFN(sym.Variable("data"), num_experts=16, hidden_size=5,
                   gated=True, score_bias=True, num_held=4, first_held=4,
                   name="moe")
    assert s.list_arguments() == [
        "data", "moe_gate_weight", "moe_gate_bias",
        "moe_expert_gate_weight", "moe_expert_up_weight",
        "moe_expert_down_weight"]
    arg_shapes, out_shapes, _ = s.infer_shape(data=(2, 3, 8))
    assert arg_shapes[1:] == [(8, 16), (16,), (4, 8, 5), (4, 8, 5),
                              (4, 5, 8)]
    assert out_shapes == [(2, 3, 8)]


# ---------------------------------------------------------------------------
# the gated layer's grouped form: only the held (row, expert) pairs, sorted by
# expert, through megablox's grouped product (interpreted here), chosen by
# ``grouped_selected`` from the call's rows
# ---------------------------------------------------------------------------

@pytest.fixture
def grouped_form(monkeypatch):
    """Every call inside takes the grouped form: Pallas interpreted and the
    crossover lowered to one row.  The imperative path keeps a trace by the
    op's attributes, so its cache is emptied on the way in and out."""
    from mxnet_tpu import config, registry
    from mxnet_tpu.ops import moe

    monkeypatch.setattr(moe, "GROUPED_MIN_ROWS", 1)
    registry._jitted.cache_clear()
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        yield moe
    registry._jitted.cache_clear()


def _lane_weights(rng, e=16, d=128, h=128):
    """Weights of whole lane tiles (the grouped form's rule), scaled so that
    an output is of order one."""
    wr, b, wg, wu, wd = _gated_weights(rng, d, e, h)
    shared = tuple(0.1 * rng.normal(size=s).astype(np.float32)
                   for s in ((d, h), (d, h), (h, d)))
    return (0.2 * wr, b, 0.2 * wg, 0.2 * wu, 0.2 * wd), shared


def _layer(kind, x, w, shared, k, first=0, held=0):
    """(the op's output, numpy's) for a ``sigmoid_bias`` or a
    ``softmax_shared`` layer at a share."""
    wr, b, wg, wu, wd = w
    if kind == "sigmoid_bias":
        return (_gated(x, *w, k=k, first=first, held=held),
                _np_gated(x, *w, k=k, first=first, held=held or None))
    return (_softmax_shared(x, wr, wg, wu, wd, shared, k=k, first=first,
                            held=held),
            _np_softmax_shared(x, wr, wg, wu, wd, shared, k=k, first=first,
                               held=held or None))


@pytest.mark.parametrize("kind", ["sigmoid_bias", "softmax_shared"])
@pytest.mark.parametrize("held,first", [(4, 4), (8, 8), (4, 12)])
def test_grouped_form_is_the_dense_form_and_the_numpy_layer(
        kind, held, first, grouped_form, monkeypatch):
    rng = np.random.RandomState(21)
    w, shared = _lane_weights(rng)
    x = rng.normal(size=(40, 128)).astype(np.float32)
    got, want = _layer(kind, x, w, shared, 4, first, held)
    assert grouped_form.MOE_PATH["last"] == "held_grouped"
    assert np.abs(want).max() > 0.1
    assert_almost_equal(got, want, rtol=1e-4, atol=1e-5)
    # the dense form of the same call: a backend that runs no kernel
    from mxnet_tpu import registry
    from mxnet_tpu.ops import attention as attn

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (False, False))
    registry._jitted.cache_clear()
    dense, _ = _layer(kind, x, w, shared, 4, first, held)
    assert grouped_form.MOE_PATH["last"] == "held_dense"
    assert_almost_equal(got, dense, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["sigmoid_bias", "softmax_shared"])
@pytest.mark.parametrize("held", [4, 8])
def test_grouped_shares_add_up(kind, held, grouped_form):
    """The grouped shares of a 16-expert layer sum to the uncut layer (the
    shared expert, which every share holds, counted once)."""
    rng = np.random.RandomState(22)
    w, shared = _lane_weights(rng)
    x = rng.normal(size=(24, 128)).astype(np.float32)
    firsts = range(0, 16, held)
    parts = [_layer(kind, x, w, shared, 4, f, held)[0] for f in firsts]
    assert grouped_form.MOE_PATH["last"] == "held_grouped"
    whole, want = _layer(kind, x, w, shared, 4)
    # what every share holds beside its experts: the shared expert, or nothing
    alone = _np_softmax_shared(x, w[0], *w[2:], shared, k=4, first=0,
                               held=0) if kind == "softmax_shared" else 0.0
    assert_almost_equal(sum(parts) - (len(parts) - 1) * alone, want,
                        rtol=1e-4, atol=1e-5)
    assert_almost_equal(whole, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chosen,first,filled", [
    ((5, 6), 4, 1.0),       # both held: every row of the buffer is a pair
    ((5, 9), 4, 0.5), ((5, 9), 8, 0.5)])
def test_grouped_routing_is_dropless_under_a_skewed_router(
        chosen, first, filled, grouped_form):
    """Every token to the same two experts: two groups of 96 rows each, or
    one; with both held the pairs are the buffer's worst case, 192 rows of a
    buffer of two tiles, and every one is computed."""
    rng = np.random.RandomState(23)
    n, k = 96, 2
    w, _ = _lane_weights(rng)
    wr, b = np.zeros_like(w[0]), np.zeros_like(w[1])
    b[chosen[0]], b[chosen[1]] = 5.0, 4.0
    w = (wr, b) + w[2:]
    x = np.abs(rng.normal(size=(n, 128))).astype(np.float32)
    out = _gated(x, *w, k=k, first=first, held=4)
    assert grouped_form.MOE_PATH["last"] == "held_grouped"
    want = _np_gated(x, *w, k=k, first=first, held=4)
    held_pairs = sum(first <= c < first + 4 for c in chosen) * n
    assert held_pairs == filled * n * k
    assert np.abs(want).max(axis=1).min() > 0       # every token has a part
    assert_almost_equal(out, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["sigmoid_bias", "softmax_shared"])
def test_grouped_share_that_holds_none_of_the_chosen(kind, grouped_form):
    """No pair is held: every group is empty, no tile of the buffer is ever
    computed, and what lies there is selected away: zeros beside the shared
    expert, not what an unwritten buffer held."""
    rng = np.random.RandomState(24)
    w, shared = _lane_weights(rng)
    wr = np.zeros_like(w[0])
    b = np.zeros_like(w[1])
    b[:4] = 9.0                     # a bias moves a sigmoid layer's choice
    x = np.abs(rng.normal(size=(24, 128))).astype(np.float32)
    if kind == "softmax_shared":
        wr[:, :4] = 1.0             # a softmax layer's choice is its scores'
    got, want = _layer(kind, x, (wr, b) + w[2:], shared, 4, first=8, held=4)
    assert grouped_form.MOE_PATH["last"] == "held_grouped"
    assert np.isfinite(got).all()
    if kind == "sigmoid_bias":
        assert not got.any() and not want.any()
    else:
        assert np.abs(want).max() > 0.01
        assert_almost_equal(got, want, rtol=1e-4, atol=1e-5)


def test_grouped_row_count_is_static_and_the_counts_are_the_dense_forms(
        grouped_form, monkeypatch):
    """One trace of the grouped form serves four routings (the groups' sizes
    are data), each counted in ``mx_moe_dispatch_total{form}``; what
    ``collecting()`` counts, padding rows left out, is what the dense form
    counts of the same call."""
    import jax.numpy as jnp

    from mxnet_tpu import obs
    from mxnet_tpu.ops import attention as attn

    moe = grouped_form
    rng = np.random.RandomState(25)
    n, k = 12, 4
    w, _ = _lane_weights(rng)
    attrs = dict(num_experts=16, hidden_size=128, gated=True,
                 score_func="sigmoid", score_bias=True,
                 num_experts_per_tok=k, num_held=4, first_held=8)
    count = lambda form: obs.registry.counter(
        "mx_moe_dispatch_total", labels=("form",)).labels(form=form).get()
    before = count("held_grouped"), count("held_dense")
    traces, dense_traces = [], []
    run = _counting_run(attrs, w, slice(8, 12), traces)
    real = (np.arange(n) < 7).astype(np.int32)
    seen, got = set(), []
    for seed in range(4):
        x = np.random.RandomState(seed).normal(size=(n, 128)) \
            .astype(np.float32)
        bias = np.roll(w[1], seed)
        out, rows = run(jnp.asarray(x), jnp.asarray(bias), jnp.asarray(real))
        assert_almost_equal(
            np.asarray(out),
            _np_gated(x, w[0], bias, *w[2:], k=k, first=8, held=4),
            rtol=1e-4, atol=1e-5)
        held_rows, elsewhere, visits = (int(v) for v in np.asarray(rows))
        assert held_rows + elsewhere == 7 * k and 0 <= visits <= 4
        seen.add(held_rows)
        got.append((x, bias, np.asarray(rows)))
    assert len(traces) == 1 and moe.MOE_PATH["last"] == "held_grouped"
    assert len(seen) > 1            # the routing did change
    assert count("held_grouped") == before[0] + 1
    monkeypatch.setattr(attn, "_kernel_backend", lambda: (False, False))
    dense = _counting_run(attrs, w, slice(8, 12), dense_traces)
    for x, bias, rows in got:
        _, want = dense(jnp.asarray(x), jnp.asarray(bias), jnp.asarray(real))
        assert list(rows) == list(np.asarray(want))
    assert moe.MOE_PATH["last"] == "held_dense"
    assert count("held_dense") == before[1] + 1


def test_grouped_form_refuses_a_gradient_by_name(grouped_form):
    """What lies past the last group would reach a gradient: the grouped
    form has none, and says so; it is never silently the dense form's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.registry import OpContext, get_op

    rng = np.random.RandomState(26)
    w, _ = _lane_weights(rng)
    op = get_op("MoEFFN")
    attrs = op.parse_attrs(dict(
        num_experts=16, hidden_size=128, gated=True, score_func="sigmoid",
        score_bias=True, num_experts_per_tok=4, num_held=4, first_held=4))
    ins = [jnp.asarray(v) for v in
           (w[0], w[1], w[2][4:8], w[3][4:8], w[4][4:8])]
    loss = lambda x: op.fcompute(attrs, [x] + ins, [], OpContext())[0][0].sum()
    x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    assert np.isfinite(float(loss(x)))
    with pytest.raises(NotImplementedError, match="held_grouped"):
        jax.grad(loss)(x)


# (k, held, d, h) of the three serving cells' gated layers
_CELL_LAYERS = {"mistral4": (4, 16, 4096, 2048), "mimo": (8, 16, 4096, 2048),
                "exaone": (8, 16, 6144, 2048)}


@pytest.mark.parametrize("cell", sorted(_CELL_LAYERS))
@pytest.mark.parametrize("rows,grouped", [(20, False), (64, False),
                                          (96, False), (256, False),
                                          (512, True), (2048, True)])
def test_chooser_follows_the_calls_rows(cell, rows, grouped, monkeypatch):
    """Decode ticks (20-96 rows) keep the dense form, chunks of 512 (mimo,
    exaone) and of 2048 (mistral4) take the grouped one, where the probe put
    it ahead by 1.4 x or more; nothing takes it under a mesh, on a backend
    that runs no kernel, or at widths that are not whole lane tiles."""
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import moe

    k, held, d, h = _CELL_LAYERS[cell]
    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    assert moe.grouped_selected(rows, k, held, d, h) == (grouped, False)
    assert moe.grouped_selected(rows, k, held, d, h, mesh_active=True) \
        == (False, False)
    assert moe.grouped_selected(rows, k, held, d - 64, h) == (False, False)
    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, True))
    assert moe.grouped_selected(rows, k, held, d, h) == (grouped, True)
    monkeypatch.setattr(attn, "_kernel_backend", lambda: (False, False))
    assert moe.grouped_selected(rows, k, held, d, h) == (False, False)


# ---------------------------------------------------------------------------
# an expert body of two matrices (``expert_act="relu2"``: relu(x W_u)^2 W_d,
# both stacks a hidden unit a row) and a shared MLP of a width of its own:
# the share, the router and the two forms of the routed product stay
# ---------------------------------------------------------------------------

def _relu2_weights(rng, d, e, h, hs):
    """Router, selection bias, W_u and W_d (e, h, d), the shared pair
    (hs, d); scaled so that an output is of order one."""
    return (0.2 * rng.normal(0, 0.5, (d, e)).astype(np.float32),
            rng.normal(0, 0.3, (e,)).astype(np.float32),
            rng.normal(0, 0.1, (e, h, d)).astype(np.float32),
            rng.normal(0, 0.1, (e, h, d)).astype(np.float32),
            rng.normal(0, 0.1, (hs, d)).astype(np.float32),
            rng.normal(0, 0.1, (hs, d)).astype(np.float32))


def _np_relu2(x, wr, b, wu, wd, su, sd, k, factor, first=0, held=None):
    """The layer by a dense einsum: sigmoid scores over all the experts, the
    k largest of score + bias, their weights renormalised and times
    ``factor``; the experts [first, first + held) and the shared MLP."""
    e = wr.shape[1]
    held = e if held is None else held
    s = 1.0 / (1.0 + np.exp(-(x @ wr)))
    chosen = np.argsort(-(s + b), axis=1)[:, :k]
    w = np.take_along_axis(s, chosen, 1)
    w = factor * w / (w.sum(1, keepdims=True) + 1e-20)
    dense = np.zeros((x.shape[0], e), np.float32)
    np.put_along_axis(dense, chosen, w, 1)
    dense = dense[:, first:first + held]
    act = np.maximum(np.einsum("nd,ehd->enh", x, wu[first:first + held]),
                     0) ** 2
    y = np.einsum("enh,ehd,ne->nd", act, wd[first:first + held], dense)
    return y + np.maximum(x @ su.T, 0) ** 2 @ sd


def _relu2(x, wr, b, wu, wd, su, sd, k, factor, first=0, held=0):
    e = wr.shape[1]
    sl = slice(first, first + (held or e))
    return nd.MoEFFN(
        nd.array(x), nd.array(wr), nd.array(b), nd.array(wu[sl]),
        nd.array(wd[sl]), nd.array(su), nd.array(sd), num_experts=e,
        hidden_size=wu.shape[1], gated=True, expert_act="relu2",
        score_func="sigmoid", score_bias=True, num_experts_per_tok=k,
        routed_scaling_factor=factor, n_shared_experts=1,
        shared_hidden_size=su.shape[0], num_held=held,
        first_held=first).asnumpy()


@pytest.mark.parametrize("held,first", [(0, 0), (4, 4), (8, 8)])
def test_relu2_share_is_the_dense_einsum_in_both_forms(held, first,
                                                       grouped_form,
                                                       monkeypatch):
    """Two matrices an expert, no gate, a shared MLP 80 wide beside experts
    48 wide (1.5 sublane tiles of 32: the grouped product takes the width
    whole), the weights times 2.5: the grouped form (Pallas interpreted), the
    dense form and numpy agree at every share, each form under its own
    label."""
    from mxnet_tpu import registry
    from mxnet_tpu.ops import attention as attn

    rng = np.random.RandomState(31)
    w = _relu2_weights(rng, 128, 16, 48, 80)
    x = rng.normal(size=(40, 128)).astype(np.float32)
    want = _np_relu2(x, *w, k=3, factor=2.5, first=first, held=held or None)
    got = _relu2(x, *w, k=3, factor=2.5, first=first, held=held)
    assert grouped_form.MOE_PATH["last"] == "held_grouped_relu2"
    assert np.abs(want).max() > 0.1
    assert_almost_equal(got, want, rtol=1e-4, atol=1e-5)
    monkeypatch.setattr(attn, "_kernel_backend", lambda: (False, False))
    registry._jitted.cache_clear()
    dense = _relu2(x, *w, k=3, factor=2.5, first=first, held=held)
    assert grouped_form.MOE_PATH["last"] == "held_dense_relu2"
    assert_almost_equal(dense, want, rtol=1e-4, atol=1e-5)
    # the square is in it, and so is the factor
    assert np.abs(_np_relu2(x, *w, k=3, factor=1.0, first=first,
                            held=held or None) - want).max() > 1e-2


def test_relu2_symbol_names_and_shapes():
    """Two stacks and no gate, both a hidden unit a row; the shared MLP at
    its own width, or n_shared_experts x hidden_size where none is given;
    the gated body's names as they were."""
    s = sym.MoEFFN(sym.Variable("data"), num_experts=16, hidden_size=5,
                   gated=True, expert_act="relu2", score_bias=True,
                   num_held=4, first_held=4, n_shared_experts=1,
                   shared_hidden_size=7, name="moe")
    assert s.list_arguments() == [
        "data", "moe_gate_weight", "moe_gate_bias", "moe_expert_up_weight",
        "moe_expert_down_weight", "moe_shared_up_weight",
        "moe_shared_down_weight"]
    arg_shapes, out_shapes, _ = s.infer_shape(data=(2, 3, 8))
    assert arg_shapes[1:] == [(8, 16), (16,), (4, 5, 8), (4, 5, 8), (7, 8),
                              (7, 8)]
    assert out_shapes == [(2, 3, 8)]
    s = sym.MoEFFN(sym.Variable("data"), num_experts=16, hidden_size=5,
                   gated=True, n_shared_experts=2, name="moe")
    arg_shapes, _, _ = s.infer_shape(data=(2, 3, 8))
    assert s.list_arguments()[2:] == [
        "moe_expert_gate_weight", "moe_expert_up_weight",
        "moe_expert_down_weight", "moe_shared_gate_weight",
        "moe_shared_up_weight", "moe_shared_down_weight"]
    assert arg_shapes[2:] == [(16, 8, 5), (16, 8, 5), (16, 5, 8), (8, 10),
                              (8, 10), (10, 8)]
    with pytest.raises(Exception, match="expert_act"):
        sym.MoEFFN(sym.Variable("data"), num_experts=4, hidden_size=5,
                   gated=True, expert_act="gelu",
                   name="moe").infer_shape(data=(2, 3, 8))


@pytest.mark.parametrize("cap,width,tile", [
    # whole powers of two, and 3 x 2048: the largest common divisor, as ever
    (4096, 4096, 4096), (4096, 2048, 2048), (4096, 6144, 2048),
    (512, 2048, 512), (512, 4096, 512), (512, 6144, 512), (4096, 128, 128),
    (512, 128, 128), (512, 256, 256),
    # 2688 = 21 x 128: whole under 4096, its divisor 384 under 512
    (4096, 2688, 2688), (512, 2688, 384),
    # 1856 = 29 x 64: whole under 4096, 512 with a partial last tile
    (4096, 1856, 1856), (512, 1856, 512), (512, 1920, 384)])
def test_a_grouped_products_tile_of_a_width(cap, width, tile):
    from mxnet_tpu.ops import moe

    assert moe._tile(cap, width) == tile


def test_chooser_takes_a_unit_row_width_of_whole_sublane_tiles(monkeypatch):
    """Nemotron's 2688 x 1856 at 64 held, top 6: a chunk of 2048 rows takes
    the grouped form where every stack is a hidden unit a row, a decode tick
    of 64 rows the dense one; as (held, d, h) stacks the lane rule stands as
    it was, and the model's width is held to whole lane tiles either way."""
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import moe

    monkeypatch.setattr(attn, "_kernel_backend", lambda: (True, False))
    assert moe.grouped_selected(2048, 6, 64, 2688, 1856, unit_rows=True) \
        == (True, False)
    assert moe.grouped_selected(64, 6, 64, 2688, 1856, unit_rows=True) \
        == (False, False)
    assert moe.grouped_selected(2048, 6, 64, 2688, 1856) == (False, False)
    assert moe.grouped_selected(2048, 6, 64, 2688, 1848, unit_rows=True) \
        == (False, False)
    assert moe.grouped_selected(2048, 6, 64, 2688 - 64, 1856,
                                unit_rows=True) == (False, False)
    assert moe.grouped_selected(2048, 6, 64, 2688, 1856, mesh_active=True,
                                unit_rows=True) == (False, False)
