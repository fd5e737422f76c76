"""Sequence/context parallelism (virtual 8-CPU mesh).

Leapfrogs the reference (SURVEY §2.5 "Sequence-length scaling": bucketing
and fused RNN only): attention ops shard over the 'seq' mesh axis through
the executor (GSPMD inserts the collectives), and parallel.ring implements
explicit-collective ring attention with flash-attention numerics.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.parallel.ring import dense_attention, ring_attention
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def _np_sdpa(q, k, v, num_heads, causal=False):
    b, tq, e = q.shape
    tk = k.shape[1]
    hd = e // num_heads
    ev = v.shape[2] // num_heads
    qh = q.reshape(b, tq, num_heads, hd)
    kh = k.reshape(b, tk, num_heads, hd)
    vh = v.reshape(b, tk, num_heads, ev)
    logits = np.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(hd)
    if causal:
        mask = np.tril(np.ones((tq, tk), bool), k=tk - tq)
        logits = np.where(mask[None, None], logits, -1e30)
    logits -= logits.max(-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bkhe->bqhe", p, vh)
    return out.reshape(b, tq, num_heads * ev)


# ---------------------------------------------------------------------------
# op numerics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads,causal", [(1, False), (2, False), (2, True)])
def test_dot_product_attention_forward(heads, causal):
    rng = np.random.RandomState(0)
    q = rng.normal(size=(2, 5, 8)).astype(np.float32)
    k = rng.normal(size=(2, 5, 8)).astype(np.float32)
    v = rng.normal(size=(2, 5, 8)).astype(np.float32)
    out = nd.dot_product_attention(nd.array(q), nd.array(k), nd.array(v),
                                   num_heads=heads, causal=causal).asnumpy()
    ref = _np_sdpa(q, k, v, heads, causal)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_dot_product_attention_cross():
    """Tq != Tk (cross attention)."""
    rng = np.random.RandomState(1)
    q = rng.normal(size=(2, 3, 8)).astype(np.float32)
    k = rng.normal(size=(2, 7, 8)).astype(np.float32)
    v = rng.normal(size=(2, 7, 8)).astype(np.float32)
    out = nd.dot_product_attention(nd.array(q), nd.array(k), nd.array(v),
                                   num_heads=2).asnumpy()
    assert_almost_equal(out, _np_sdpa(q, k, v, 2), rtol=1e-4, atol=1e-5)


def test_dot_product_attention_grad():
    rng = np.random.RandomState(2)
    loc = {n: rng.normal(size=(1, 4, 6)).astype(np.float32)
           for n in ("q", "k", "v")}
    s = sym.dot_product_attention(sym.Variable("q"), sym.Variable("k"),
                                  sym.Variable("v"), num_heads=2)
    check_numeric_gradient(s, loc, rtol=0.05, atol=1e-2)


def test_attention_in_symbol_graph():
    """Attention composes into a trainable LM block (MHA from FC + sdpa)."""
    rng = np.random.RandomState(3)
    b, t, e, vocab = 4, 6, 16, 11

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    emb = sym.Embedding(data, input_dim=vocab, output_dim=e, name="embed")
    q = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="q")
    k = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="k")
    v = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="v")
    att = sym.dot_product_attention(q, k, v, num_heads=4, causal=True)
    out = sym.FullyConnected(sym.Reshape(att, shape=(-1, e)),
                             num_hidden=vocab, name="head")
    net = sym.SoftmaxOutput(out, sym.Reshape(label, shape=(-1,)),
                            name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    x = rng.randint(0, vocab, size=(200, t)).astype(np.float32)
    y = np.concatenate([x[:, 1:], np.zeros((200, 1), np.float32)], axis=1)
    it = mx.io.NDArrayIter(x, y, batch_size=b)
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 5e-3},
            initializer=mx.initializer.Xavier(), num_epoch=2,
            eval_metric=mx.metric.Perplexity(ignore_label=None))
    # trains without error and the loss head produces a distribution
    out = mod.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-4)


# ---------------------------------------------------------------------------
# ring attention == dense attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq_par", [4, 8])
def test_ring_attention_matches_dense(causal, seq_par):
    import jax
    from mxnet_tpu.parallel.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.RandomState(4)
    b, t, e, heads = 2, 16, 8, 2
    q = rng.normal(size=(b, t, e)).astype(np.float32)
    k = rng.normal(size=(b, t, e)).astype(np.float32)
    v = rng.normal(size=(b, t, e)).astype(np.float32)

    mesh = Mesh(np.array(jax.devices()[:seq_par]), ("seq",))
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=causal),
        mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
        out_specs=P(None, "seq", None))
    out = np.asarray(jax.jit(ring)(q, k, v))
    ref = np.asarray(dense_attention(*map(np.asarray, (q, k, v)),
                                     num_heads=heads, causal=causal))
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)
    np_ref = _np_sdpa(q, k, v, heads, causal)
    assert_almost_equal(out, np_ref, rtol=1e-3, atol=1e-4)


def test_ring_attention_grads_match_dense():
    import jax
    from mxnet_tpu.parallel.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.RandomState(5)
    b, t, e, heads = 1, 8, 4, 1
    q = rng.normal(size=(b, t, e)).astype(np.float32)
    k = rng.normal(size=(b, t, e)).astype(np.float32)
    v = rng.normal(size=(b, t, e)).astype(np.float32)

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=True),
        mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
        out_specs=P(None, "seq", None))

    def loss_ring(q_, k_, v_):
        return (ring(q_, k_, v_) ** 2).sum()

    def loss_dense(q_, k_, v_):
        return (dense_attention(q_, k_, v_, num_heads=heads,
                                causal=True) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_dense):
        assert_almost_equal(np.asarray(a), np.asarray(b_), rtol=1e-3,
                            atol=1e-4)


# ---------------------------------------------------------------------------
# seq-sharded executor path
# ---------------------------------------------------------------------------
def _attn_lm(vocab=11, e=16):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    emb = sym.Embedding(data, input_dim=vocab, output_dim=e, name="embed")
    q = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="q")
    k = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="k")
    v = sym.FullyConnected(emb, num_hidden=e, flatten=False, name="v")
    att = sym.dot_product_attention(q, k, v, num_heads=2, causal=True)
    out = sym.FullyConnected(sym.Reshape(att, shape=(-1, e)),
                             num_hidden=vocab, name="head")
    return sym.SoftmaxOutput(out, sym.Reshape(label, shape=(-1,)),
                             name="softmax")


def test_seq_sharded_executor_matches_single_device():
    """(data=2, seq=4) mesh with layout-NTC inputs computes the same
    forward/backward as one device."""
    rng = np.random.RandomState(6)
    b, t, vocab = 4, 8, 11
    net = _attn_lm(vocab)
    data_desc = DataDesc("data", (b, t), layout="NT")
    label_desc = DataDesc("softmax_label", (b, t), layout="NT")

    mod1 = mx.mod.Module(net, context=mx.cpu(0))
    mod1.bind(data_shapes=[data_desc], label_shapes=[label_desc])
    mod1.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    arg_params, aux_params = mod1.get_params()

    modN = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                         mesh_config=MeshConfig(data=2, seq=4))
    modN.bind(data_shapes=[data_desc], label_shapes=[label_desc])
    modN.init_params(arg_params=arg_params, aux_params=aux_params)

    group = modN._exec_group
    assert group._seq_par == 4
    x = rng.randint(0, vocab, size=(b, t)).astype(np.float32)
    y = np.concatenate([x[:, 1:], np.zeros((b, 1), np.float32)], axis=1)
    batch = DataBatch([nd.array(x)], [nd.array(y)],
                      provide_data=[data_desc], provide_label=[label_desc])

    mod1.forward(batch, is_train=True)
    modN.forward(batch, is_train=True)
    o1 = mod1.get_outputs()[0].asnumpy()
    oN = modN.get_outputs()[0].asnumpy()
    assert_almost_equal(oN, o1, rtol=1e-4, atol=1e-5)

    # the time axis really is sharded over 'seq'
    darr = group.exec_.arg_dict["data"].data
    spec = darr.sharding.spec
    assert tuple(spec) == ("data", "seq"), spec

    mod1.backward()
    modN.backward()
    g1 = mod1._exec_group.grad_arrays
    gN = modN._exec_group.grad_arrays
    for name, a, b_ in zip(mod1._exec_group.param_names, g1, gN):
        if a is None:
            continue
        assert_almost_equal(b_.asnumpy(), a.asnumpy(), rtol=1e-3, atol=1e-4,
                            names=(name + "_N", name + "_1"))


def test_seq_sharded_training_learns():
    """End-to-end fit on the (data=2, seq=4) mesh converges on a
    deterministic next-token task."""
    rng = np.random.RandomState(7)
    b, t, vocab = 8, 8, 13
    net = _attn_lm(vocab, e=16)
    x = np.zeros((240, t), np.float32)
    x[:, 0] = rng.randint(1, vocab, size=240)
    for i in range(1, t):
        x[:, i] = (x[:, i - 1] * 5 + 3) % vocab
    y = np.concatenate([x[:, 1:], ((x[:, -1:] * 5 + 3) % vocab)], axis=1)

    data_desc = DataDesc("data", (b, t), layout="NT")
    label_desc = DataDesc("softmax_label", (b, t), layout="NT")
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                        mesh_config=MeshConfig(data=2, seq=4))
    mod.bind(data_shapes=[data_desc], label_shapes=[label_desc])

    it = mx.io.NDArrayIter(x, y, batch_size=b)
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 1e-2},
            initializer=mx.initializer.Xavier(), num_epoch=8,
            eval_metric=mx.metric.Perplexity(ignore_label=None))
    # the FUSED step trained, and its per-input rule shards time on 'seq'
    assert mod._fused_step is not None
    group = mod._exec_group
    assert tuple(group._input_sharding("data").spec) == ("data", "seq")
    metric = mx.metric.Perplexity(ignore_label=None)
    it.reset()
    score = dict(mod.score(it, metric))
    assert score["Perplexity"] < 4.0, score

# ---------------------------------------------------------------------------
# flash-in-ring: the Pallas kernel is the per-hop compute on the mesh
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq_par", [2, 4])
def test_ring_flash_matches_dense(causal, seq_par):
    """Ring attention with the flash kernel inside (use_flash=True,
    interpreter mode on CPU) == dense attention — fwd numerics."""
    import jax
    from mxnet_tpu.parallel.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.ring import RING_PATH

    rng = np.random.RandomState(6)
    b, t, e, heads = 2, 512, 128, 2
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float32)
               for _ in range(3)]
    mesh = Mesh(np.array(jax.devices()[:seq_par]), ("seq",))
    # check_vma=False: pallas interpreter mode can't satisfy strict vma
    # typing inside shard_map (jax interpreter limitation); the compiled
    # TPU path needs no such relaxation
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=causal,
                                          use_flash=True, interpret=True),
        mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
        out_specs=P(None, "seq", None), check_vma=False)
    RING_PATH["last"] = None
    out = np.asarray(jax.jit(ring)(q, k, v))
    assert RING_PATH["last"] == "flash"
    ref = np.asarray(dense_attention(*map(np.asarray, (q, k, v)),
                                     num_heads=heads, causal=causal))
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_flash_grads_match_dense():
    """Training through the flash ring: the custom_vjp's backward ring
    (dK/dV accumulators rotating with their blocks) == dense grads."""
    import jax
    from mxnet_tpu.parallel.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.RandomState(7)
    b, t, e, heads = 1, 256, 128, 2
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float32)
               for _ in range(3)]
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=True,
                                          use_flash=True, interpret=True),
        mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
        out_specs=P(None, "seq", None), check_vma=False)

    def loss_ring(q_, k_, v_):
        return (ring(q_, k_, v_) ** 2).sum()

    def loss_dense(q_, k_, v_):
        return (dense_attention(q_, k_, v_, num_heads=heads,
                                causal=True) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_dense):
        assert_almost_equal(np.asarray(a), np.asarray(b_), rtol=1e-3,
                            atol=1e-4)


def test_ring_flash_kernel_actually_traced():
    """Path-selection tripwire: the ring's jaxpr must contain pallas_call
    equations (the kernel, not jnp streaming math), and the auto dispatch
    must pick streaming for kernel-unfriendly local blocks."""
    import jax
    from mxnet_tpu.parallel.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.ring import RING_PATH

    b, t, e, heads = 1, 512, 128, 2
    q = np.zeros((b, t, e), np.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=True,
                                          use_flash=True, interpret=True),
        mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
        out_specs=P(None, "seq", None), check_vma=False)
    jaxpr = str(jax.make_jaxpr(ring)(q, q, q))
    assert "pallas_call" in jaxpr

    # kernel-unfriendly local block (t_local % 128 != 0): auto dispatch
    # (use_flash=None) must take the streaming path
    t2 = 96 * 2
    q2 = np.zeros((b, t2, e), np.float32)
    ring2 = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=True),
        mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
        out_specs=P(None, "seq", None))
    RING_PATH["last"] = None
    np.asarray(jax.jit(ring2)(q2, q2, q2))
    assert RING_PATH["last"] == "streaming"


def test_module_seq_mesh_dispatches_to_ring(monkeypatch):
    """With the time axis on 'seq', the executor's dot_product_attention
    runs the explicit-collective ring INSIDE the program (the flagship
    long-context path, Module-reachable) — and matches one device.
    MXNET_RING_ATTENTION=0 restores the GSPMD einsum path."""
    import mxnet_tpu as mx
    from mxnet_tpu import config as _config
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.ops.attention import PATH_TAKEN

    b, t, e, heads = 4, 16, 8, 2
    rng = np.random.RandomState(8)

    def build(contexts, mesh_config=None):
        data = sym.Variable("data")
        q = sym.FullyConnected(data, num_hidden=e, flatten=False, name="q")
        k = sym.FullyConnected(data, num_hidden=e, flatten=False, name="k")
        v = sym.FullyConnected(data, num_hidden=e, flatten=False, name="v")
        att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                        causal=True)
        net = sym.FullyConnected(att, num_hidden=4, name="head")
        net = sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=contexts, mesh_config=mesh_config)
        desc = DataDesc("data", (b, t, e), layout="NTC")
        mod.bind(data_shapes=[desc],
                 label_shapes=[("softmax_label", (b,))])
        return mod

    mod1 = build(mx.cpu(0))
    mod1.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    arg_params, aux_params = mod1.get_params()

    modN = build([mx.cpu(i) for i in range(8)],
                 mesh_config=MeshConfig(data=2, seq=4))
    modN.init_params(arg_params=arg_params, aux_params=aux_params)

    x = rng.normal(size=(b, t, e)).astype(np.float32)
    y = rng.randint(0, 4, (b,)).astype(np.float32)
    batch = DataBatch([nd.array(x)], [nd.array(y)])
    mod1.forward(batch, is_train=True)
    from mxnet_tpu import obs

    ring_count = obs.registry.counter(
        "mx_attn_dispatch_total", labels=("path",)).labels(path="ring")
    rings = ring_count.get()
    PATH_TAKEN["last"] = None
    modN.forward(batch, is_train=True)
    assert PATH_TAKEN["last"] == "ring", PATH_TAKEN
    assert ring_count.get() == rings + 1
    assert_almost_equal(modN.get_outputs()[0].asnumpy(),
                        mod1.get_outputs()[0].asnumpy(),
                        rtol=1e-4, atol=1e-5)
    # backward through the in-program ring
    mod1.backward()
    modN.backward()
    for name, a, b_ in zip(mod1._exec_group.param_names,
                           mod1._exec_group.grad_arrays,
                           modN._exec_group.grad_arrays):
        if a is None:
            continue
        assert_almost_equal(b_.asnumpy(), a.asnumpy(), rtol=1e-3,
                            atol=1e-4, names=(name + "_N", name + "_1"))

    # kill switch restores the GSPMD einsum path
    monkeypatch.setenv("MXNET_RING_ATTENTION", "0")
    _config.refresh("MXNET_RING_ATTENTION")
    try:
        modE = build([mx.cpu(i) for i in range(8)],
                     mesh_config=MeshConfig(data=2, seq=4))
        modE.init_params(arg_params=arg_params, aux_params=aux_params)
        PATH_TAKEN["last"] = None
        modE.forward(batch, is_train=True)
        assert PATH_TAKEN["last"] == "einsum", PATH_TAKEN
        assert_almost_equal(modE.get_outputs()[0].asnumpy(),
                            mod1.get_outputs()[0].asnumpy(),
                            rtol=1e-4, atol=1e-5)
    finally:
        _config.refresh("MXNET_RING_ATTENTION")


def test_module_ring_attention_fit_converges():
    """Training THROUGH the in-program ring (seq-sharded mesh) reaches the
    same quality as ordinary attention: Module.fit end to end."""
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.ops.attention import PATH_TAKEN

    b, t, e, heads, classes = 8, 16, 8, 2, 2
    rng = np.random.RandomState(9)
    n = 64
    X = rng.normal(size=(n, t, e)).astype(np.float32)
    # label depends on the mean of the first feature over time: attention
    # must aggregate across the (seq-sharded) time axis to solve it
    y = (X[:, :, 0].mean(-1) > 0).astype(np.float32)

    data = sym.Variable("data")
    q = sym.FullyConnected(data, num_hidden=e, flatten=False, name="q")
    k = sym.FullyConnected(data, num_hidden=e, flatten=False, name="k")
    v = sym.FullyConnected(data, num_hidden=e, flatten=False, name="v")
    att = sym.dot_product_attention(q, k, v, num_heads=heads)
    net = sym.FullyConnected(att, num_hidden=classes, name="head")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                        mesh_config=MeshConfig(data=2, seq=4))
    # bind with the NTC layout explicitly (fit keeps an existing binding)
    mod.bind(data_shapes=[DataDesc("data", (b, t, e), layout="NTC")],
             label_shapes=[("softmax_label", (b,))])
    it = NDArrayIter({"data": X}, {"softmax_label": y}, batch_size=b)
    np.random.seed(15)
    PATH_TAKEN["last"] = None
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 1e-2},
            initializer=mx.initializer.Xavier(), num_epoch=30)
    assert PATH_TAKEN["last"] == "ring", PATH_TAKEN
    it.reset()
    score = dict(mod.score(it, "acc"))
    assert score["accuracy"] > 0.9, score


# ---------------------------------------------------------------------------
# ring × tensor parallelism: head-sharded ring attention on (data, seq,
# model) meshes — the Megatron composition (heads are per-ring independent,
# so head groups shard over 'model' while K/V blocks rotate over 'seq')
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_ring_tp_matches_dense(causal):
    """Head-sharded streaming ring on a (data=2, seq=2, model=2) mesh ==
    dense attention: each model shard rotates only its own K/V slice."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map

    rng = np.random.RandomState(10)
    b, t, e, heads = 2, 16, 16, 4
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float32)
               for _ in range(3)]
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "seq", "model"))
    spec = P("data", "seq", "model")
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=causal,
                                          head_axis="model"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    out = np.asarray(jax.jit(ring)(q, k, v))
    ref = np.asarray(dense_attention(q, k, v, num_heads=heads,
                                     causal=causal))
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)
    assert_almost_equal(out, _np_sdpa(q, k, v, heads, causal), rtol=1e-3,
                        atol=1e-4)


def test_ring_tp_flash_matches_dense():
    """The custom-VJP flash ring under head sharding (model axis on the
    folded head dim): fwd numerics and the backward ring's dK/dV
    accumulators — each shard's gradients for ITS head group only."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map
    from mxnet_tpu.parallel.ring import RING_PATH

    rng = np.random.RandomState(11)
    b, t, e, heads = 1, 512, 256, 2
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float32)
               for _ in range(3)]
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("seq", "model"))
    spec = P(None, "seq", "model")
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=True,
                                          use_flash=True, interpret=True,
                                          head_axis="model"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    RING_PATH["last"] = None
    out = np.asarray(jax.jit(ring)(q, k, v))
    assert RING_PATH["last"] == "flash"
    ref = np.asarray(dense_attention(q, k, v, num_heads=heads, causal=True))
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)

    def loss_ring(q_, k_, v_):
        return (ring(q_, k_, v_) ** 2).sum()

    def loss_dense(q_, k_, v_):
        return (dense_attention(q_, k_, v_, num_heads=heads,
                                causal=True) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_dense):
        assert_almost_equal(np.asarray(a), np.asarray(b_), rtol=1e-3,
                            atol=1e-4)


def test_ring_tp_gradient_finite_difference():
    """Finite-difference check through the head-sharded backward ring:
    directional derivatives of a scalar loss match central differences."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map

    rng = np.random.RandomState(12)
    b, t, e, heads = 1, 8, 8, 2
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float64)
               for _ in range(3)]
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "seq", "model"))
    spec = P(None, "seq", "model")
    ring = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=True,
                                          head_axis="model"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    w = rng.normal(size=(b, t, e))

    @jax.jit                # one program for the six evaluations below
    def loss(q_, k_, v_):
        return jnp.sum(ring(q_, k_, v_) * w)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    # ring internals accumulate in float32, so directional FD agreement is
    # bounded by kernel precision, not the f64 inputs — same tolerance
    # regime as check_numeric_gradient elsewhere in the suite
    eps = 1e-3
    for i, (x, g) in enumerate(zip((q, k, v), grads)):
        d = rng.normal(size=x.shape)
        args_p = [q, k, v]
        args_m = [q, k, v]
        args_p[i] = x + eps * d
        args_m[i] = x - eps * d
        fd = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
        analytic = float(np.sum(np.asarray(g) * d))
        np.testing.assert_allclose(analytic, fd, rtol=0.02,
                                   err_msg="arg %d" % i)


def test_module_ring_tp_mesh_dispatches_to_ring():
    """PATH_TAKEN tripwire on the full (data=2, seq=2, model=2) mesh: the
    traced path must be ring when model > 1 (head groups shard over
    'model'), and forward/backward must match one device."""
    from mxnet_tpu.ops.attention import PATH_TAKEN

    b, t, e, heads = 4, 16, 16, 4
    rng = np.random.RandomState(13)

    def build(contexts, mesh_config=None):
        data = sym.Variable("data")
        q = sym.FullyConnected(data, num_hidden=e, flatten=False, name="q")
        k = sym.FullyConnected(data, num_hidden=e, flatten=False, name="k")
        v = sym.FullyConnected(data, num_hidden=e, flatten=False, name="v")
        att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                        causal=True)
        net = sym.FullyConnected(att, num_hidden=4, name="head")
        net = sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=contexts, mesh_config=mesh_config)
        mod.bind(data_shapes=[DataDesc("data", (b, t, e), layout="NTC")],
                 label_shapes=[("softmax_label", (b,))])
        return mod

    mod1 = build(mx.cpu(0))
    mod1.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    arg_params, aux_params = mod1.get_params()

    modN = build([mx.cpu(i) for i in range(8)],
                 mesh_config=MeshConfig(data=2, seq=2, model=2))
    modN.init_params(arg_params=arg_params, aux_params=aux_params)

    x = rng.normal(size=(b, t, e)).astype(np.float32)
    y = rng.randint(0, 4, (b,)).astype(np.float32)
    batch = DataBatch([nd.array(x)], [nd.array(y)])
    mod1.forward(batch, is_train=True)
    PATH_TAKEN["last"] = None
    modN.forward(batch, is_train=True)
    assert PATH_TAKEN["last"] == "ring", PATH_TAKEN
    assert_almost_equal(modN.get_outputs()[0].asnumpy(),
                        mod1.get_outputs()[0].asnumpy(),
                        rtol=1e-4, atol=1e-5)
    mod1.backward()
    modN.backward()
    for name, a, b_ in zip(mod1._exec_group.param_names,
                           mod1._exec_group.grad_arrays,
                           modN._exec_group.grad_arrays):
        if a is None:
            continue
        assert_almost_equal(b_.asnumpy(), a.asnumpy(), rtol=1e-3,
                            atol=1e-4, names=(name + "_N", name + "_1"))


def test_module_ring_tp_fewer_collective_bytes(monkeypatch):
    """hlo_stats contract on the identical (2, 2, 2) mesh: the ring×TP
    train step must move strictly fewer collective bytes (and fewer
    collectives) than the GSPMD einsum plan, and compute the same step."""
    from mxnet_tpu import config as _config
    from mxnet_tpu.parallel.hlo_stats import collective_stats

    b, t, e, heads = 4, 64, 16, 4
    rng = np.random.RandomState(14)
    x = rng.normal(size=(b, t, e)).astype(np.float32)
    y = rng.randint(0, 4, (b,)).astype(np.float32)

    def step_hlo(ring_on):
        monkeypatch.setenv("MXNET_RING_ATTENTION", "1" if ring_on else "0")
        _config.refresh("MXNET_RING_ATTENTION")
        try:
            data = sym.Variable("data")
            q = sym.FullyConnected(data, num_hidden=e, flatten=False,
                                   name="q")
            k = sym.FullyConnected(data, num_hidden=e, flatten=False,
                                   name="k")
            v = sym.FullyConnected(data, num_hidden=e, flatten=False,
                                   name="v")
            att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                            causal=True)
            net = sym.FullyConnected(att, num_hidden=4, name="head")
            net = sym.SoftmaxOutput(net, name="softmax")
            mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                                mesh_config=MeshConfig(data=2, seq=2,
                                                       model=2))
            mod.bind(data_shapes=[DataDesc("data", (b, t, e),
                                           layout="NTC")],
                     label_shapes=[("softmax_label", (b,))])
            np.random.seed(16)  # identical params under both paths
            mod.init_params(mx.initializer.Xavier())
            batch = DataBatch([nd.array(x)], [nd.array(y)])
            mod.forward(batch, is_train=True)
            mod.backward()
            out = mod.get_outputs()[0].asnumpy()
            hlo = mod._exec_group.exec_.compiled_hlo()
        finally:
            _config.refresh("MXNET_RING_ATTENTION")
        return hlo, out

    hlo_r, out_r = step_hlo(True)
    hlo_e, out_e = step_hlo(False)
    assert_almost_equal(out_r, out_e, rtol=1e-4, atol=1e-5)
    st_r = collective_stats(hlo_r)
    st_e = collective_stats(hlo_e)
    assert st_r["total"]["bytes"] < st_e["total"]["bytes"], (st_r, st_e)
    assert st_r["total"]["count"] < st_e["total"]["count"], (st_r, st_e)


def test_ring_dispatch_rejects_malformed_head_configs():
    """e % heads != 0 must fall through to the einsum path's explicit
    assert (not a reshape trace error inside shard_map); heads % model
    != 0 must degrade to the einsum path, never to wrong numbers."""
    from mxnet_tpu.ops.attention import PATH_TAKEN

    def build(e, heads, mesh_config):
        b, t = 4, 16
        data = sym.Variable("data")
        q = sym.FullyConnected(data, num_hidden=e, flatten=False, name="q")
        k = sym.FullyConnected(data, num_hidden=e, flatten=False, name="k")
        v = sym.FullyConnected(data, num_hidden=e, flatten=False, name="v")
        att = sym.dot_product_attention(q, k, v, num_heads=heads)
        net = sym.SoftmaxOutput(sym.FullyConnected(att, num_hidden=4,
                                                   name="head"),
                                name="softmax")
        mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                            mesh_config=mesh_config)
        mod.bind(data_shapes=[DataDesc("data", (b, t, e), layout="NTC")],
                 label_shapes=[("softmax_label", (b,))])
        mod.init_params(mx.initializer.Xavier())
        rng = np.random.RandomState(17)
        x = rng.normal(size=(b, t, e)).astype(np.float32)
        y = rng.randint(0, 4, (b,)).astype(np.float32)
        mod.forward(DataBatch([nd.array(x)], [nd.array(y)]),
                    is_train=False)
        return mod

    # embed dim not divisible by heads: the named head-group guard, not a
    # shard_map reshape trace error
    with pytest.raises(ValueError, match="not divisible by num_heads"):
        build(e=10, heads=3, mesh_config=MeshConfig(data=2, seq=4))

    # heads not divisible by the model axis: einsum fallback
    PATH_TAKEN["last"] = None
    build(e=12, heads=3, mesh_config=MeshConfig(data=1, seq=4, model=2))
    assert PATH_TAKEN["last"] == "einsum", PATH_TAKEN


def test_ring_flash_interpret_mode_warns():
    """use_flash=True silently resolving to Pallas interpreter mode on a
    non-TPU backend must warn — ONCE per process, not once per
    trace/retrace; an explicit interpret=True (tests) or the streaming
    path must never warn."""
    import warnings

    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map
    from mxnet_tpu.parallel.ring import _INTERPRET_WARNED

    b, t, e, heads = 1, 512, 128, 1
    q = np.zeros((b, t, e), np.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))

    def run(tl=t, **kw):
        qq = np.zeros((b, tl, e), np.float32)
        ring = shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                              num_heads=heads, **kw),
            mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
            out_specs=P(None, "seq", None), check_vma=False)
        np.asarray(jax.jit(ring)(qq, qq, qq))

    _INTERPRET_WARNED["done"] = False  # re-arm: an earlier test may have
    try:                               # already burned the process latch
        with pytest.warns(RuntimeWarning, match="interpreter mode"):
            run(use_flash=True)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            # a RETRACE (new shape) of the same hazard must not warn again
            run(tl=256, use_flash=True)
        # explicit interpret=True / the streaming path never warn — even
        # with the latch re-armed
        _INTERPRET_WARNED["done"] = False
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            run(use_flash=True, interpret=True)
            run(use_flash=False)
        assert not _INTERPRET_WARNED["done"]
    finally:
        _INTERPRET_WARNED["done"] = False


# ---------------------------------------------------------------------------
# double-buffered ring schedule: the ppermute fetching hop r+1's K/V (and
# the backward ring's traveling dK/dV rotation) issues BEFORE hop r's
# kernel, so async-collective backends overlap wire time with compute.
# Schedules must be bit-identical, and the forward rings must elide the
# final hop's discarded K/V rotation.
# ---------------------------------------------------------------------------
def _ring_222(db, causal, heads=4, **kw):
    """The (data=2, seq=2, model=2) head-sharded ring as a jitted fn."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "seq", "model"))
    spec = P("data", "seq", "model")
    return jax.jit(shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                          num_heads=heads, causal=causal,
                                          head_axis="model",
                                          double_buffer=db, **kw),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_double_buffer_bit_identical_streaming(causal):
    """Serial vs double-buffered streaming ring on the (2,2,2) mesh:
    outputs AND gradients bit-identical (same block visit order, same
    (m, l, acc) merge sequence — the schedules differ only in when the
    collectives are issued)."""
    import jax

    rng = np.random.RandomState(20)
    b, t, e, heads = 2, 16, 16, 4
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float32)
               for _ in range(3)]

    o_db = np.asarray(_ring_222(True, causal)(q, k, v))
    o_se = np.asarray(_ring_222(False, causal)(q, k, v))
    assert np.array_equal(o_db, o_se)
    # sanity: still the right numbers, not just consistently wrong ones
    ref = np.asarray(dense_attention(q, k, v, num_heads=heads,
                                     causal=causal))
    assert_almost_equal(o_db, ref, rtol=1e-4, atol=1e-5)

    def loss(f):
        return lambda q_, k_, v_: (f(q_, k_, v_) ** 2).sum()

    g_db = jax.jit(jax.grad(loss(_ring_222(True, causal)),
                            argnums=(0, 1, 2)))(q, k, v)
    g_se = jax.jit(jax.grad(loss(_ring_222(False, causal)),
                            argnums=(0, 1, 2)))(q, k, v)
    for name, a, b_ in zip("qkv", g_db, g_se):
        assert np.array_equal(np.asarray(a), np.asarray(b_)), "d" + name


@pytest.mark.parametrize("causal", [False, True])
def test_ring_double_buffer_bit_identical_flash(causal):
    """Serial vs double-buffered flash ring on the (2,2,2) mesh: the
    custom-VJP backward's lag-by-one dK/dV rotation folds hop r-1's
    contribution before rotation r — same adds, same rotations, so
    gradients are bit-identical to the serial schedule."""
    import jax

    rng = np.random.RandomState(21)
    b, t, e, heads = 2, 256, 256, 2
    q, k, v = [rng.normal(size=(b, t, e)).astype(np.float32)
               for _ in range(3)]
    kw = dict(heads=2, use_flash=True, interpret=True)

    from mxnet_tpu.parallel.ring import RING_PATH

    RING_PATH["last"] = None
    o_db = np.asarray(_ring_222(True, causal, **kw)(q, k, v))
    assert RING_PATH["last"] == "flash"
    o_se = np.asarray(_ring_222(False, causal, **kw)(q, k, v))
    assert np.array_equal(o_db, o_se)
    ref = np.asarray(dense_attention(q, k, v, num_heads=2, causal=causal))
    assert_almost_equal(o_db, ref, rtol=1e-4, atol=1e-5)

    def loss(f):
        return lambda q_, k_, v_: (f(q_, k_, v_) ** 2).sum()

    g_db = jax.jit(jax.grad(loss(_ring_222(True, causal, **kw)),
                            argnums=(0, 1, 2)))(q, k, v)
    g_se = jax.jit(jax.grad(loss(_ring_222(False, causal, **kw)),
                            argnums=(0, 1, 2)))(q, k, v)
    for name, a, b_ in zip("qkv", g_db, g_se):
        assert np.array_equal(np.asarray(a), np.asarray(b_)), "d" + name


def test_ring_double_buffer_schedule_tripwire():
    """PATH_TAKEN-style schedule tripwires, asserted at the layer each
    backend can express:

    * jaxpr equation order (what this code controls, any backend): under
      double_buffer=True every forward ring issues its ppermute BEFORE
      the hop's kernel; serial issues it after.
    * rotation counts: an n-hop forward ring moves exactly 2*(n-1) K/V
      slices (final hop elided); the flash VJP adds 2*(n-1) K/V + 2*n
      traveling dK/dV rotations in the backward ring.
    * compiled HLO: both schedules move identical collective-permute
      count/bytes, and when the backend splits collectives into async
      pairs (TPU), every start has its done and hlo_stats reports them
      as overlappable bytes; XLA:CPU keeps sync collective-permute, so
      there the overlappable statistic must be exactly 0 (that is the
      documented CPU limitation, not a schedule regression).
    """
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.compat import shard_map
    from mxnet_tpu.parallel.hlo_stats import collective_stats

    n = 4
    b, t, e, heads = 1, 16 * n, 8, 2
    x = np.zeros((b, t, e), np.float32)
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))

    def ring(db, **kw):
        return shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="seq",
                                              num_heads=heads, causal=False,
                                              double_buffer=db, **kw),
            mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
            out_specs=P(None, "seq", None), check_vma=False)

    # jaxpr order: streaming kernel = the einsum dot_general
    jx_db = str(jax.make_jaxpr(ring(True))(x, x, x))
    jx_se = str(jax.make_jaxpr(ring(False))(x, x, x))
    assert jx_db.count("ppermute") == 2 * (n - 1), jx_db.count("ppermute")
    assert jx_se.count("ppermute") == 2 * (n - 1)
    assert jx_db.index("ppermute") < jx_db.index("dot_general")
    assert jx_se.index("ppermute") > jx_se.index("dot_general")

    # flash ring (interpreter kernels): same ordering around pallas_call,
    # and the backward ring's rotation budget — fwd 2*(n-1) inside
    # rf_fwd, plus bwd 2*(n-1) K/V and 2*n traveling dK/dV
    tf, ef = 128 * n, 128
    xf = np.zeros((b, tf, ef), np.float32)

    def fgrad(db):
        f = ring(db, use_flash=True, interpret=True)
        return jax.grad(lambda *a: (f(*a) ** 2).sum(), argnums=(0, 1, 2))

    jf_db = str(jax.make_jaxpr(ring(True, use_flash=True,
                                    interpret=True))(xf, xf, xf))
    assert jf_db.count("ppermute") == 2 * (n - 1)
    # the kernels are jitted once (their body, with the pallas_call, is
    # printed above the ring); the hop's kernel is that function's call
    assert "pallas_call" in jf_db
    assert jf_db.index("ppermute") < jf_db.index("name=_fwd_kernel")
    jg_db = str(jax.make_jaxpr(fgrad(True))(xf, xf, xf))
    jg_se = str(jax.make_jaxpr(fgrad(False))(xf, xf, xf))
    expect = 2 * (n - 1) + 2 * (n - 1) + 2 * n
    assert jg_db.count("ppermute") == expect, jg_db.count("ppermute")
    assert jg_se.count("ppermute") == expect

    # compiled HLO: schedules are traffic-identical; async pairs (when
    # the backend emits them) are recognized once and totalled as
    # overlappable bytes
    for db in (True, False):
        hlo = jax.jit(ring(db)).lower(x, x, x).compile().as_text()
        st = collective_stats(hlo)
        cp = st.get("collective-permute")
        assert cp is not None and cp["count"] == 2 * (n - 1), st
        starts = hlo.count(" collective-permute-start(")
        dones = hlo.count(" collective-permute-done(")
        assert starts == dones
        if starts:  # async-collective backend (TPU)
            assert st["overlappable"]["count"] == starts
            assert st["overlappable"]["bytes"] > 0
        else:       # XLA:CPU keeps sync collective-permute
            assert st["overlappable"] == {"count": 0, "bytes": 0}


def test_module_ring_double_buffer_train_step(monkeypatch):
    """The knob threads through the op dispatch: Module train steps on the
    (2,2,2) mesh under MXNET_RING_DOUBLE_BUFFER=0/1 take the ring path
    both ways, produce bit-identical outputs and gradients, and move the
    identical collective traffic (the schedules differ in issue order,
    never in bytes)."""
    from mxnet_tpu import config as _config
    from mxnet_tpu.ops.attention import PATH_TAKEN
    from mxnet_tpu.parallel.hlo_stats import collective_stats

    b, t, e, heads = 4, 16, 16, 4
    rng = np.random.RandomState(22)
    x = rng.normal(size=(b, t, e)).astype(np.float32)
    y = rng.randint(0, 4, (b,)).astype(np.float32)

    def step(dbuf):
        monkeypatch.setenv("MXNET_RING_DOUBLE_BUFFER", dbuf)
        _config.refresh("MXNET_RING_DOUBLE_BUFFER")
        try:
            data = sym.Variable("data")
            q = sym.FullyConnected(data, num_hidden=e, flatten=False,
                                   name="q")
            k = sym.FullyConnected(data, num_hidden=e, flatten=False,
                                   name="k")
            v = sym.FullyConnected(data, num_hidden=e, flatten=False,
                                   name="v")
            att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                            causal=True)
            net = sym.FullyConnected(att, num_hidden=4, name="head")
            net = sym.SoftmaxOutput(net, name="softmax")
            mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)],
                                mesh_config=MeshConfig(data=2, seq=2,
                                                       model=2))
            mod.bind(data_shapes=[DataDesc("data", (b, t, e),
                                           layout="NTC")],
                     label_shapes=[("softmax_label", (b,))])
            np.random.seed(23)  # identical params under both schedules
            mod.init_params(mx.initializer.Xavier())
            PATH_TAKEN["last"] = None
            mod.forward(DataBatch([nd.array(x)], [nd.array(y)]),
                        is_train=True)
            assert PATH_TAKEN["last"] == "ring", PATH_TAKEN
            mod.backward()
            out = mod.get_outputs()[0].asnumpy()
            grads = [g.asnumpy() for g in mod._exec_group.grad_arrays
                     if g is not None]
            hlo = mod._exec_group.exec_.compiled_hlo()
        finally:
            _config.refresh("MXNET_RING_DOUBLE_BUFFER")
        return out, grads, hlo

    out_db, grads_db, hlo_db = step("1")
    out_se, grads_se, hlo_se = step("0")
    assert np.array_equal(out_db, out_se)
    for g_db, g_se in zip(grads_db, grads_se):
        assert np.array_equal(g_db, g_se)
    st_db = collective_stats(hlo_db)
    st_se = collective_stats(hlo_se)
    cp_db = st_db.get("collective-permute")
    assert cp_db is not None and cp_db["count"] > 0, st_db
    assert cp_db == st_se.get("collective-permute"), (st_db, st_se)
    assert st_db["total"]["bytes"] == st_se["total"]["bytes"]
