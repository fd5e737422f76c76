"""Multi-device data parallelism on the virtual CPU mesh.

TPU analog of the reference's fake-device tests
(tests/python/unittest/test_multi_device_exec.py, test_model_parallel.py):
8 virtual XLA-CPU devices stand in for 8 TPU chips; the executor group
builds a Mesh over them and shards the batch.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import DataBatch, NDArrayIter


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def test_eight_device_mesh_available():
    import jax

    assert len(jax.devices()) == 8


def test_data_parallel_forward_matches_single():
    net = _mlp()
    X = np.random.RandomState(0).randn(16, 10).astype(np.float32)
    y = np.zeros(16, dtype=np.float32)

    mod1 = mx.mod.Module(net, context=mx.cpu(0))
    mod1.bind(data_shapes=[("data", (16, 10))],
              label_shapes=[("softmax_label", (16,))])
    mod1.init_params(mx.initializer.One())

    modN = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)])
    modN.bind(data_shapes=[("data", (16, 10))],
              label_shapes=[("softmax_label", (16,))])
    modN.init_params(mx.initializer.One())

    batch = DataBatch([nd.array(X)], [nd.array(y)])
    mod1.forward(batch, is_train=False)
    modN.forward(batch, is_train=False)
    np.testing.assert_allclose(mod1.get_outputs()[0].asnumpy(),
                               modN.get_outputs()[0].asnumpy(), rtol=1e-5)


def test_data_parallel_grads_match_single():
    net = _mlp()
    rng = np.random.RandomState(1)
    X = rng.randn(16, 10).astype(np.float32)
    y = rng.randint(0, 4, 16).astype(np.float32)
    batch = DataBatch([nd.array(X)], [nd.array(y)])

    grads = {}
    for label, ctx in [("single", mx.cpu(0)),
                       ("mesh", [mx.cpu(i) for i in range(8)])]:
        mod = mx.mod.Module(net, context=ctx)
        mod.bind(data_shapes=[("data", (16, 10))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params(mx.initializer.Xavier(rnd_type="gaussian", magnitude=2))
        # same params for both runs
        if label == "single":
            params = mod.get_params()
        else:
            mod.set_params(*params)
        mod.forward_backward(batch)
        grads[label] = {n: g.asnumpy().copy() for n, g in
                        zip(mod._exec_group.param_names,
                            mod._exec_group.grad_arrays)}
    for name in grads["single"]:
        np.testing.assert_allclose(grads["single"][name], grads["mesh"][name],
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="grad mismatch for %s" % name)


def test_data_parallel_training_learns():
    np.random.seed(7)  # Xavier draws from global np.random; pin the init
    rng = np.random.RandomState(0)
    X = rng.randn(400, 10).astype(np.float32)
    W = np.random.RandomState(99).randn(10, 4).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=40)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(4)])
    mod.fit(it, initializer=mx.initializer.Xavier(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.5}, num_epoch=5,
            kvstore="device")
    acc = dict(mod.score(it, "acc"))["accuracy"]
    assert acc > 0.9, acc


def test_batch_not_divisible_raises():
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(3)])
    with pytest.raises(Exception):
        mod.bind(data_shapes=[("data", (16, 10))])


def test_fake_context_ids_fall_back():
    """CPU contexts beyond physical devices share hardware; executor falls
    back to unsharded execution (reference fake-device trick still works).
    Accelerator contexts never do: where there is no TPU (this harness),
    ``mx.tpu()``/``mx.gpu()`` raise instead of quietly naming the host."""
    import jax

    assert mx.cpu(8).jax_device == mx.cpu(0).jax_device == jax.devices()[0]
    for ctx in (mx.tpu(), mx.gpu(3)):
        with pytest.raises(mx.MXNetError, match="no TPU backend"):
            ctx.jax_device
    assert mx.num_devices("tpu") == 0
    net = _mlp()
    mod = mx.mod.Module(net, context=[mx.cpu(0), mx.cpu(8)])  # 8 wraps to 0
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    batch = DataBatch([nd.ones((4, 10))], [nd.zeros((4,))])
    mod.forward(batch, is_train=False)
    assert mod.get_outputs()[0].shape == (4, 4)
