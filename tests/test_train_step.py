"""Fused (donated, jitted) train step: parity with the eager update path.

Analog of the reference's expectation that bulk-exec segments change
scheduling, not numerics (graph_executor.cc:678-756).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import DataBatch


def _make_module(fused, optimizer="sgd", compute_dtype=None, seed=7,
                 optimizer_params=None, fixed=None, bf16_head=False):
    """``bf16_head``: fc2's weight and bias are bfloat16 masters beside
    fc1's float32 ones."""
    from mxnet_tpu import config

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    if bf16_head:
        fc2 = mx.sym.FullyConnected(
            mx.sym.Cast(act, dtype="bfloat16"), name="fc2", num_hidden=4,
            weight=mx.sym.Variable("fc2_weight", dtype="bfloat16"),
            bias=mx.sym.Variable("fc2_bias", dtype="bfloat16"))
        fc2 = mx.sym.Cast(fc2, dtype="float32")
    else:
        fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=4)
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu(), compute_dtype=compute_dtype,
                        fixed_param_names=fixed)
    mod.bind(data_shapes=[("data", (8, 10))], label_shapes=[("softmax_label", (8,))])
    mx.random.seed(seed)
    mod.init_params(mx.initializer.Uniform(0.1))
    import os

    if optimizer_params is None:
        optimizer_params = {"learning_rate": 0.1, "momentum": 0.9,
                            "wd": 1e-4} \
            if optimizer == "sgd" else {"learning_rate": 0.01}
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1" if fused else "0"
    config.refresh("MXNET_FUSED_TRAIN_STEP")
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=dict(optimizer_params))
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    config.refresh("MXNET_FUSED_TRAIN_STEP")
    return mod


def _batches(n, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = nd.array(rng.uniform(-1, 1, (8, 10)).astype(np.float32))
        y = nd.array(rng.randint(0, 4, (8,)).astype(np.float32))
        out.append(DataBatch([x], [y]))
    return out


# every optimizer with a ``fused_kernel()``, in each form that changes its
# slots or its math
_KERNELS = {
    "sgd": ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    "sgd-plain": ("sgd", {"learning_rate": 0.1}),
    "nag": ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    "ccsgd": ("ccsgd", {"learning_rate": 0.1, "momentum": 0.9}),
    "adam": ("adam", {"learning_rate": 0.01}),
    "adagrad": ("adagrad", {"learning_rate": 0.05}),
    "rmsprop": ("rmsprop", {"learning_rate": 0.01}),
    "rmsprop-centered": ("rmsprop", {"learning_rate": 0.01,
                                     "centered": True}),
}


def _kernel_pair(kernel, extra=None, **kwargs):
    """The fused and the eager module of one ``_KERNELS`` entry."""
    optimizer, params = _KERNELS[kernel]
    params = dict(params, **(extra or {}))
    fused = _make_module(True, optimizer, optimizer_params=params, **kwargs)
    eager = _make_module(False, optimizer, optimizer_params=params, **kwargs)
    assert fused._fused_step is not None
    assert eager._fused_step is None
    return fused, eager


def _step_both(fused, eager, batches):
    for batch in batches:
        fused.forward_backward(batch)
        fused.update()
        eager.forward_backward(batch)
        eager.update()


def _assert_same_params(fused, eager, rtol=1e-5, atol=1e-6):
    fargs, eargs = fused.get_params()[0], eager.get_params()[0]
    for name in fargs:
        np.testing.assert_allclose(
            fargs[name].asnumpy().astype(np.float32),
            eargs[name].asnumpy().astype(np.float32),
            rtol=rtol, atol=atol, err_msg=name)


@pytest.mark.parametrize("decay_and_clip", [False, True])
@pytest.mark.parametrize("kernel", list(_KERNELS))
def test_fused_matches_eager(kernel, decay_and_clip):
    # the clip is low enough to bite: the rescaled gradients reach ~0.4
    extra = {"wd": 1e-4, "clip_gradient": 0.05} if decay_and_clip else None
    fused, eager = _kernel_pair(kernel, extra)
    _step_both(fused, eager, _batches(5))
    _assert_same_params(fused, eager)


@pytest.mark.parametrize("kernel", ["sgd", "sgd-plain", "adam"])
def test_fused_matches_eager_on_mixed_dtype_masters(kernel):
    """A tree of float32 and bfloat16 trainables: every master and every
    slot keeps its own type through the step (the eager SGD update
    promotes a bfloat16 weight to float32, so the two agree to a bfloat16
    rounding, not to the bit)."""
    fused, eager = _kernel_pair(kernel, bf16_head=True)
    step = fused._fused_step
    dtypes = {n: v.dtype for n, v in step.params.items()}
    assert {str(d) for d in dtypes.values()} == {"float32", "bfloat16"}
    _step_both(fused, eager, _batches(4))
    for n, v in step.params.items():
        assert v.dtype == dtypes[n], n
        assert all(s.dtype == dtypes[n] for s in step.slots[n]), n
    _assert_same_params(fused, eager, rtol=0, atol=2e-3)


def test_fused_matches_eager_with_fixed_params():
    """A fixed parameter rides through the step as a forward input: it has
    no slot, stays as initialised, and the others train as on the eager
    path."""
    fused, eager = _kernel_pair("sgd", fixed=["fc1_bias"])
    assert "fc1_bias" not in fused._fused_step.slots
    before = fused.get_params()[0]["fc1_bias"].asnumpy().copy()
    _step_both(fused, eager, _batches(4))
    np.testing.assert_array_equal(
        fused.get_params()[0]["fc1_bias"].asnumpy(), before)
    _assert_same_params(fused, eager)


def test_set_params_between_steps_is_read_by_the_next_step():
    """Masters replaced from outside the step (``set_params``) are what the
    next step trains from; the slots carry over."""
    fused, eager = _kernel_pair("sgd")
    batches = _batches(3)
    _step_both(fused, eager, batches[:2])
    new_args, new_aux = _make_module(True, seed=99).get_params()
    fused.set_params(new_args, new_aux)
    eager.set_params(new_args, new_aux)
    _step_both(fused, eager, batches[2:])
    _assert_same_params(fused, eager)
    # and not from the masters the first two steps left
    moved = fused.get_params()[0]["fc1_weight"].asnumpy()
    assert np.abs(moved - new_args["fc1_weight"].asnumpy()).max() < 0.05


def test_fused_outputs_feed_metric():
    mod = _make_module(True)
    metric = mx.metric.Accuracy()
    for batch in _batches(3):
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)
    name, value = metric.get()
    assert 0.0 <= value <= 1.0


def test_fused_then_eval_forward_uses_fresh_params():
    mod = _make_module(True)
    batches = _batches(4)
    for batch in batches:
        mod.forward_backward(batch)
        mod.update()
    # eval forward must see post-update params, not the bind-time ones
    mod.forward(batches[0], is_train=False)
    out = mod.get_outputs()[0].asnumpy()
    fresh = _make_module(True)
    fresh.forward(batches[0], is_train=False)
    out0 = fresh.get_outputs()[0].asnumpy()
    assert not np.allclose(out, out0)


def test_bf16_compute_trains():
    mod = _make_module(True, compute_dtype="bfloat16")
    assert mod._fused_step is not None
    metric = mx.metric.CrossEntropy()
    batches = _batches(2)
    first = None
    for i in range(30):
        b = batches[i % 2]
        mod.forward_backward(b)
        mod.update()
        metric.reset()
        mod.update_metric(metric, b.label)
        if first is None:
            first = metric.get()[1]
    last = metric.get()[1]
    assert last < first  # loss decreased under bf16 compute


@pytest.mark.parametrize("kernel",
                         ["sgd", "nag", "adam", "adagrad", "rmsprop"])
def test_fused_optimizer_state_roundtrip(tmp_path, kernel):
    mod = _make_module(True, _KERNELS[kernel][0],
                       optimizer_params=_KERNELS[kernel][1])
    for batch in _batches(3):
        mod.forward_backward(batch)
        mod.update()
    fname = str(tmp_path / "opt.states")
    mod.save_optimizer_states(fname)
    slots_before = {n: [np.asarray(s) for s in sl]
                    for n, sl in mod._fused_step.slots.items()}
    for batch in _batches(2, seed=11):
        mod.forward_backward(batch)
        mod.update()
    mod.load_optimizer_states(fname)
    for n, sl in mod._fused_step.slots.items():
        for a, b in zip(sl, slots_before[n]):
            np.testing.assert_allclose(np.asarray(a), b)


def test_rescale_clip_are_runtime_scalars():
    # mutating rescale_grad after compilation must take effect (ADVICE r1)
    mod = _make_module(True)
    batch = _batches(1)[0]
    mod.forward_backward(batch)
    mod.update()
    p1 = {n: a.asnumpy().copy() for n, a in mod.get_params()[0].items()}
    mod._optimizer.rescale_grad = 0.0  # freeze: grad contribution zeroed
    mod._optimizer.wd = 0.0
    mod._optimizer.momentum = 0.0
    # rebuild kernel-free check: with rescale 0 and wd 0, only momentum moves
    # params; run enough steps for momentum to decay to ~nothing first
    for _ in range(60):
        mod.forward_backward(batch)
        mod.update()
    p2 = {n: a.asnumpy().copy() for n, a in mod.get_params()[0].items()}
    for n in p1:
        # params drifted only by decayed momentum, not by fresh gradients
        assert np.max(np.abs(p2[n] - p1[n])) < 1.0


@pytest.mark.parametrize("kernel",
                         ["sgd", "nag", "adam", "adagrad", "rmsprop"])
def test_fused_to_eager_handoff_preserves_momentum(kernel):
    # install_monitor mid-training drops to the eager path; momentum must
    # carry over so the trajectory matches a pure-eager run
    fused, eager = _kernel_pair(kernel)
    batches = _batches(6)
    for b in batches[:3]:
        fused.forward_backward(b)
        fused.update()
        eager.forward_backward(b)
        eager.update()

    class _NullMon:
        def install(self, exe):
            pass

    fused.install_monitor(_NullMon())
    assert fused._fused_step is None
    for b in batches[3:]:
        fused.forward_backward(b)
        fused.update()
        eager.forward_backward(b)
        eager.update()
    fargs = fused.get_params()[0]
    eargs = eager.get_params()[0]
    for name in fargs:
        np.testing.assert_allclose(fargs[name].asnumpy(), eargs[name].asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_reinit_optimizer_keeps_trained_params():
    mod = _make_module(True)
    for b in _batches(3):
        mod.forward_backward(b)
        mod.update()
    trained = {n: a.asnumpy().copy() for n, a in mod.get_params()[0].items()}
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01},
                       force_init=True)
    now = {n: a.asnumpy().copy() for n, a in mod.get_params()[0].items()}
    for n in trained:
        np.testing.assert_allclose(now[n], trained[n], err_msg=n)


def test_cross_format_state_load(tmp_path):
    # save on the fused path, load on the eager path (and back)
    fused = _make_module(True)
    for b in _batches(3):
        fused.forward_backward(b)
        fused.update()
    f = str(tmp_path / "f.states")
    fused.save_optimizer_states(f)

    eager = _make_module(False)
    for b in _batches(1):
        eager.forward_backward(b)
        eager.update()
    eager.load_optimizer_states(f)
    # momentum slot for fc1_weight should equal the fused one
    idx = eager._exec_group.param_names.index("fc1_weight")
    m_eager = eager._updater.states[idx].asnumpy()
    m_fused = np.asarray(fused._fused_step.slots["fc1_weight"][0])
    np.testing.assert_allclose(m_eager, m_fused, rtol=1e-6)

    e = str(tmp_path / "e.states")
    eager.save_optimizer_states(e)
    fused.load_optimizer_states(e)
    np.testing.assert_allclose(
        np.asarray(fused._fused_step.slots["fc1_weight"][0]), m_fused,
        rtol=1e-6)


def test_bucketing_shares_one_fused_store():
    """All bucket modules train through ONE CompiledTrainStep (shared master
    weights, per-bucket compiled programs) and learn across buckets."""
    import numpy as np

    from mxnet_tpu import rnn as rnn_mod

    rng = np.random.RandomState(0)
    sentences = []
    for _ in range(300):
        length = rng.randint(2, 8)
        start = rng.randint(1, 40)
        s = [start]
        for _ in range(length - 1):
            s.append((s[-1] * 31 + 7) % 40 or 1)
        sentences.append(s)
    it = rnn_mod.BucketSentenceIter(sentences, batch_size=16, buckets=[4, 8],
                                    seed=0)

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        embed = sym.Embedding(data, input_dim=40, output_dim=12, name="embed")
        cell = mx.rnn.LSTMCell(24, prefix="l0_")
        outputs, _ = cell.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = sym.FullyConnected(sym.Reshape(outputs, shape=(-1, 24)),
                                  num_hidden=40, name="fc")
        flat = sym.Reshape(label, shape=(-1,))
        return sym.SoftmaxOutput(pred, flat, use_ignore=True,
                                 ignore_label=-1, name="softmax"), \
            ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=it.default_bucket_key)
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(), num_epoch=6,
            eval_metric=mx.metric.Perplexity(ignore_label=-1))

    steps = {id(m._fused_step) for m in mod._buckets.values()
             if m._fused_step is not None}
    assert len(mod._buckets) >= 2          # both buckets were exercised
    assert len(steps) == 1                 # ... through one shared store
    store = next(iter(mod._buckets.values()))._fused_step
    assert store is not None
    assert len(store._fns) >= 2            # per-bucket compiled programs
    assert store.num_steps > 0

    # the trained model predicts the deterministic chain with low perplexity
    metric = mx.metric.Perplexity(ignore_label=-1)
    it.reset()
    score = dict(mod.score(it, metric))
    assert score["Perplexity"] < 3.0, score


def test_bucketing_on_data_parallel_mesh():
    """BucketingModule composes with the mesh executor: all buckets share
    one fused store AND shard batches over the 8-device data mesh."""
    import numpy as np

    from mxnet_tpu import rnn as rnn_mod

    rng = np.random.RandomState(0)
    sentences = []
    for _ in range(200):
        length = rng.randint(2, 8)
        start = rng.randint(1, 30)
        s = [start]
        for _ in range(length - 1):
            s.append((s[-1] * 7 + 3) % 30 or 1)
        sentences.append(s)
    it = rnn_mod.BucketSentenceIter(sentences, batch_size=16, buckets=[4, 8],
                                    seed=0)

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        emb = sym.Embedding(data, input_dim=30, output_dim=8, name="embed")
        cell = mx.rnn.LSTMCell(16, prefix="l0_")
        out, _ = cell.unroll(seq_len, inputs=emb, merge_outputs=True)
        pred = sym.FullyConnected(sym.Reshape(out, shape=(-1, 16)),
                                  num_hidden=30, name="fc")
        return sym.SoftmaxOutput(pred, sym.Reshape(label, shape=(-1,)),
                                 use_ignore=True, ignore_label=-1,
                                 name="softmax"), ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=it.default_bucket_key,
                                 context=[mx.cpu(i) for i in range(8)])
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(), num_epoch=7,
            eval_metric=mx.metric.Perplexity(ignore_label=-1))

    stores = {id(m._fused_step) for m in mod._buckets.values()
              if m._fused_step is not None}
    assert len(mod._buckets) >= 2 and len(stores) == 1
    # batches genuinely shard over the mesh's data axis
    group = mod._buckets[it.default_bucket_key]._exec_group
    assert group._mesh is not None
    spec = tuple(group.exec_.arg_dict["data"].data.sharding.spec)
    assert spec and spec[0] == "data", spec

    metric = mx.metric.Perplexity(ignore_label=-1)
    it.reset()
    score = dict(mod.score(it, metric))
    assert score["Perplexity"] < 6.0, score


def test_lr_scheduler_drives_fused_path():
    """A FactorScheduler's decaying lr reaches the compiled step (the
    hyper cache re-uploads when host-computed values change): fused and
    eager trajectories match under scheduling."""
    def build(fused):
        import os

        from mxnet_tpu import config

        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, name="fc", num_hidden=4)
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (8, 10))],
                 label_shapes=[("softmax_label", (8,))])
        mx.random.seed(11)
        mod.init_params(mx.initializer.Uniform(0.1))
        os.environ["MXNET_FUSED_TRAIN_STEP"] = "1" if fused else "0"
        config.refresh("MXNET_FUSED_TRAIN_STEP")
        sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.4,
                                             "lr_scheduler": sched})
        os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
        config.refresh("MXNET_FUSED_TRAIN_STEP")
        return mod

    fused, eager = build(True), build(False)
    assert fused._fused_step is not None and eager._fused_step is None
    for batch in _batches(8, seed=21):
        fused.forward_backward(batch)
        fused.update()
        eager.forward_backward(batch)
        eager.update()
    # the scheduler actually decayed the lr over those updates
    assert fused._optimizer._get_lr(0) < 0.4
    fargs = fused.get_params()[0]
    eargs = eager.get_params()[0]
    for name in fargs:
        np.testing.assert_allclose(fargs[name].asnumpy(),
                                   eargs[name].asnumpy(), rtol=1e-5,
                                   atol=1e-6, err_msg=name)
