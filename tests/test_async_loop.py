"""Asynchronous training loop (tier-1, CPU harness).

Device-side metric accumulation inside the donated train step, device
prefetch of upcoming batches, and bounded in-flight dispatch must change
SCHEDULING only: async and sync loops produce bit-identical losses and
final parameters, while measured device->host transfers per step drop by
the metric sync period (the acceptance contract of the async-loop PR).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, profiler
from mxnet_tpu.io import DataBatch, DevicePrefetchIter, NDArrayIter
from mxnet_tpu.metric import DeviceMetricAccumulator

ASYNC_KNOBS = ("MXNET_DEVICE_METRICS", "MXNET_DEVICE_PREFETCH",
               "MXNET_MAX_STEPS_IN_FLIGHT", "MXNET_METRIC_SYNC_PERIOD")

SYNC_ENV = {"MXNET_DEVICE_METRICS": "0", "MXNET_DEVICE_PREFETCH": "0",
            "MXNET_MAX_STEPS_IN_FLIGHT": "1", "MXNET_METRIC_SYNC_PERIOD": "0"}
ASYNC_ENV = {"MXNET_DEVICE_METRICS": "1", "MXNET_DEVICE_PREFETCH": "1",
             "MXNET_MAX_STEPS_IN_FLIGHT": "4", "MXNET_METRIC_SYNC_PERIOD": "4"}


@pytest.fixture
def loop_knobs():
    saved = {k: os.environ.get(k) for k in ASYNC_KNOBS}

    def set_knobs(env):
        for k, v in env.items():
            os.environ[k] = str(v)
            config.refresh(k)

    yield set_knobs
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
        config.refresh(k)


def _mlp(contexts=None):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=4)
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    return mx.mod.Module(net, context=contexts or mx.cpu())


def _dataset(n=64, d=10, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-1, 1, (n, d)).astype(np.float32)
    y = rng.randint(0, classes, (n,)).astype(np.float32)
    return X, y


def _fit(env, set_knobs, metric, num_epoch=3, batch_end_callback=None,
         contexts=None):
    set_knobs(env)
    X, y = _dataset()
    it = NDArrayIter(X, y, batch_size=8)
    mx.random.seed(7)
    mod = _mlp(contexts)
    profiler.reset_step_stats()
    mod.fit(it, eval_metric=metric, num_epoch=num_epoch,
            initializer=mx.initializer.Uniform(0.1), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            batch_end_callback=batch_end_callback)
    stats = profiler.step_stats()
    params = {n_: a.asnumpy() for n_, a in mod.get_params()[0].items()}
    return mod, params, stats


def test_async_vs_sync_bit_identical(loop_knobs):
    """The full async loop (device metrics + prefetch + 4 steps in flight)
    must match the synchronous loop bit for bit: same final params, same
    reported losses/metrics over a multi-epoch MLP fit."""
    m_sync = mx.metric.create(["acc", "ce"])
    m_async = mx.metric.create(["acc", "ce"])
    _, p_sync, _ = _fit(SYNC_ENV, loop_knobs, m_sync)
    mod, p_async, _ = _fit(ASYNC_ENV, loop_knobs, m_async)
    assert mod._fused_step is not None
    assert mod._fused_step._metric_acc is not None  # device path was active
    for name in p_sync:
        np.testing.assert_array_equal(p_sync[name], p_async[name],
                                      err_msg=name)
    vs, va = dict(m_sync.get_name_value()), dict(m_async.get_name_value())
    assert vs["accuracy"] == va["accuracy"]
    np.testing.assert_allclose(vs["cross-entropy"], va["cross-entropy"],
                               rtol=1e-6)


def test_metric_sync_period_bounds_host_transfers(loop_knobs):
    """With MXNET_METRIC_SYNC_PERIOD=N the measured metric device->host
    transfers per step drop to <= 1/N of the synchronous loop's (the
    acceptance criterion, asserted via the profiler/bench counters)."""
    _, _, s_sync = _fit(SYNC_ENV, loop_knobs, mx.metric.Accuracy())
    _, _, s_async = _fit(ASYNC_ENV, loop_knobs, mx.metric.Accuracy())
    assert s_sync["steps"] == s_async["steps"] > 0
    sync_rate = s_sync["host_syncs_per_step"]
    assert sync_rate >= 2.0  # label + pred materialize every step
    period = int(ASYNC_ENV["MXNET_METRIC_SYNC_PERIOD"])
    assert s_async["host_syncs_per_step"] <= sync_rate / period
    # device-side accumulation: well under the two transfers a step
    # (label + pred) of the synchronous host-metric loop
    assert s_async["host_syncs_per_step"] < 1.0, s_async
    for stats in (s_sync, s_async):
        assert 0.0 <= stats["input_stall_fraction"] <= 1.0, stats


def test_async_loop_with_metric_reading_callback(loop_knobs):
    """A callback that reads the metric every batch (Speedometer-style)
    forces drains mid-epoch; values must still match the sync loop."""
    seen = []

    def reader(param):
        seen.append(dict(param.eval_metric.get_name_value()))

    m_sync = mx.metric.Accuracy()
    m_async = mx.metric.Accuracy()
    _, p_sync, _ = _fit(SYNC_ENV, loop_knobs, m_sync,
                        batch_end_callback=reader)
    sync_seen, seen = list(seen), []
    _, p_async, _ = _fit(ASYNC_ENV, loop_knobs, m_async,
                         batch_end_callback=reader)
    for name in p_sync:
        np.testing.assert_array_equal(p_sync[name], p_async[name])
    assert len(seen) == len(sync_seen) > 0
    assert seen == sync_seen  # per-batch running accuracy identical


def test_device_metric_protocol_matches_host():
    """Each device-capable metric accumulates the same values through the
    DeviceMetricAccumulator as through host update()."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    pred = rng.uniform(0.05, 1.0, (16, 5)).astype(np.float32)
    pred /= pred.sum(axis=1, keepdims=True)
    label = rng.randint(0, 5, (16,)).astype(np.float32)
    reg_label = rng.uniform(-1, 1, (16, 5)).astype(np.float32)

    cases = [
        (mx.metric.Accuracy, label),
        (lambda: mx.metric.TopKAccuracy(top_k=3), label),
        (mx.metric.CrossEntropy, label),
        (lambda: mx.metric.Perplexity(ignore_label=0), label),
        (mx.metric.MSE, reg_label),
        (mx.metric.MAE, reg_label),
        (mx.metric.RMSE, reg_label),
        (mx.metric.Loss, label),
    ]
    for make, lab in cases:
        host, dev = make(), make()
        assert dev.device_supported(), type(dev).__name__
        host.update([lab], [pred])
        acc = DeviceMetricAccumulator(dev)
        acc.install()
        for _ in range(2):  # two batches: accumulation, not overwrite
            acc.commit(acc.update(acc.state, [jnp.asarray(lab)],
                                  [jnp.asarray(pred)]))
        host.update([lab], [pred])
        hn, hv = host.get()
        dn, dv = dev.get()  # drains the device state
        assert hn == dn
        np.testing.assert_allclose(hv, dv, rtol=1e-5, err_msg=str(hn))


def test_unsupported_metric_falls_back_to_host(loop_knobs):
    """A metric without a device mirror trains through the classic host
    path under the async loop — same values, no crash."""
    assert not DeviceMetricAccumulator.supported(mx.metric.F1())

    def feval(label, pred):
        return float((np.argmax(pred, axis=1) == label).mean())

    m_sync = mx.metric.CustomMetric(feval, name="custom_acc")
    m_async = mx.metric.CustomMetric(feval, name="custom_acc")
    assert not DeviceMetricAccumulator.supported(m_sync)
    _, p_sync, _ = _fit(SYNC_ENV, loop_knobs, m_sync)
    mod, p_async, _ = _fit(ASYNC_ENV, loop_knobs, m_async)
    assert mod._fused_step._metric_acc is None  # declined, not crashed
    for name in p_sync:
        np.testing.assert_array_equal(p_sync[name], p_async[name])
    assert m_sync.get() == m_async.get()


def test_composite_metric_accumulates_on_device(loop_knobs):
    comp = mx.metric.create(["acc", "ce"])
    assert DeviceMetricAccumulator.supported(comp)
    mod, _, stats = _fit(ASYNC_ENV, loop_knobs, comp)
    acc = mod._fused_step._metric_acc
    assert acc is not None and len(acc._leaves) == 2
    values = dict(comp.get_name_value())
    assert 0.0 <= values["accuracy"] <= 1.0
    assert values["cross-entropy"] > 0


def test_device_prefetch_iter_places_with_group_sharding(loop_knobs):
    """DevicePrefetchIter's worker thread lands batches on the mesh with
    the executor group's input sharding before the consumer sees them."""
    loop_knobs(SYNC_ENV)  # prefetch driven explicitly below
    contexts = [mx.cpu(i) for i in range(8)]
    X, y = _dataset(n=64)
    mod = _mlp(contexts)
    mod.bind(data_shapes=[("data", (16, 10))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.initializer.Uniform(0.1))
    it = DevicePrefetchIter(NDArrayIter(X, y, batch_size=16), module=mod,
                            depth=3)
    batches = list(it)
    assert len(batches) == 4
    group = mod._exec_group
    for batch in batches:
        data = batch.data[0].data
        if group._mesh is not None:  # distinct devices -> sharded on 'data'
            assert tuple(data.sharding.spec)[0] == "data"
    it.reset()
    assert len(list(it)) == 4
    it.close()


def test_fit_auto_wraps_device_prefetch(loop_knobs):
    loop_knobs(ASYNC_ENV)
    X, y = _dataset()
    it = NDArrayIter(X, y, batch_size=8)
    wrapped = {}
    mod = _mlp()

    orig = mod._wrap_train_data

    def spy(train_data):
        wrapped["iter"] = orig(train_data)
        return wrapped["iter"]

    mod._wrap_train_data = spy
    mod.fit(it, eval_metric="acc", num_epoch=2,
            initializer=mx.initializer.Uniform(0.1))
    assert isinstance(wrapped["iter"], DevicePrefetchIter)
    # fit closed its own wrapper on the way out
    assert wrapped["iter"]._thread is None


def test_update_metric_pulls_only_consumed_heads(loop_knobs):
    """metric.output_indices restricts which output heads are handed to
    (and materialized for) the metric — a two-head Group symbol only
    transfers the head the metric names."""
    import mxnet_tpu.metric as metric_mod

    loop_knobs(SYNC_ENV)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, name="fc", num_hidden=4)
    head = mx.sym.SoftmaxOutput(fc, name="softmax")
    aux = mx.sym.Activation(fc, name="aux_head", act_type="relu")
    net = mx.sym.Group([head, aux])
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))], for_training=False)
    mod.init_params(mx.initializer.Uniform(0.1))
    X, y = _dataset(n=8)
    batch = DataBatch([mx.nd.array(X)], [mx.nd.array(y)])
    mod.forward(batch, is_train=False)

    metric = mx.metric.Accuracy()
    metric.output_indices = [0]
    calls = []
    orig_host = metric_mod._host

    def counting_host(x):
        calls.append(x)
        return orig_host(x)

    metric_mod._host = counting_host
    try:
        mod._exec_group.update_metric(metric, batch.label)
    finally:
        metric_mod._host = orig_host
    assert len(calls) == 2  # 1 label + 1 consumed head; aux head untouched
    assert 0.0 <= metric.get()[1] <= 1.0
    # without selection, the length mismatch is the old failure mode
    plain = mx.metric.Accuracy()
    with pytest.raises(ValueError):
        mod._exec_group.update_metric(plain, batch.label)


def test_pipeline_module_async_loop_bit_identical(loop_knobs):
    """PipelineModule rides the same async loop: device-side metric
    accumulation inside the pipelined step + bounded in-flight dispatch
    leave the trajectory bit-identical to the sync loop."""
    from mxnet_tpu import symbol as sym

    d, classes, n_stages = 8, 2, 4
    rng = np.random.RandomState(3)
    X = rng.randn(64, d).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)

    def stage():
        s = sym.FullyConnected(sym.Variable("data"), num_hidden=d, name="fc")
        return sym.Activation(s, act_type="tanh", name="act")

    def head():
        h = sym.FullyConnected(sym.Variable("data"), num_hidden=classes,
                               name="out")
        return sym.SoftmaxOutput(h, name="softmax")

    def run(env, metric):
        loop_knobs(env)
        pipe = mx.mod.PipelineModule(
            stage(), head(), num_stages=n_stages, num_microbatches=4,
            context=[mx.cpu(i) for i in range(8)])
        it = NDArrayIter({"data": X}, {"softmax_label": y}, batch_size=16)
        mx.random.seed(11)
        np.random.seed(7)
        pipe.fit(it, optimizer="sgd",
                 optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
                 initializer=mx.initializer.Xavier(), num_epoch=3,
                 eval_metric=metric)
        return pipe, {n: a.asnumpy() for n, a in pipe.get_params()[0].items()}

    m_sync, m_async = mx.metric.Accuracy(), mx.metric.Accuracy()
    _, p_sync = run(SYNC_ENV, m_sync)
    pipe, p_async = run(ASYNC_ENV, m_async)
    assert pipe._metric_acc is not None  # device accumulation was active
    for name in p_sync:
        np.testing.assert_array_equal(p_sync[name], p_async[name],
                                      err_msg=name)
    assert m_sync.get() == m_async.get()

    # score() runs the forward-only program: updates must land on the host
    # even though the SAME metric object is armed for training (regression:
    # the device early-return swallowed validation updates -> NaN)
    it = NDArrayIter({"data": X}, {"softmax_label": y}, batch_size=16)
    score = dict(pipe.score(it, m_async))
    assert not np.isnan(score["accuracy"]) and score["accuracy"] > 0


def test_device_metrics_knob_off_detaches_between_fits(loop_knobs):
    """Turning MXNET_DEVICE_METRICS off (or switching metrics) between
    fit() calls must actually disarm the step's accumulator."""
    loop_knobs(ASYNC_ENV)
    X, y = _dataset()
    mod = _mlp()
    metric = mx.metric.Accuracy()
    mod.fit(NDArrayIter(X, y, batch_size=8), eval_metric=metric, num_epoch=1,
            initializer=mx.initializer.Uniform(0.1))
    assert mod._fused_step._metric_acc is not None

    # a different metric instance re-arms for the new one, not the old
    metric2 = mx.metric.Accuracy()
    mod.fit(NDArrayIter(X, y, batch_size=8), eval_metric=metric2, num_epoch=1,
            initializer=mx.initializer.Uniform(0.1))
    assert mod._fused_step._metric_acc.metric is metric2
    assert metric._device_sync is None  # old metric's hooks are unbound

    loop_knobs(dict(ASYNC_ENV, MXNET_DEVICE_METRICS="0"))
    mod.fit(NDArrayIter(X, y, batch_size=8), eval_metric=metric2, num_epoch=1,
            initializer=mx.initializer.Uniform(0.1))
    assert mod._fused_step._metric_acc is None
    assert 0.0 <= metric2.get()[1] <= 1.0


def test_fit_leaves_iterator_fresh_for_refit(loop_knobs):
    """fit() must leave the caller's iterator reset — a second fit() on the
    same iterator trains on real batches, not zero."""
    loop_knobs(ASYNC_ENV)
    X, y = _dataset()
    it = NDArrayIter(X, y, batch_size=8)
    mod = _mlp()
    mod.fit(it, eval_metric="acc", num_epoch=1,
            initializer=mx.initializer.Uniform(0.1))
    profiler.reset_step_stats()
    mod.fit(it, eval_metric="acc", num_epoch=1,
            initializer=mx.initializer.Uniform(0.1))
    assert profiler.step_stats()["steps"] == 8  # 64/8 batches, not 0


def test_trace_failing_metric_detaches_once(loop_knobs):
    """A metric whose device mirror fails to trace falls back to the host
    path ONCE — no attach/detach/recompile churn on every step."""
    loop_knobs(ASYNC_ENV)

    class BrokenDevice(mx.metric.Accuracy):
        def device_batch(self, label, pred):
            raise ValueError("no device mirror after all")

    metric = BrokenDevice()
    X, y = _dataset()
    mod = _mlp()
    attach_calls = []
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    step = mod._fused_step
    orig_attach = step.attach_metric
    step.attach_metric = lambda m: (attach_calls.append(1),
                                    orig_attach(m))[1]
    mod.fit(NDArrayIter(X, y, batch_size=8), eval_metric=metric, num_epoch=1,
            initializer=mx.initializer.Uniform(0.1))
    # armed once, trace failed once, rejected thereafter (idempotent
    # re-checks are fine; re-ARMING would recompile twice per step)
    assert step._metric_acc is None
    assert step._metric_rejected is metric
    assert len(attach_calls) <= 2
    assert 0.0 <= metric.get()[1] <= 1.0  # host path carried the epoch


def test_max_steps_in_flight_one_matches_default(loop_knobs):
    """The in-flight bound is a scheduling knob only."""
    env1 = dict(ASYNC_ENV, MXNET_MAX_STEPS_IN_FLIGHT="1")
    env8 = dict(ASYNC_ENV, MXNET_MAX_STEPS_IN_FLIGHT="8")
    _, p1, _ = _fit(env1, loop_knobs, mx.metric.Accuracy())
    _, p8, _ = _fit(env8, loop_knobs, mx.metric.Accuracy())
    for name in p1:
        np.testing.assert_array_equal(p1[name], p8[name])


def test_score_device_metrics_skip_per_batch_transfers(loop_knobs):
    """PR-4 satellite (ROADMAP PR-3 open item): score() accumulates the
    metric INSIDE a forward-only executor program — same values as the
    host path, but the per-batch 2-transfer floor (label + pred) drops to
    one accumulator drain for the whole pass."""
    loop_knobs(SYNC_ENV)
    X, y = _dataset()
    it = NDArrayIter(X, y, batch_size=8)
    mod = _mlp()
    mod.fit(it, eval_metric="acc", num_epoch=1,
            initializer=mx.initializer.Uniform(0.1), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    n_batches = len(X) // 8

    loop_knobs({"MXNET_DEVICE_METRICS": "0"})
    profiler.reset_step_stats()
    host = dict(mod.score(it, mx.metric.create(["acc", "ce"])))
    host_d2h = profiler.step_stats()["metric_d2h"]

    loop_knobs({"MXNET_DEVICE_METRICS": "1"})
    profiler.reset_step_stats()
    dev = dict(mod.score(it, mx.metric.create(["acc", "ce"])))
    dev_d2h = profiler.step_stats()["metric_d2h"]

    assert host["accuracy"] == dev["accuracy"]
    np.testing.assert_allclose(host["cross-entropy"], dev["cross-entropy"],
                               rtol=1e-5)
    assert host_d2h >= 2 * n_batches  # the classic per-batch floor
    assert dev_d2h <= host_d2h / 2    # one batched drain, not per-batch
    assert dev_d2h <= 8


def test_score_device_metrics_reuse_compiled_step(loop_knobs):
    """Scoring twice with the same metric reuses the compiled eval step
    (fit's per-epoch validation must not recompile every epoch)."""
    loop_knobs(SYNC_ENV)
    loop_knobs({"MXNET_DEVICE_METRICS": "1"})
    X, y = _dataset()
    it = NDArrayIter(X, y, batch_size=8)
    mod = _mlp()
    mod.fit(it, eval_metric="acc", num_epoch=1,
            initializer=mx.initializer.Uniform(0.1), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    metric = mx.metric.Accuracy()
    first = dict(mod.score(it, metric))
    step = mod._eval_step_cache[2]
    second = dict(mod.score(it, metric))
    assert mod._eval_step_cache[2] is step
    assert first == second


def test_score_unsupported_metric_stays_on_host(loop_knobs):
    """A metric without a device mirror scores through the classic path,
    values intact."""
    loop_knobs(SYNC_ENV)
    loop_knobs({"MXNET_DEVICE_METRICS": "1"})
    X, y = _dataset()
    it = NDArrayIter(X, y, batch_size=8)
    mod = _mlp()
    mod.fit(it, eval_metric="acc", num_epoch=1,
            initializer=mx.initializer.Uniform(0.1), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    host_only = mx.metric.CustomMetric(
        lambda label, pred: float((np.argmax(pred, 1) == label).mean()),
        name="np_acc")
    val = dict(mod.score(it, host_only))["np_acc"]
    ref = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    np.testing.assert_allclose(val, ref, rtol=1e-6)


def test_device_prefetch_falls_back_on_bucketed_batches(loop_knobs):
    """PR-4 satellite: DevicePrefetchIter must not device_put a
    shape-varying (bucketed) batch with the bound executor's stale
    sharding — mismatching arrays pass through untouched (the consumer
    places them per-bucket) and the fallback is counted, not silent."""
    loop_knobs(ASYNC_ENV)
    mod = _mlp()
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.initializer.Uniform(0.1))

    batches = [
        DataBatch([mx.nd.array(np.full((8, 10), i, np.float32))],
                  [mx.nd.array(np.zeros((8,), np.float32))])
        if i != 1 else
        DataBatch([mx.nd.array(np.full((4, 10), i, np.float32))],
                  [mx.nd.array(np.zeros((4,), np.float32))])
        for i in range(3)
    ]

    class TwoShapeIter(mx.io.DataIter):
        def __init__(self):
            super().__init__(8)
            self.i = 0

        @property
        def provide_data(self):
            return [mx.io.DataDesc("data", (8, 10))]

        @property
        def provide_label(self):
            return [mx.io.DataDesc("softmax_label", (8,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= len(batches):
                raise StopIteration
            b = batches[self.i]
            self.i += 1
            return b

    it = DevicePrefetchIter(TwoShapeIter(), module=mod)
    try:
        got = list(it)
    finally:
        it.close()
    assert len(got) == 3
    # the odd-shaped batch passed through identically; bound-shape batches
    # were placed (fresh device-resident NDArrays)
    assert got[1].data[0] is batches[1].data[0]
    assert got[0].data[0] is not batches[0].data[0]
    assert got[2].data[0] is not batches[2].data[0]
    assert it.fallback_batches == 1
    for i, b in enumerate(got):
        assert float(b.data[0].asnumpy()[0, 0]) == float(i)


def test_fit_validation_shares_train_metric_instance(loop_knobs):
    """fit() defaults validation_metric to the TRAIN metric instance whose
    drain hooks the fused step's accumulator owns; the eval device path
    must not steal them — Train-* values stay real in every epoch.

    Runs with boundary-only drains (MXNET_METRIC_SYNC_PERIOD=0, the
    default): the epoch-end metric read then depends entirely on the
    drain hook a hijacking eval pass would have nulled."""
    import logging

    loop_knobs(dict(ASYNC_ENV, MXNET_METRIC_SYNC_PERIOD="0"))
    messages = []

    class Capture(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    logger = logging.getLogger("test_fit_shared_metric")
    logger.setLevel(logging.INFO)
    logger.addHandler(Capture())
    X, y = _dataset()
    mod = _mlp()
    mod.logger = logger
    mx.random.seed(7)
    mod.fit(NDArrayIter(X, y, batch_size=8),
            eval_data=NDArrayIter(X, y, batch_size=8),
            eval_metric="acc", num_epoch=3,
            initializer=mx.initializer.Uniform(0.1), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    train_lines = [m for m in messages if "Train-accuracy" in m]
    assert len(train_lines) == 3
    for line in train_lines:
        val = float(line.rsplit("=", 1)[1])
        assert np.isfinite(val) and 0.0 < val <= 1.0, train_lines
