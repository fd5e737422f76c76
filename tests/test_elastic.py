"""Elastic training tests — async fenced checkpointing, deterministic
kill-and-resume, and liveness-driven mesh shrink/regrow, all on the
8-virtual-device CPU mesh with every failure injected deterministically
(FaultInjector) instead of waiting on wall clocks.

The two headline guarantees:

* **kill-and-resume equality** — a fit() killed mid-epoch and resumed
  from the last committed fence replays to BIT-identical params and
  metric history vs an uninterrupted run (single device AND the
  data-parallel mesh), because the fence carries the RNG chain, metric
  sums and iterator cursor alongside params/slots;
* **shrink/regrow** — a heartbeat-declared dead rank mid-fit re-forms
  the 'data' axis 8->4 on the survivors and resumes from the last fence
  (no step skipped, loss finite), and the rank's return regrows 4->8.
"""
import json
import logging
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, elastic
from mxnet_tpu import profiler
from mxnet_tpu.io import DevicePrefetchIter, NDArrayIter
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.parallel.health import FailureMonitor, Heartbeat


def _net(hidden=16, classes=4):
    s = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=hidden,
                              name="fc1")
    s = mx.sym.Activation(s, act_type="relu")
    s = mx.sym.FullyConnected(s, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(s, name="softmax")


def _dataset(n, features=8, classes=4, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, features)).astype(np.float32)
    Y = rng.randint(0, classes, size=(n,)).astype(np.float32)
    return X, Y


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.rows = []

    def emit(self, record):
        self.rows.append(record.getMessage())


def _fit(tag, contexts, mesh_config, X, Y, batch_size, num_epoch,
         elastic_ctl=None, seed=42, batch_end_callback=None,
         last_batch_handle="pad"):
    """One seeded fit; returns (module, train-accuracy history lines)."""
    mx.random.seed(seed)
    cap = _Capture()
    lg = logging.Logger("elastic-" + tag)
    lg.addHandler(cap)
    mod = mx.mod.Module(_net(), context=contexts, mesh_config=mesh_config,
                        logger=lg)
    mod.fit(NDArrayIter(X, Y, batch_size=batch_size,
                        last_batch_handle=last_batch_handle),
            optimizer="adam", optimizer_params={"learning_rate": 5e-3},
            initializer=mx.initializer.Xavier(), num_epoch=num_epoch,
            eval_metric="acc", elastic=elastic_ctl,
            batch_end_callback=batch_end_callback)
    return mod, [r for r in cap.rows if "Train-accuracy" in r]


def _assert_params_identical(mod_a, mod_b):
    pa, _ = mod_a.get_params()
    pb, _ = mod_b.get_params()
    for name in pa:
        a, b = pa[name].asnumpy(), pb[name].asnumpy()
        assert np.array_equal(a, b), \
            "%s differs (max |d|=%g)" % (name, np.abs(a - b).max())


# ---------------------------------------------------------------------------
# kill-and-resume equality
# ---------------------------------------------------------------------------
def test_kill_and_resume_bit_identical_module(tmp_path):
    """fit() killed at an arbitrary mid-epoch step and resumed from the
    last fence produces BIT-identical params and metric history to the
    uninterrupted run (single-device Module)."""
    X, Y = _dataset(96)
    args = dict(contexts=mx.cpu(), mesh_config=None, X=X, Y=Y,
                batch_size=8, num_epoch=2)          # 12 steps/epoch

    mod_a, hist_a = _fit("uninterrupted", **args)

    d = str(tmp_path / "ck")
    # sync saves: every period-th fence commits deterministically, so the
    # kill provably resumes from a MID-EPOCH fence, not from step 0
    inj = elastic.FaultInjector().kill_at(17)
    ctl = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=5, async_write=False),
        injector=inj)
    with pytest.raises(elastic.WorkerKilled):
        _fit("killed", elastic_ctl=ctl, **args)
    assert checkpoint.latest_step(d) == 15          # epoch 1, 3 batches in
    with open(os.path.join(d, "15", "elastic.json")) as f:
        meta = json.load(f)
    assert meta["epoch"] == 1 and meta["nbatch_done"] == 3

    # crash debris from a previous run (below the newest commit) must be
    # swept by the next successful write, not accumulate shard payloads
    elastic.FaultInjector.torn_checkpoint(d, 1)

    ctl2 = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=5, async_write=False))
    mod_b, hist_b = _fit("resumed", elastic_ctl=ctl2, **args)
    assert ctl2.recoveries == 1
    assert not os.path.isdir(os.path.join(d, "1"))   # debris pruned
    _assert_params_identical(mod_a, mod_b)
    # epoch 0 completed before the kill; the resumed run re-logs only the
    # interrupted epoch — its metric value must match exactly (the fence
    # carried both the host sums and the pending device accumulators)
    assert hist_b == hist_a[-len(hist_b):]
    assert hist_a[-1] == hist_b[-1]

    # resume=0 over a directory holding this run's commits is REFUSED:
    # mixing lineages would let a later mid-fit recovery restore the old
    # run's state (its higher step numbers win every restore/prune)
    ctl3 = elastic.ElasticController(checkpointer=elastic.Checkpointer(
        d, period=5, async_write=False, resume=False))
    with pytest.raises(mx.MXNetError, match="previous run"):
        _fit("refused", elastic_ctl=ctl3, **args)

    # and a begin_epoch AHEAD of the fence is refused too: restoring
    # mid-epoch-1 params into an epoch-9 run is a state no uninterrupted
    # run could produce
    ctl4 = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=5, async_write=False))
    mod4 = mx.mod.Module(_net(), context=mx.cpu(),
                         logger=logging.Logger("elastic-behind"))
    with pytest.raises(mx.MXNetError, match="behind"):
        mod4.fit(NDArrayIter(X, Y, batch_size=8), optimizer="adam",
                 initializer=mx.initializer.Xavier(), num_epoch=12,
                 begin_epoch=9, eval_metric="acc", elastic=ctl4)


def test_kill_and_resume_roll_over_iterator(tmp_path):
    """Stateful-reset iterators too: NDArrayIter roll_over carries the
    tail cursor across reset(), so the resumed run replays the fresh
    iterator's prior-epoch lifecycle before restoring the mid-epoch
    cursor — params still bit-identical."""
    X, Y = _dataset(92)                  # 92 % 8 != 0: roll_over is live
    args = dict(contexts=mx.cpu(), mesh_config=None, X=X, Y=Y,
                batch_size=8, num_epoch=2, last_batch_handle="roll_over")

    mod_a, hist_a = _fit("ro-uninterrupted", **args)

    d = str(tmp_path / "ck")
    inj = elastic.FaultInjector().kill_at(17)   # epoch 1 (12+11 batches)
    ctl = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=5, async_write=False),
        injector=inj)
    with pytest.raises(elastic.WorkerKilled):
        _fit("ro-killed", elastic_ctl=ctl, **args)
    assert checkpoint.latest_step(d) == 15

    ctl2 = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=5, async_write=False))
    mod_b, hist_b = _fit("ro-resumed", elastic_ctl=ctl2, **args)
    _assert_params_identical(mod_a, mod_b)
    assert hist_a[-1] == hist_b[-1]


def test_kill_and_resume_bit_identical_mesh(tmp_path):
    """The same equality on the data-parallel mesh: fence shards are
    written per the 8-device placement and restore re-shards them."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device CPU platform")
    X, Y = _dataset(160)
    ctxs = [mx.cpu(i) for i in range(8)]
    args = dict(contexts=ctxs, mesh_config=MeshConfig(data=8), X=X, Y=Y,
                batch_size=16, num_epoch=1)         # 10 steps

    mod_a, hist_a = _fit("mesh-uninterrupted", **args)

    d = str(tmp_path / "ck")
    inj = elastic.FaultInjector().kill_at(7)
    ctl = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=4, async_write=False),
        injector=inj)
    with pytest.raises(elastic.WorkerKilled):
        _fit("mesh-killed", elastic_ctl=ctl, **args)
    assert checkpoint.latest_step(d) == 4

    ctl2 = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(d, period=4, async_write=False))
    mod_b, hist_b = _fit("mesh-resumed", elastic_ctl=ctl2, **args)
    assert ctl2.recoveries == 1
    _assert_params_identical(mod_a, mod_b)
    assert hist_a == hist_b


# ---------------------------------------------------------------------------
# shrink / regrow
# ---------------------------------------------------------------------------
def test_shrink_and_regrow_data_axis(tmp_path):
    """A heartbeat-declared dead rank mid-fit triggers automatic 8->4
    'data'-axis re-formation and resume from the last fence (no NaN, no
    step skipped); the rank's return regrows back to 8."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device CPU platform")
    X, Y = _dataset(160)
    hb = str(tmp_path / "hb")
    ck = str(tmp_path / "ck")
    # 2 workers x 4 data rows each; both stamp once at launch
    Heartbeat(hb, 0).beat()
    Heartbeat(hb, 1).beat()
    # rank 1 goes stale at step 6 (backdated stamp — no wall-clock wait)
    # and returns at step 14
    inj = (elastic.FaultInjector()
           .stale_heartbeat_at(6, hb, 1, age=1e9)
           .revive_heartbeat_at(14, hb, 1))
    mon = FailureMonitor(hb, num_workers=2, my_rank=0, timeout=1e6, grace=0)
    ctl = elastic.ElasticController(
        checkpointer=elastic.Checkpointer(ck, period=2, async_write=False),
        monitor=mon, injector=inj)

    seen = []
    holder = {}

    def cb(p):
        mesh = holder["mod"]._exec_group._mesh
        seen.append((p.epoch, p.nbatch,
                     dict(mesh.shape)["data"] if mesh is not None else 1))

    mx.random.seed(0)
    cap = _Capture()
    lg = logging.Logger("elastic-shrink")
    lg.addHandler(cap)
    mod = mx.mod.Module(_net(), context=[mx.cpu(i) for i in range(8)],
                        mesh_config=MeshConfig(data=8), logger=lg)
    holder["mod"] = mod
    mod.fit(NDArrayIter(X, Y, batch_size=16),  # 10 steps/epoch
            optimizer="adam", optimizer_params={"learning_rate": 5e-3},
            initializer=mx.initializer.Xavier(), num_epoch=2,
            eval_metric="acc", batch_end_callback=cb, elastic=ctl)

    datas = [d for (_, _, d) in seen]
    # the mesh really was 8-wide, shrank to 4, and finished regrown to 8
    assert 8 in datas and 4 in datas and datas[-1] == 8, datas
    assert ctl.recoveries == 2
    # no step skipped: each epoch's batch indices cover 0..9 contiguously
    for ep in (0, 1):
        covered = sorted(set(n for (e, n, _) in seen if e == ep))
        assert covered == list(range(10)), (ep, covered)
    # the loss curve continued: params finite, both epoch metrics logged
    pa, _ = mod.get_params()
    for name in pa:
        assert np.isfinite(pa[name].asnumpy()).all(), name
    hist = [r for r in cap.rows if "Train-accuracy" in r]
    assert len(hist) == 2 and all("nan" not in h.lower() for h in hist)
    # per-replica batch rescaled: global batch 16 over data=4 during the
    # shrink means 4 rows/device instead of 2 — shapes were asserted
    # implicitly by the steps running; check the checkpoint round-tripped
    # across DIFFERENT mesh widths (a 4-device fence restored onto 8)
    assert checkpoint.latest_step(ck) is not None

    # the telemetry acceptance half: exporting the always-on timeline
    # right after this run yields VALID chrome-trace JSON whose events
    # cover the fit (epoch spans, fused-step program spans) AND the
    # elastic protocol — the heartbeat transitions, both mesh re-forms,
    # the fence checkpoints and their writer-thread commits
    from mxnet_tpu import obs
    from mxnet_tpu.test_utils import assert_chrome_trace

    assert_chrome_trace(
        obs.timeline.export(),
        required_names=("fit_epoch", "train_step", "heartbeat_shrink",
                        "heartbeat_regrow", "elastic_shrink",
                        "elastic_regrow", "ckpt_fence", "ckpt_commit"))


# ---------------------------------------------------------------------------
# async overlap + stall accounting
# ---------------------------------------------------------------------------
def test_async_checkpoint_overlaps_and_stalls_less_than_sync(tmp_path):
    """With MXNET_CKPT_ASYNC=1, steps are dispatched WHILE a write is in
    flight (counted, not inferred from timing), and the measured
    checkpoint_stall_fraction is strictly below the synchronous-save
    configuration on the same trace — the Check-Freq decoupling."""
    features, hidden, classes = 128, 512, 8
    rng = np.random.RandomState(0)
    X = rng.normal(size=(240, features)).astype(np.float32)
    Y = rng.randint(0, classes, size=(240,)).astype(np.float32)

    def run(async_write, directory):
        mx.random.seed(1)
        ctl = elastic.ElasticController(checkpointer=elastic.Checkpointer(
            str(directory), period=2, async_write=async_write))
        mod = mx.mod.Module(_net(hidden=hidden, classes=classes),
                            context=mx.cpu(),
                            logger=logging.Logger("elastic-a%d"
                                                  % int(async_write)))
        profiler.reset_step_stats()
        mod.fit(NDArrayIter(X, Y, batch_size=12),  # 20 steps
                optimizer="adam",
                optimizer_params={"learning_rate": 1e-3},
                initializer=mx.initializer.Xavier(), num_epoch=1,
                eval_metric="acc", elastic=ctl)
        return ctl.checkpointer, profiler.step_stats()

    ck_async, stats_async = run(True, tmp_path / "async")
    ck_sync, stats_sync = run(False, tmp_path / "sync")

    # deterministic halves first: the async run really overlapped steps
    # with an in-flight write, and never blocked the loop to queue one
    assert ck_async.steps_during_write > 0
    assert ck_async.writes >= 1
    assert ck_async.writes + ck_async.skipped_busy >= 10  # every fence seen
    # the sync run commits EVERY fence inline (initial + 10 periodic)
    assert ck_sync.writes == 11 and ck_sync.skipped_busy == 0
    assert ck_sync.steps_during_write == 0

    # the stall comparison the async design exists to win: the sync loop
    # pays d2h + serialize + write per fence on the loop thread, async
    # only the copy dispatches (margin is structural — sync does strictly
    # more loop-thread work per fence — so noise cannot flip it)
    assert stats_async["ckpt_stall_s"] < stats_sync["ckpt_stall_s"], \
        (stats_async["ckpt_stall_s"], stats_sync["ckpt_stall_s"])
    assert stats_async["checkpoint_stall_fraction"] < \
        stats_sync["checkpoint_stall_fraction"], (stats_async, stats_sync)
    # both runs produced resumable state and the accounting fields exist
    assert stats_sync["last_ckpt_ms"] > 0 and stats_async["last_ckpt_ms"] > 0
    assert stats_async["recoveries"] == stats_sync["recoveries"] == 0
    for stats in (stats_async, stats_sync):
        assert 0.0 <= stats["checkpoint_stall_fraction"] <= 1.0, stats


# ---------------------------------------------------------------------------
# iterator fast-forward protocol
# ---------------------------------------------------------------------------
def test_fast_forward_matches_draining(tmp_path):
    """NDArrayIter's O(1) cursor jump lands on exactly the batch that
    draining n batches reaches, and the prefetching wrapper fast-forwards
    by draining its queue (its source is read-ahead, so the queue is the
    only honest position)."""
    X, Y = _dataset(56, seed=3)

    drained = NDArrayIter(X, Y, batch_size=8)
    for _ in range(3):
        drained.next()
    jumped = NDArrayIter(X, Y, batch_size=8)
    jumped.fast_forward(3)
    state_after_3 = jumped.checkpoint_state()
    assert state_after_3 == {"cursor": 2 * 8}
    a, b = drained.next(), jumped.next()
    np.testing.assert_array_equal(a.data[0].asnumpy(), b.data[0].asnumpy())
    np.testing.assert_array_equal(a.label[0].asnumpy(),
                                  b.label[0].asnumpy())

    # the wrapper: identical batch after fast_forward despite read-ahead
    wrapped = DevicePrefetchIter(NDArrayIter(X, Y, batch_size=8),
                                 placement=lambda kind, name, arr: arr)
    try:
        wrapped.fast_forward(3)
        w = wrapped.next()
        np.testing.assert_array_equal(w.data[0].asnumpy(),
                                      a.data[0].asnumpy())
    finally:
        wrapped.close()

    # restore_state round-trips the seekable cursor
    fresh = NDArrayIter(X, Y, batch_size=8)
    fresh.restore_state(state_after_3)
    np.testing.assert_array_equal(fresh.next().data[0].asnumpy(),
                                  b.data[0].asnumpy())
