"""Profiler spans: dump_profile must contain real per-op events
(reference: src/engine/profiler.h OprExecStat, python/mxnet/profiler.py)."""
import json
import os

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.io import DataBatch


def test_imperative_ops_record_spans(tmp_path):
    fname = str(tmp_path / "prof.json")
    mx.profiler.profiler_set_config(filename=fname)
    mx.profiler.profiler_set_state("run")
    a = nd.array(np.ones((4, 4), np.float32))
    b = nd.array(np.ones((4, 4), np.float32))
    (a + b).asnumpy()
    nd.dot(a, b).asnumpy()
    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    events = json.load(open(fname))["traceEvents"]
    assert events, "dump_profile wrote an empty trace"
    names = {e["name"] for e in events}
    assert "dot" in names


def test_monitored_executor_records_per_node_spans(tmp_path):
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc",
                              num_hidden=3), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mx.random.seed(0)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer()
    mon = mx.monitor.Monitor(interval=1)
    mod.install_monitor(mon)

    fname = str(tmp_path / "prof2.json")
    mx.profiler.profiler_set_config(filename=fname)
    mx.profiler.profiler_set_state("run")
    rng = np.random.RandomState(0)
    batch = DataBatch([nd.array(rng.rand(4, 6).astype(np.float32))],
                      [nd.array(rng.randint(0, 3, (4,)).astype(np.float32))])
    mon.tic()
    mod.forward(batch, is_train=False)
    mon.toc()
    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    names = {e["name"] for e in json.load(open(fname))["traceEvents"]}
    assert "fc" in names        # per-node span from the eager executor walk
    assert "softmax" in names


def test_fit_with_monitor_taps(tmp_path):
    # fit(monitor=...) must actually observe per-op outputs (the monitor
    # disables the fused step) — regression for the install-order bug
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc",
                              num_hidden=3), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())

    class Iter:
        batch_size = 4
        provide_data = [("data", (4, 6))]
        provide_label = [("softmax_label", (4,))]

        def __iter__(self):
            rng = np.random.RandomState(0)
            for _ in range(2):
                yield DataBatch(
                    [nd.array(rng.rand(4, 6).astype(np.float32))],
                    [nd.array(rng.randint(0, 3, (4,)).astype(np.float32))])

        def reset(self):
            pass

    seen = []
    mon = mx.monitor.Monitor(interval=1)
    orig = mon._observe

    def spy(name, arr):
        seen.append(name)
        return orig(name, arr)

    mon._observe = spy
    mod.fit(Iter(), num_epoch=1, monitor=mon,
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_step is None
    assert "fc_output" in seen


def test_registered_options_are_read_and_documented():
    """Every option ``config.py`` registers is read somewhere in the
    package outside ``config.py`` and has a row in ``docs/env_vars.md``,
    and that file names no ``MXNET_`` option that is not registered: an
    option deleted (or added) by halves fails here."""
    import re

    from mxnet_tpu import config

    pkg = os.path.dirname(os.path.abspath(mx.__file__))
    registered = set(config._REGISTRY)
    # 57 until PR 48: ``MXNET_PALLAS_DECODE`` went when a rule on the
    # call's shapes took its place (``ops.attention.decode_kernel_selected``);
    # 56 until PR 49: the switches of the fused update kernel and of the
    # block-shape tuner went with their code; 54 until PR 61: the MFU
    # denominator's override went with the host-clock table it divided
    assert len(registered) == 53 and "MXNET_PALLAS_DECODE" not in registered
    source = []
    for folder, _, files in os.walk(pkg):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and path != os.path.join(pkg, "config.py"):
                with open(path) as f:
                    source.append(f.read())
    read = set(re.findall(r"\bMXNET_[A-Z0-9_]+\b", "\n".join(source)))
    assert registered - read == set(), sorted(registered - read)

    with open(os.path.join(os.path.dirname(pkg), "docs", "env_vars.md")) as f:
        doc = f.read()
    rows = set(re.findall(r"^\| `(MXNET_[A-Z0-9_]+)` \|", doc, re.M))
    assert registered - rows == set(), sorted(registered - rows)
    named = set(re.findall(r"\bMXNET_[A-Z0-9_]+\b", doc))
    assert named - registered == set(), sorted(named - registered)
