"""``train_fit``: fixed-shape batches through ``Module.fit``, timed as whole
steps between two fences.

One epoch spans warm-up, window and tail, so no iterator reset and no
epoch-boundary drain falls inside the window; the batches are on the device
before it opens; inside it the benchmark's own code is one
``time.perf_counter()`` into a preallocated list per step.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.io import DataBatch, DataDesc, DevicePrefetchIter

from .. import correct, harness, timing, traffic as traffic_mod, weights

MAX_STEPS = 200000


def _shapes(cfg, traffic):
    batch = int(traffic["batch"])
    if "seq_len" in traffic:
        t = int(traffic["seq_len"])
        return (batch, t), (batch, t), "NT"
    return (batch,) + tuple(cfg["image_shape"]), (batch,), None


class PoolIter(mx.io.DataIter):
    """Cycles device-resident batches until told to stop (or for ``limit``
    batches)."""

    def __init__(self, batches, batch_size, provide_data, provide_label,
                 limit=None):
        super().__init__(batch_size)
        self.provide_data = provide_data
        self.provide_label = provide_label
        self._batches, self._limit = batches, limit
        self.stop = False
        self.served = 0

    def next(self):
        if self.stop or (self._limit is not None
                         and self.served >= self._limit):
            raise StopIteration
        b = self._batches[self.served % len(self._batches)]
        self.served += 1
        return b

    def reset(self):
        pass


class Window:
    """The ``batch_end_callback``: counts warm-up steps, opens the window
    behind a fence, stamps each step, and closes it behind a fence once
    ``seconds`` have passed."""

    def __init__(self, fence, seconds, warmup, counters, tracer, source,
                 phases, memory, counted, max_steps=MAX_STEPS):
        self._fence, self._seconds, self._warmup = fence, seconds, warmup
        self._counted = counted
        self._counters, self._tracer, self._source = counters, tracer, source
        self._phases, self._memory = phases, memory
        self.stamps = [0.0] * max_steps
        self.n = 0
        self.calls = 0
        self.state = 0              # 0 warming, 1 measuring, 2 done
        self.t0 = self.t1 = None
        self.stats = None
        self.gc0 = self.gc1 = None
        self.counters_before = None
        self._span = None

    def __call__(self, param):
        if self.state == 1:
            now = time.perf_counter()
            self.stamps[self.n] = now
            self.n += 1
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = self._tracer.span("fit_step")
                self._span.__enter__()
            if now - self.t0 >= self._seconds or self.n == len(self.stamps):
                self._close()
        elif self.state == 0:
            self.calls += 1
            if self.calls == 1:
                self._phases.mark("compile_or_cache")
            if self.calls >= self._warmup:
                self._open()

    def _open(self):
        self._fence()
        self._phases.mark("warmup")
        self._memory.sample()
        harness.quiesce()
        self.gc0 = harness.gc_counts()
        self.counters_before = harness.program_counters(self._counted)
        profiler.reset_step_stats()
        self._tracer.start()
        self._phases.mark("trace_start")
        if self._tracer.on:
            self._span = self._tracer.span("fit_step")
            self._span.__enter__()
        self._counters.window_open = True
        self.state = 1
        self.t0 = time.perf_counter()

    def _close(self):
        self._fence()
        self.t1 = time.perf_counter()
        self._phases.mark("window")
        self.state = 2
        self._counters.window_open = False
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self.stats = profiler.step_stats()
        self.gc1 = harness.gc_counts()
        self._memory.sample()
        self._source.stop = True
        self._tracer.stop(self._phases)


def model_of(cfg, traffic):
    """``(sym, layers, dshape, lshape, layout, args, auxs)``: the symbol at
    the cell's size, the shapes of a batch, and the shapes of the weights
    and of the auxiliary states."""
    layers = cfg[traffic["layers_key"]] if "layers_key" in traffic else None
    overrides = {}
    if layers is not None:
        overrides["num_layers"] = layers
    if "seq_len" in traffic:
        overrides["seq_len"] = int(traffic["seq_len"])
    sym = harness.build_symbol(cfg, **overrides)
    dshape, lshape, layout = _shapes(cfg, traffic)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape,
                                                softmax_label=lshape)
    args = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    auxs = dict(zip(sym.list_auxiliary_states(), aux_shapes))
    return sym, layers, dshape, lshape, layout, args, auxs


def reference_rows(cfg, traffic, layers):
    """``(n, fwd)``: ``fwd(params, data)`` is the plain reference's logits
    over the part of a batch that the comparison reads, ``n`` rows of the
    system's output: the first ``check_tokens`` positions of the first
    sequence, or the first ``check_samples`` images.  A configuration with
    ``check_is_train`` (BatchNorm) is computed in training mode, statistics
    over the whole batch on both sides: with seeded moving statistics an
    evaluation-mode ResNet saturates, and the batch's own are what the cell
    trains with."""
    ref = correct.reference_of(cfg)
    if "seq_len" in traffic:
        n = min(int(traffic.get("check_tokens", 256)),
                int(traffic["seq_len"]))
        return n, jax.jit(
            lambda p, x: ref.forward(p, cfg, x[:1, :n], layers)[0])
    training = bool(cfg.get("check_is_train", False))
    n = min(int(traffic.get("check_samples", 8)), int(traffic["batch"]))
    return n, jax.jit(lambda p, x: ref.forward(
        p, cfg, x if training else x[:n], layers, training=training)[:n])


def check_against_reference(mod, cfg, traffic, batch, layers, seeded,
                            limits):
    """The system's forward pass on one of the run's own batches against the
    plain reference on the same weights (``reference_rows``): log-
    probabilities, and for images the loss.

    Made after the window, with the module set back to the seeded weights
    (``seeded()`` makes them again): the weights the window leaves depend
    on how long it ran, and a trained net's larger logits carry a larger
    bf16 error, so only the seeded ones give one reading to hold a limit
    to."""
    params, arg_params, aux_params = seeded()
    mod.set_params(arg_params, aux_params)
    mod.forward(batch, is_train=bool(cfg.get("check_is_train", False)))
    probs = mod.get_outputs()[0].data
    n, fwd = reference_rows(cfg, traffic, layers)
    logits = fwd(params, batch.data[0].data)
    checks = [correct.compare_logp(probs[:n], logits, limits["logp_atol"])]
    if "loss_rtol" in limits:
        checks.append(correct.compare_loss(
            probs[:n], logits, batch.label[0].data[:n],
            limits["loss_rtol"]))
    return checks


def control_case(cfg, traffic, seed):
    """For ``chipbench.control``: the seeded weights, ``forward(params)``
    as this driver's comparison calls the reference on the run's first
    batch, and the type the cell computes in."""
    _, layers, _, _, _, args, auxs = model_of(cfg, traffic)
    params = weights.make_params(dict(args, **auxs), cfg, seed,
                                 cfg["master_dtype"])
    data, _ = traffic_mod.train_batches(traffic, cfg, seed)()[0]
    _, fwd = reference_rows(cfg, traffic, layers)
    return {"params": params, "forward": lambda p: fwd(p, data),
            "dtype": cfg["compute_dtype"]}


def run(job):
    cfg, traffic, phases = job["config"], job["traffic"], job["phases"]
    seed, tracer = job["seed"], job["tracer"]
    # what the program's counters held before this run traced anything
    counted = harness.program_counters()
    # the limits this run's comparison needs, read before anything is built:
    # a configuration that states none fails here and not after the window
    limits = {k: correct.limit(cfg, "train_fit", k) for k in (
        ("logp_atol",) if "seq_len" in traffic
        else ("logp_atol", "loss_rtol"))}
    sym, layers, dshape, lshape, layout, args, auxs = model_of(cfg, traffic)
    if layout:
        descs = ([DataDesc("data", dshape, layout=layout)],
                 [DataDesc("softmax_label", lshape, layout=layout)])
    else:
        descs = ([DataDesc("data", dshape)],
                 [DataDesc("softmax_label", lshape)])
    # a traffic file's `mesh` (axis sizes) is how a cell shards anything
    # but the batch; without it Module spreads the batch over the contexts
    mesh = {} if "mesh" not in traffic else {
        "mesh_config": mx.parallel.MeshConfig(**traffic["mesh"])}
    mod = mx.mod.Module(sym, context=job["contexts"],
                        compute_dtype=cfg["compute_dtype"], **mesh)
    mod.bind(data_shapes=descs[0], label_shapes=descs[1])
    phases.mark("import_and_bind")

    ctx0 = job["contexts"][0]

    def seeded():
        p = weights.make_params(dict(args, **auxs), cfg, seed,
                                cfg["master_dtype"])
        return (p, {n: mx.nd.NDArray(p[n], ctx0) for n in args},
                {n: mx.nd.NDArray(p[n], ctx0) for n in auxs})

    params, arg_params, aux_params = seeded()
    mod.init_params(arg_params=arg_params, aux_params=aux_params)
    del params, arg_params, aux_params
    pool = traffic_mod.train_batches(traffic, cfg, seed)()
    raw = [DataBatch([mx.nd.NDArray(d, ctx0)], [mx.nd.NDArray(l, ctx0)],
                     pad=0, provide_data=descs[0], provide_label=descs[1])
           for d, l in pool]
    batch = int(traffic["batch"])
    once = DevicePrefetchIter(
        PoolIter(raw, batch, descs[0], descs[1], limit=len(raw)),
        module=mod)
    placed = list(once)
    once.close()
    jax.block_until_ready([b.data[0].data for b in placed])
    phases.mark("weights_and_data")

    source = PoolIter(placed, batch, descs[0], descs[1])
    window = Window(lambda: jax.block_until_ready(mod._fused_step.params),
                    job["seconds"], int(traffic["warmup_steps"]),
                    job["counters"], tracer, source, phases, job["memory"],
                    counted)
    metric = mx.metric.create("ce")
    opt = traffic["optimizer"]
    mod.fit(source, eval_metric=metric, num_epoch=1, optimizer=opt["name"],
            optimizer_params=dict(opt["params"]), batch_end_callback=window)
    if window.state != 2:
        raise RuntimeError("the window never closed (state %d, %d steps)"
                           % (window.state, window.n))
    assert mod._fused_step is not None, "fused train step not active"
    # after the window: the reference costs no set-up time and shares no
    # memory with the step program's peak
    phases.mark("after_window")
    checks = check_against_reference(mod, cfg, traffic, placed[0], layers,
                                     seeded, limits)
    phases.mark("check")

    mean_loss = float(metric.get()[1])
    finite = bool(math.isfinite(mean_loss))
    steps, stamps = window.n, window.stamps[:window.n]
    rate = timing.window_rate(batch, steps, window.t0, window.t1)
    worst, at = timing.longest_step(stamps, window.t0)
    return {
        "end_to_end": {"train_samples_per_s": rate},
        "setup_s": phases.since_start(window.t0),
        "attempted": steps, "failed": 0 if finite else steps,
        "checks": checks + [{"ok": finite, "mean_loss_all_steps": mean_loss}],
        "trace": tracer.parsed, "trace_bytes": tracer.trace_bytes,
        "facts": {"rate": rate, "steps": steps, "step_stats": window.stats,
                  "window_s": window.t1 - window.t0, "batch": batch},
        "side": {
            "window_s": window.t1 - window.t0, "steps": steps,
            "segment_rates": timing.segment_rates(
                stamps, window.t0, [batch] * steps),
            "prefix_rates": timing.prefix_rates(
                stamps, window.t0, [batch] * steps, timing.PREFIX_MARKS_S),
            "longest_step_s": worst, "longest_step_index": at,
            "median_step_s": float(np.median(np.diff(
                [window.t0] + stamps))),
            "gc_collections_in_window": [b - a for a, b in
                                         zip(window.gc0, window.gc1)],
            "mean_loss_all_steps": mean_loss,
            "program_counters_before_window": window.counters_before,
        },
    }
