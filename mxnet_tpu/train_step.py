"""CompiledTrainStep — the whole training step as ONE donated XLA program.

TPU-native analog of the reference's bulk-exec segments
(`src/executor/graph_executor.cc:678-756`), taken to its conclusion: where
the reference fuses forward/backward node sequences into single engine ops
but leaves the optimizer as separate per-parameter kernels
(`python/mxnet/optimizer.py` dispatching `sgd_mom_update` etc.), here
forward + backward + optimizer + aux-state update compile into a single
``jax.jit`` with ``donate_argnums`` on parameters / optimizer slots / aux —
XLA reuses their buffers in place, so the steady-state step does no
allocation and no host round-trips.

Mixed precision: master weights and optimizer slots stay float32 on device;
when ``compute_dtype`` (e.g. bfloat16) is set, parameters and input data are
cast once at program entry, the graph (matmuls/convs on the MXU) runs in the
compute dtype, and gradients are cast back to float32 before the optimizer.
Ops with precision-critical internals (BatchNorm statistics, softmax)
compute in float32 regardless.

State lives here as jax arrays, not NDArrays — Module flushes it back into
the executor's NDArray buffers only at eval/checkpoint boundaries.
"""
from __future__ import annotations

import functools
import logging
import pickle

import numpy as np

from . import obs as _obs
from .base import MXNetError
from .obs.scopes import scope as _scope

__all__ = ["CompiledTrainStep", "CompiledEvalStep"]


def _weak_hlo_reader(step):
    """A lazy reader of ``step.compiled_hlo()`` (the optimized HLO for
    ``obs.programs.scope_map``) that does NOT pin the step object (and
    transitively its executor group + master weights) in the
    process-global readers: once the step is collected, it resolves to
    None."""
    import weakref

    ref = weakref.ref(step)

    def read():
        live = ref()
        return live.compiled_hlo() if live is not None else None

    return read


def _register_step_spec(step):
    """Register a step's :class:`~mxnet_tpu.programs.spec.ProgramSpec`
    with the process-wide program registry — name, donation map, lazy
    abstract args and the retrace counters, registered ONCE per step
    (the registry holds it weakly; the step owns it).  Works for both
    :class:`CompiledTrainStep` (params, slots, aux and metric state
    donated) and :class:`CompiledEvalStep` (donated accumulator state
    only)."""
    import weakref

    from .programs import registry as _registry
    from .programs.spec import ProgramSpec

    ref = weakref.ref(step)
    is_train = isinstance(step, CompiledTrainStep)

    def abstract():
        live = ref()
        if live is None:
            return None
        if is_train:
            return live._abstract_args(live._group)
        return live._last_args

    spec = ProgramSpec(
        step.telemetry_name, step._fn, owner=step,
        abstract_args=abstract,
        donate_argnums=(0, 1, 2, 3) if is_train else (2,),
        compute_dtype=lambda: (str(ref()._cdtype)
                               if ref() is not None and is_train
                               and ref()._cdtype is not None else None),
        mesh_shape=lambda: (dict(ref()._group._mesh.shape)
                            if ref() is not None and is_train
                            and ref()._group._mesh is not None else None),
        trace_count=lambda: (ref().trace_count
                             if ref() is not None else None),
        expected_traces=lambda: (ref().programs_built
                                 if ref() is not None and is_train else 1))
    return _registry.register(spec)


class CompiledEvalStep:
    """Forward-only executor program with device-side metric accumulation.

    The eval/score counterpart of the train loop's device metrics (ROADMAP
    PR-3 open item): one jitted program runs the inference forward AND
    folds the metric's ``device_update`` into donated ``(sum, count)``
    accumulator state, so ``score()`` performs no per-batch device→host
    transfer — the classic path pays 2 (label + pred materialization in
    ``metric.update``) per batch.  Reading the metric drains lazily via
    the ``DeviceMetricAccumulator`` hooks, exactly like the train side;
    :meth:`finish` uninstalls them (folding what's pending) when the eval
    pass ends.

    Raises ``MXNetError`` from the constructor when this metric/graph
    combination can't accumulate on device (host path is the fallback);
    the first ``run`` validates the trace with ``jax.eval_shape`` and
    raises likewise before anything is donated.
    """

    def __init__(self, exec_group, metric):
        from .metric import DeviceMetricAccumulator

        # retrace instrumentation (analysis.RetracePass): the python body
        # below runs only while jax traces it, so this counter is the
        # ground truth for "the eval program traced exactly once" (the
        # eval_shape validation probe shares the jit trace cache, so it
        # IS that one trace).  artifact() lowering sets _probing so probe
        # re-traces don't count as cache misses.
        self.trace_count = 0
        self._probing = False
        exe = exec_group.exec_
        self._group = exec_group
        self._exec = exe
        self._data_names = list(exec_group.data_names)
        self._label_names = [n for n in exec_group.label_names
                             if n in exe.arg_dict]
        if len(self._label_names) != len(exec_group.label_names):
            # the program only sees labels the graph consumes; extra
            # iterator labels would shift the host pairing (same rule as
            # CompiledTrainStep.attach_metric)
            raise MXNetError("graph does not consume every label input; "
                             "metric pairing would differ from the host "
                             "path")
        self._param_names = [n for n in exe._arg_names
                             if n not in self._data_names
                             and n not in self._label_names]
        try:
            self._acc = DeviceMetricAccumulator(metric)
        except ValueError as exc:
            raise MXNetError(str(exc))
        self._acc.install()
        self._validated = False

        import jax

        acc = self._acc
        label_names = self._label_names
        param_names = self._param_names

        def step(params, aux, mstate, data, rng):
            if not self._probing:
                self.trace_count += 1
            env = dict(zip(param_names, params))
            env.update(data)
            arg_vals = [env[n] for n in exe._arg_names]
            outs, _ = exe._fwd_impl(arg_vals, aux, rng, False)
            labels = [data[n] for n in label_names]
            with _scope("metric"):
                return acc.update(mstate, labels, list(outs))

        self._fn = jax.jit(step, donate_argnums=(2,))
        self._last_args = None   # aval snapshot for artifact probes
        self._snap_traces = -1   # trace_count the snapshot was taken at
        self._registered = False  # spec + HLO reader registered once

    def _place(self, arr, name):
        import jax

        from . import ndarray as _nd

        group = self._group
        dst = group.exec_.arg_dict.get(name)
        v = arr.data if isinstance(arr, _nd.NDArray) else np.asarray(arr)
        if dst is not None and v.dtype != dst.data.dtype:
            v = v.astype(dst.data.dtype)
        if group._mesh is not None:
            return jax.device_put(v, group._input_sharding(name))
        return jax.device_put(v, group.contexts[0].jax_device)

    # telemetry: the name this program's dispatch spans carry
    telemetry_name = "eval_step"

    def run(self, data_batch):
        """Accumulate one batch on device.  No host transfer happens here;
        the metric's accumulator state is donated through the program.
        Each dispatch leaves a ``cat="program"`` span on the timeline —
        host-side only, the program is untouched."""
        if not _obs.enabled():
            return self._run_impl(data_batch)
        if not self._registered:
            self._registered = True
            self._program_spec = _register_step_spec(self)
        with _obs.program_span(self.telemetry_name):
            return self._run_impl(data_batch)

    def _run_impl(self, data_batch):
        from . import random as _rnd

        exe = self._exec
        data = {}
        for name, arr in zip(self._group.data_names, data_batch.data):
            data[name] = self._place(arr, name)
        if data_batch.label:
            for name, arr in zip(self._group.label_names, data_batch.label):
                if name in self._label_names:
                    data[name] = self._place(arr, name)
        missing = [n for n in self._data_names + self._label_names
                   if n not in data]
        if missing:
            raise MXNetError("eval batch is missing inputs %s" % missing)
        params = [exe.arg_dict[n].data for n in self._param_names]
        aux = [exe.aux_dict[n].data for n in exe._aux_names]
        rng = _rnd.split_key()
        if not self._validated:
            import jax

            # trace-only probe: a metric mirror this graph rejects must
            # fail BEFORE the donated accumulator state is consumed.  It
            # COUNTS as the program's one trace — eval_shape on a jitted
            # fn populates the same trace cache the real call hits.
            jax.eval_shape(self._fn, params, aux, self._acc.state, data,
                           rng)
            self._validated = True
        if self._last_args is None or self._snap_traces != self.trace_count:
            # aval snapshot for artifact probes — (re)built only when no
            # snapshot exists or the program re-traced, not per batch
            import jax
            import jax.tree_util as jtu

            from .analysis.artifact import aval_of

            def _bare(x):
                # accumulator scalars stay sharding-free: they are
                # re-seeded uncommitted after drains and relocate with
                # the program
                return jax.ShapeDtypeStruct(x.shape, x.dtype)

            self._last_args = (
                jtu.tree_map(aval_of, params), jtu.tree_map(aval_of, aux),
                jtu.tree_map(_bare, self._acc.state),
                jtu.tree_map(aval_of, data), aval_of(rng))
            self._snap_traces = self.trace_count
        self._acc.commit(self._fn(params, aux, self._acc.state, data, rng))

    def finish(self):
        """Fold pending device sums into the host metric and detach the
        hooks — call when the eval pass ends (or falls back mid-way)."""
        self._acc.uninstall()

    def rearm(self):
        """Re-install the metric hooks for another eval pass over the same
        compiled program (fit's per-epoch validation reuses one step
        instead of recompiling every epoch)."""
        self._acc.install()
        return self

    def artifact(self, name="eval_step"):
        """:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` of the
        eval program at the last-run shapes (None before the first
        ``run``).  Same probe economics as ``compiled_hlo``: avals only,
        throwaway compile, trace flagged as non-counting."""
        import jax.tree_util as jtu

        from .programs.spec import probe_artifact

        if self._last_args is None:
            return None
        params, aux, mstate, data, rng = self._last_args
        return probe_artifact(
            self, self._fn, (params, aux, mstate, data, rng), name,
            donated_leaves=len(jtu.tree_leaves(mstate)),
            trace_count=self.trace_count, expected_traces=1,
            metric=type(self._acc.metric).__name__)


class CompiledTrainStep:
    """One master-weight store + per-executor-group compiled step programs.

    Bucketed training shares a single instance across all bucket modules:
    each bucket's shape-specialized executor gets its own jitted program
    (``_entry_for``), but every program reads and donates the same
    params/slots/aux dicts — the analog of the reference's shared memory
    pools across bucket executors (bucketing_module.py:18-120) extended to
    the fused update path.
    """

    def __init__(self, exec_group, optimizer, compute_dtype=None):
        import jax.numpy as jnp

        kernel = optimizer.fused_kernel()
        if kernel is None:
            raise MXNetError("optimizer %s has no fused kernel"
                             % type(optimizer).__name__)
        self._make_slots, self._opt_apply = kernel
        self._optimizer = optimizer
        self._group = exec_group
        self._exec = exec_group.exec_

        exe = self._exec
        self._data_names = list(exec_group.data_names)
        self._label_names = [n for n in exec_group.label_names
                             if n in exe.arg_dict]
        self._param_names = [n for n in exe._arg_names
                             if n not in self._data_names
                             and n not in self._label_names]
        # only params with a gradient request get optimizer updates; fixed
        # params ride along as forward inputs
        self._grad_names = [n for n in self._param_names
                            if exe.grad_req.get(n, "null") == "write"]
        unsupported = [n for n in self._param_names
                       if exe.grad_req.get(n, "null") not in ("null", "write")]
        if unsupported:
            raise MXNetError("fused train step supports grad_req "
                             "null/write only; got add for %s" % unsupported)
        self._aux_names = list(exe._aux_names)
        # optimizer bookkeeping (update counts, lr_mult) is keyed by the
        # param's index in the executor group, matching the eager path
        self._grad_indices = [exec_group.param_names.index(n)
                              for n in self._grad_names]

        if compute_dtype in (None, "", "float32", np.float32):
            self._cdtype = None
        else:
            self._cdtype = jnp.dtype(compute_dtype)

        # own copies: the first donated step invalidates its input buffers,
        # and the executor's NDArrays must keep theirs
        self.params = {n: jnp.copy(exe.arg_dict[n].data)
                       for n in self._param_names}
        self.aux = {n: jnp.copy(exe.aux_dict[n].data) for n in self._aux_names}
        self.reset_slots()
        # compiled programs keyed by executor identity (the value holds a
        # strong ref to the executor so a GC'd id can't alias a new one);
        # a reshape rebuilds group.exec_, so the stale program is skipped
        # device-side metric accumulation: when a DeviceMetricAccumulator is
        # attached, its state rides the program as EXTRA DONATED STATE and
        # the per-step device->host output read disappears (metric.py).
        # _metric_traced_ids tracks which executors' programs have traced
        # the metric successfully — per executor, because a shared store
        # compiles one program per bucket and a later bucket's graph may
        # still reject the metric's device mirror
        self._metric_acc = None
        self._metric_traced_ids = set()
        self._metric_rejected = None  # metric whose device mirror failed
        # retrace instrumentation (analysis.RetracePass): the step body
        # increments trace_count only while jax traces it; every program
        # (re)build bumps programs_built, so trace_count > programs_built
        # means a jit cache miss at an already-built signature — dtype /
        # weak-type drift.  compiled_hlo/artifact lowerings set _probing
        # and don't count (the metric eval_shape probe does: it shares
        # the trace cache the real call hits).
        self.trace_count = 0
        self.programs_built = 0
        self._probing = False
        self._fns = {}
        self._fn = self._build(exec_group)
        self._fns[id(exec_group.exec_)] = (self._fn, exec_group.exec_)
        self.num_steps = 0
        self._hyper_cache = None
        self._registered = False  # spec + HLO reader registered once
        # lifecycle state is a property of the shared store, not of any one
        # module (several bucket modules may view this step)
        self.step_stale = False   # executor buffers newer than the store
        self.exec_stale = False   # store newer than executor buffers
        self.opt_owner = "eager"  # who holds live optimizer slots

    def compatible(self, group):
        """Whether a (bucket) executor group can train through this store.

        Requires every master param/aux to be the *same shared buffer* as
        the primary executor's (shared binding shares identity when shapes
        match), and no extra trainable params.  Buckets with shape-varying
        params (the reference lets those be per-bucket copies) must use the
        eager path instead."""
        exe = group.exec_
        prim = self._exec
        for n in self._param_names:
            if exe.arg_dict.get(n) is not prim.arg_dict[n]:
                return False
        for n in self._aux_names:
            if exe.aux_dict.get(n) is not prim.aux_dict[n]:
                return False
        data_like = set(group.data_names) | set(group.label_names)
        for n in exe._arg_names:
            if n not in data_like and n not in self._param_names:
                return False
        return True

    def _entry_for(self, group):
        """The compiled step program for a (bucket) executor group, built on
        first use.  The group must expose the same parameter set — shared
        binding guarantees it for BucketingModule."""
        exe = group.exec_
        hit = self._fns.get(id(exe))
        if hit is not None and hit[1] is exe:
            return hit[0]
        if not self.compatible(group):
            raise MXNetError(
                "bucket executor's parameter set is not shared with the "
                "master store; demote this bucket to the eager path")
        fn = self._build(group)
        self._fns[id(exe)] = (fn, exe)
        return fn

    # ------------------------------------------------------------------
    # device-side metrics
    # ------------------------------------------------------------------
    def attach_metric(self, metric):
        """Fold ``metric``'s accumulation into the step program as donated
        state.  Returns True when armed; False when this metric (or this
        graph's label routing) can't accumulate on device — the caller then
        stays on the host ``update_metric`` path.  Idempotent per metric."""
        from .metric import DeviceMetricAccumulator

        if self._metric_acc is not None and self._metric_acc.metric is metric:
            return True
        if metric is self._metric_rejected:
            return False  # its device mirror already failed to trace once
        if not DeviceMetricAccumulator.supported(metric):
            return False
        # the step only sees labels the graph consumes; if the iterator
        # feeds extra labels the host pairing would differ — stay on host
        if len(self._label_names) != len(self._group.label_names):
            return False
        self.detach_metric()
        self._metric_acc = DeviceMetricAccumulator(metric)
        self._metric_acc.install()
        self._metric_traced_ids = set()
        self._fns = {}  # program signature changed: recompile per executor
        return True

    def detach_metric(self):
        """Drain pending device accumulation and drop the metric from the
        program (fused->eager handoff, monitor installation, re-init)."""
        if self._metric_acc is None:
            return
        self._metric_acc.uninstall()
        self._metric_acc = None
        self._metric_traced_ids = set()
        self._fns = {}

    # ------------------------------------------------------------------
    def _build(self, group):
        import jax
        import jax.numpy as jnp

        exe = group.exec_
        cdtype = self._cdtype
        data_names = self._data_names
        grad_names = self._grad_names
        aux_names = self._aux_names
        opt_apply = self._opt_apply
        label_names = self._label_names
        macc = self._metric_acc

        def cast(v):
            if cdtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(cdtype)
            if v.dtype == jnp.uint8:
                # uint8 data = image bytes shipped compact (4x less h2d;
                # ImageIter dtype="uint8"): cast on DEVICE to the compute
                # dtype.  Integer label/id inputs keep their dtype — they
                # arrive as s32/f32, never u8.
                return v.astype(cdtype if cdtype is not None
                                else jnp.float32)
            return v

        def step(params, slots, aux, mstate, data, lrs, wds, rescale, clip,
                 extra, rng):
            if not self._probing:
                self.trace_count += 1
            castp = {n: cast(v) for n, v in params.items()}
            # labels keep their dtype (integer class ids beyond bf16's exact
            # range must survive); only data inputs are cast
            datac = {n: (cast(v) if n in data_names else v)
                     for n, v in data.items()}

            def fwd(gvals):
                env = dict(castp)
                env.update(zip(grad_names, gvals))
                env.update(datac)
                outs, new_aux = exe._run_graph(env, aux, rng, True)
                return outs, [new_aux[n] for n in aux_names]

            gvals = [castp[n] for n in grad_names]
            outs, vjp_fn, new_aux_vals = jax.vjp(fwd, gvals, has_aux=True)
            cts = [jnp.ones_like(o) for o in outs]
            (grads,) = vjp_fn(cts)

            new_params = dict(params)
            new_slots = {}
            with _scope("optimizer"):
                for i, n in enumerate(grad_names):
                    g = grads[i].astype(params[n].dtype)
                    w, s = opt_apply(params[n], g, slots[n],
                                     lrs[i], wds[i], rescale, clip, extra)
                    # float32 hyper scalars promote fp16/bf16 masters; cast
                    # the update back so param dtypes are stable across
                    # steps
                    new_params[n] = w.astype(params[n].dtype)
                    new_slots[n] = tuple(
                        s_new.astype(s_old.dtype)
                        for s_new, s_old in zip(s, slots[n]))
            new_aux = {n: v.astype(aux[n].dtype)
                       for n, v in zip(aux_names, new_aux_vals)}
            if macc is not None:
                # metric accumulation reads the SAME outputs/labels the host
                # path would; it feeds nothing back into the training math
                labels = [data[n] for n in label_names]
                with _scope("metric"):
                    mstate = macc.update(mstate, labels, list(outs))
            return new_params, new_slots, new_aux, outs, mstate

        self.programs_built += 1
        return jax.jit(step, donate_argnums=(0, 1, 2, 3))

    # telemetry: the name this program's dispatch spans carry (one
    # shared store = one name, however many bucket executors)
    telemetry_name = "train_step"

    # ------------------------------------------------------------------
    def run(self, data_batch, group=None):
        """Execute one full training step; returns output jnp arrays.

        ``group`` selects the (bucket) executor whose graph to run; the
        master weights/slots are this store's regardless.  Each dispatch
        leaves a ``cat="program"`` span on the timeline — host-side
        timing only, the compiled program is byte-identical with
        telemetry on or off (tests/test_obs.py pins it).
        """
        if not _obs.enabled():
            return self._run_impl(data_batch, group)
        if not self._registered:
            self._registered = True
            _obs.programs.register_hlo(
                self.telemetry_name, _weak_hlo_reader(self), owner=self)
            self._program_spec = _register_step_spec(self)
        with _obs.program_span(self.telemetry_name):
            return self._run_impl(data_batch, group)

    def _run_impl(self, data_batch, group=None):
        from . import random as _rnd

        group = group if group is not None else self._group
        fn = self._entry_for(group)
        label_names = [n for n in group.label_names
                       if n in group.exec_.arg_dict]
        data = {}
        for name, arr in zip(group.data_names, data_batch.data):
            data[name] = self._place(arr, name, group)
        if label_names and data_batch.label:
            # zip the *unfiltered* group label list so an unconsumed early
            # label cannot shift later labels onto the wrong arrays; names
            # the symbol doesn't take are skipped in-loop (same alignment
            # rule as DataParallelExecutorGroup.forward)
            for name, arr in zip(group.label_names, data_batch.label):
                if name in label_names:
                    data[name] = self._place(arr, name, group)

        lrs, wds, rescale, clip = self._optimizer.fused_hyper(self._grad_indices)
        extra = self._optimizer.fused_extra()
        # keep hyper-params resident on device across steps: with a constant
        # schedule this is one transfer total instead of one per step
        cached = self._hyper_cache
        if cached is not None and np.array_equal(cached[0], lrs) \
                and np.array_equal(cached[1], wds) \
                and cached[2] == rescale and cached[3] == clip \
                and np.array_equal(cached[4], extra):
            hyper_dev = cached[5]
        else:
            import jax

            where = group._rep_sharding if group._mesh is not None \
                else group.contexts[0].jax_device
            hyper_dev = jax.tree_util.tree_map(
                lambda v: jax.device_put(v, where),
                (lrs, wds, rescale, clip, extra))
            self._hyper_cache = (lrs, wds, rescale, clip, extra, hyper_dev)
        rng = _rnd.split_key()
        acc = self._metric_acc
        mstate = acc.state if acc is not None else ()

        def dispatch(fn, donated_mstate):
            h0, h1, h2, h3, h4 = hyper_dev
            return fn(self.params, self.slots, self.aux, donated_mstate,
                      data, h0, h1, h2, h3, h4, rng)

        if acc is not None and id(group.exec_) not in self._metric_traced_ids:
            # validate the metric's device mirror by TRACING ONLY
            # (eval_shape executes nothing, so no donated buffer is at
            # stake); a mirror that can't trace against this graph — shape
            # pairing, unsupported op, ... — demotes the metric to the
            # host path instead of failing the step.  Real execution
            # errors below propagate untouched.
            import jax

            # (the probe trace is the program's one trace — eval_shape on
            # a jitted fn populates the cache the real call below hits)
            try:
                dispatch(functools.partial(jax.eval_shape, fn), mstate)
                self._metric_traced_ids.add(id(group.exec_))
            except Exception as exc:
                logging.getLogger(__name__).info(
                    "device metric accumulation unavailable (%s); metric "
                    "stays on the host path", exc)
                self._metric_rejected = acc.metric  # don't re-attach
                self.detach_metric()
                acc, mstate = None, ()
                fn = self._entry_for(group)
        self.params, self.slots, self.aux, outs, mstate = \
            dispatch(fn, mstate)
        if acc is not None:
            acc.commit(mstate)
        self.num_steps += 1
        return outs

    def _abstract_args(self, group):
        """Aval pytree of the step program's arguments, rebuilt from the
        live master store and the executor's bound input buffers (None
        before the first ``run``).  Shared by the ``compiled_hlo`` and
        ``artifact`` probes so nothing extra is retained on the hot path.
        """
        import jax

        from . import random as _rnd
        from .analysis.artifact import aval_of as _aval

        if self._hyper_cache is None:
            return None  # never run: no hyper avals to rebuild

        params = {n: _aval(v) for n, v in self.params.items()}
        slots = {n: tuple(_aval(s) for s in v)
                 for n, v in self.slots.items()}
        aux = {n: _aval(v) for n, v in self.aux.items()}
        exe = group.exec_
        label_names = [n for n in group.label_names if n in exe.arg_dict]
        data = {}
        for name in list(group.data_names) + label_names:
            v = exe.arg_dict[name].data
            if group._mesh is not None:
                sharding = group._input_sharding(name)
            else:
                sharding = v.sharding
            data[name] = jax.ShapeDtypeStruct(v.shape, v.dtype,
                                              sharding=sharding)
        import jax.tree_util as jtu

        hyper = jtu.tree_map(_aval, self._hyper_cache[5])

        # metric accumulator avals carry NO sharding: after a drain the
        # accumulator is re-seeded as uncommitted default-device scalars,
        # which the real call relocates freely — snapshotting that
        # placement into a committed aval would clash with mesh-sharded
        # params at lower() time
        mstate = () if self._metric_acc is None or \
            self._metric_acc.state is None \
            else jtu.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype),
                              self._metric_acc.state)
        # peek the key chain for its aval — a probe must not advance the
        # global RNG (split_key() here would shift every later step's
        # randomness and break bit-reproducibility around the probe)
        rng = _aval(_rnd._key())
        return (params, slots, aux, mstate, data) + tuple(hyper) + (rng,)

    def compiled_hlo(self, group=None):
        """Optimized-HLO text of the fused train-step program (None before
        the first ``run``).

        Same probe surface as ``Executor.compiled_hlo`` — feed it to
        ``parallel.hlo_stats.collective_stats`` — but over the program
        that actually trains: forward + backward + optimizer in the one
        donated jit.  The lowering compiles a throwaway copy of the
        program (cached jit executables are keyed by concrete arrays, not
        avals), so this is a probe, not a free read.
        """
        from .programs.spec import probing

        group = group if group is not None else self._group
        args = self._abstract_args(group)
        if args is None:
            return None
        fn = self._entry_for(group)
        with probing(self):
            return fn.lower(*args).compile().as_text()

    def artifact(self, name="train_step", group=None):
        """:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` of the
        fused step — jaxpr + lowered StableHLO + compiled HLO + the
        donation/retrace/dtype metadata the analysis passes check (None
        before the first ``run``)."""
        import jax.tree_util as jtu

        from .programs.spec import probe_artifact

        group = group if group is not None else self._group
        args = self._abstract_args(group)
        if args is None:
            return None
        fn = self._entry_for(group)
        # donated = the leading donate_argnums block
        # (params, slots, aux, mstate)
        mesh_shape = dict(group._mesh.shape) if group._mesh is not None \
            else None
        # sharding-coverage lint surface: the per-param placement records
        # executor_group._param_sharding stamped at bind time (empty when
        # no tensor-parallel/mesh-axes placement ran — pass then skips)
        coverage = None
        leaves = getattr(group, "_sharding_coverage", None)
        if mesh_shape is not None and leaves:
            coverage = {"mesh": {str(k): int(v)
                                 for k, v in mesh_shape.items()},
                        "leaves": leaves}
        return probe_artifact(
            self, fn, args, name,
            donated_leaves=len(jtu.tree_leaves(args[:4])),
            compute_dtype=str(self._cdtype) if self._cdtype is not None
            else None,
            mesh_shape=mesh_shape, trace_count=self.trace_count,
            expected_traces=self.programs_built,
            num_steps=self.num_steps,
            sharding_coverage=coverage)

    def _place(self, arr, name, group=None):
        import jax

        group = group if group is not None else self._group
        dst = group.exec_.arg_dict.get(name)
        v = arr.data
        if dst is not None and v.dtype != dst.data.dtype:
            v = v.astype(dst.data.dtype)
        if group._mesh is not None:
            # per-input rule: honors seq-axis (time) sharding from layouts
            return jax.device_put(v, group._input_sharding(name))
        return jax.device_put(v, group.contexts[0].jax_device)

    # ------------------------------------------------------------------
    # state exchange with the NDArray world
    # ------------------------------------------------------------------
    def flush_to_executor(self):
        """Write master params/aux back into the executor's NDArray buffers
        (copies — the step will donate its own buffers next run)."""
        import jax.numpy as jnp

        exe = self._exec
        for n in self._param_names:
            exe.arg_dict[n]._set_data(
                jnp.copy(self.params[n]).astype(exe.arg_dict[n].data.dtype))
        for n in self._aux_names:
            exe.aux_dict[n]._set_data(
                jnp.copy(self.aux[n]).astype(exe.aux_dict[n].data.dtype))

    def load_from_executor(self):
        """Re-seed step state from the executor (after set_params etc.)."""
        import jax.numpy as jnp

        exe = self._exec
        for n in self._param_names:
            self.params[n] = jnp.copy(exe.arg_dict[n].data)
        for n in self._aux_names:
            self.aux[n] = jnp.copy(exe.aux_dict[n].data)

    def get_states(self):
        """Serialized optimizer slots (save_optimizer_states payload)."""
        host = {n: tuple(np.asarray(s) for s in slots)
                for n, slots in self.slots.items()}
        return pickle.dumps(host)

    def set_states(self, payload):
        """Load optimizer slots.  Accepts both the fused format (keyed by
        param name, numpy tuples) and the eager Updater format (keyed by the
        param's index in the executor group, NDArray-valued)."""
        import jax.numpy as jnp

        host = pickle.loads(payload)
        index_names = {i: n for i, n in enumerate(self._group.param_names)}
        for key, state in host.items():
            name = index_names.get(key, key) if isinstance(key, int) else key
            if name not in self.slots:
                continue
            self.slots[name] = self._state_to_slots(state, jnp)

    @staticmethod
    def _state_to_slots(state, jnp):
        """Eager create_state values -> fused slot tuple: None -> (),
        single array -> 1-tuple, tuple -> tuple (NDArrays unwrapped)."""
        def leaf(v):
            return jnp.asarray(v.data if hasattr(v, "data") else v)

        if state is None:
            return ()
        if isinstance(state, (tuple, list)):
            return tuple(leaf(s) for s in state)
        return (leaf(state),)

    def reset_slots(self):
        """Synthesize fresh (zero-moment) optimizer slots for the CURRENT
        params — a slot-less checkpoint restored into a training module
        must not keep the moments of the weights it replaced."""
        self.slots = {n: self._make_slots(self.params[n])
                      for n in self._grad_names}

    def import_updater_states(self, states, param_names):
        """Seed slots from an eager Updater's state dict (index- or
        name-keyed) when the module switches eager -> fused mid-training."""
        import jax.numpy as jnp

        index_names = {i: n for i, n in enumerate(param_names)}
        for key, state in states.items():
            name = index_names.get(key, key) if isinstance(key, int) else key
            if name in self.slots:
                self.slots[name] = self._state_to_slots(state, jnp)

    def export_updater_states(self, updater, param_names, ctx):
        """Hand the fused slots to an eager Updater (fused -> eager switch:
        install_monitor, manual update() loop) so momentum carries over."""
        import jax.numpy as jnp

        from . import ndarray as _nd

        for idx, name in enumerate(param_names):
            if name not in self.slots:
                continue
            arrays = [_nd.NDArray(jnp.copy(s), ctx)
                      for s in self.slots[name]]
            updater.states[idx] = self._optimizer.pack_state(arrays)
