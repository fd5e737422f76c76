"""Module — the standard symbol-backed module.

Reference: `python/mxnet/module/module.py` (708 LoC; bind:323,
init_optimizer:432 incl. kvstore wiring + rescale_grad conventions,
update:553).  Gradients from the mesh-sharded executor group are already
globally reduced (XLA psum), so `update` is: optimizer step through the
kvstore facade (update_on_kvstore) or the local updater.
"""
from __future__ import annotations

import logging

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import obs as _obs
from .. import optimizer as opt_mod
from ..base import MXNetError
from ..model import save_checkpoint, load_checkpoint, _create_kvstore
from .base_module import BaseModule
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, compute_dtype=None,
                 mesh_config=None):
        super().__init__(logger=logger)
        # multi-axis parallelism over the bound contexts (parallel.MeshConfig:
        # data/model/pipe/seq/expert); None = pure data parallel
        self._mesh_config = mesh_config
        if compute_dtype is None:
            from .. import config as _config

            compute_dtype = _config.get("MXNET_COMPUTE_DTYPE") or None
        self._compute_dtype = compute_dtype
        # fused-train-step state (see ..train_step.CompiledTrainStep)
        self._fused_step = None
        self._fused_outputs = None
        self._fused_update_done = False   # update() becomes a no-op for it
        self._pending_metric = None       # metric to fold into the step
        self._step_stale = False          # executor arrays newer than step
        self._exec_stale = False          # step newer than executor arrays
        self._opt_owner = "eager"         # who holds live optimizer slots
        self._monitor = None
        # NOTE: _step_stale/_exec_stale are properties delegating to the
        # (possibly shared) fused step when one exists — several bucket
        # modules can view one master-weight store, so staleness must live
        # with the store, not the module
        if context is None:
            context = ctx_mod.cpu()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = list(state_names or [])
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Load from checkpoint (reference: module.py:86)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)

    # ------------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, o.shape) for n, o in
                zip(self._output_names, self._exec_group.get_outputs())] \
            if self._exec_group.exec_._outputs is not None else []

    # ------------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    @_obs.phased("build.init_params")
    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"

        param_names = self._param_names
        aux_names = self._aux_names
        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(self._exec_group.exec_.arg_dict[name].shape,
                               dtype=self._exec_group.exec_.arg_dict[name].dtype)
                for name in param_names}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(self._exec_group.exec_.aux_dict[name].shape,
                               dtype=self._exec_group.exec_.aux_dict[name].dtype)
                for name in aux_names}

        from ..initializer import InitDesc

        # Variable attrs make per-param init overrides visible to the
        # initializer (reference: initializer.py:85-107 InitDesc dispatch)
        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            desc = InitDesc(name, attrs.get(name), initializer)
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        if cache_arr.shape != arr.shape:
                            raise MXNetError(
                                "Parameter %s cannot be initialized from loading. "
                                "Shape mismatch: %s vs %s"
                                % (name, cache_arr.shape, arr.shape))
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(desc, arr)
            else:
                if initializer is not None:
                    initializer(desc, arr)

        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)
        self._step_stale = self._fused_step is not None

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init)
            return
        if self.params_initialized and not force_init:
            return
        self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self._step_stale = self._fused_step is not None
        self.params_initialized = True

    # ------------------------------------------------------------------
    @_obs.phased("build.bind")
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [s if hasattr(s, "name") else s for s in data_shapes]
        self._label_shapes = list(label_shapes) if label_shapes else None

        shared_group = None
        if shared_module is not None:
            assert shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names, mesh_config=self._mesh_config)
        self._total_exec_bytes = 0

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        # the fused step holds the live master weights; pull them back into
        # the host param dicts before the executor they came from is dropped
        if self._fused_step is not None and self.params_initialized:
            self._sync_params_from_devices()
        if self._fused_step is not None:
            self._fused_step.detach_metric()
        self._fused_step = None
        self._pending_metric = None
        self._fused_outputs = None
        self._fused_update_done = False
        self._step_stale = False
        self._exec_stale = False
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = list(data_shapes)
        self._label_shapes = list(label_shapes) if label_shapes else None
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    def reconfigure(self, contexts, mesh_config=None):
        """Re-form the module over a new device set mid-training — the
        elastic shrink/regrow step (mxnet_tpu.elastic).

        Rebinds at the SAME data/label shapes on the new contexts/mesh
        (the global batch is unchanged; each surviving device simply owns
        a larger slice of the 'data' axis) and re-initializes the
        optimizer so a fresh fused step compiles against the new executor
        group.  The caller then restores params/slots from the last fence
        checkpoint, re-sharded onto the new mesh — nothing may be in
        flight when this runs (the elastic controller drains first)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        if isinstance(contexts, ctx_mod.Context):
            contexts = [contexts]
        data_shapes, label_shapes = self._data_shapes, self._label_shapes
        optimizer = self._optimizer
        self._context = list(contexts)
        self._mesh_config = mesh_config
        self.bind(data_shapes=data_shapes, label_shapes=label_shapes,
                  for_training=True, force_rebind=True)
        # bind() pushed the host param dicts into the new group; the fused
        # step (fresh zero-moment slots) rebuilds here and the fence
        # restore that follows overwrites both
        self.init_optimizer(kvstore="local", optimizer=optimizer,
                            force_init=True)

    # ------------------------------------------------------------------
    @_obs.phased("build.init_optimizer")
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)

        batch_size = self._exec_group.batch_size
        if kvstore and kvstore.type.startswith("dist") and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._exec_group.param_names)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt_mod.create(optimizer, sym=self.symbol,
                                       param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt_mod.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad "
                    "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). "
                    "Is this intended?", optimizer.rescale_grad, rescale_grad)

        self._optimizer = optimizer
        self._kvstore = kvstore
        # when the fused step will own the update, the optimizer must NOT
        # also live in the kvstore — keep a local updater as the eager
        # fallback so state handoffs have somewhere to go
        if update_on_kvstore and self._fused_eligible(optimizer, kvstore):
            update_on_kvstore = False
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            # copy initialized params into the store
            for idx, name in enumerate(self._exec_group.param_names):
                kvstore.init(idx, self._arg_params[name])
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = opt_mod.get_updater(optimizer)

        self.optimizer_initialized = True
        self._maybe_build_fused_step()
        self._opt_owner = "fused" if self._fused_step is not None else "eager"

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    @property
    def _step_stale(self):
        if self._fused_step is not None:
            return self._fused_step.step_stale
        return self.__dict__.get("_step_stale_local", False)

    @_step_stale.setter
    def _step_stale(self, value):
        if getattr(self, "_fused_step", None) is not None:
            self._fused_step.step_stale = value
        self.__dict__["_step_stale_local"] = value

    @property
    def _exec_stale(self):
        if self._fused_step is not None:
            return self._fused_step.exec_stale
        return self.__dict__.get("_exec_stale_local", False)

    @_exec_stale.setter
    def _exec_stale(self, value):
        if getattr(self, "_fused_step", None) is not None:
            self._fused_step.exec_stale = value
        self.__dict__["_exec_stale_local"] = value

    @property
    def _opt_owner(self):
        # like the staleness flags, slot ownership belongs to the (possibly
        # shared) store: a fused->eager handoff by one bucket module must be
        # visible to every other module viewing the same master weights
        if self._fused_step is not None:
            return self._fused_step.opt_owner
        return self.__dict__.get("_opt_owner_local", "eager")

    @_opt_owner.setter
    def _opt_owner(self, value):
        if getattr(self, "_fused_step", None) is not None:
            self._fused_step.opt_owner = value
        self.__dict__["_opt_owner_local"] = value

    def _fused_eligible(self, optimizer, kvstore):
        """Whether the fused (donated, jitted) train step can own the
        update: single-process kvstore, no monitor taps, optimizer with a
        fused kernel, no data grads requested."""
        from .. import config as _config

        if not _config.get("MXNET_FUSED_TRAIN_STEP"):
            return False
        if _config.get("MXNET_ENGINE_TYPE") == "NaiveEngine":
            return False  # debugging mode: eager per-op execution
        if not self.for_training or self.inputs_need_grad:
            return False
        if self._monitor is not None:
            return False  # per-op taps need the eager executor path
        if kvstore is not None and kvstore.type.startswith("dist"):
            return False  # cross-process reduction rides the kvstore path
        if optimizer.fused_kernel() is None:
            self.logger.info(
                "optimizer %s has no fused kernel; using eager update path",
                type(optimizer).__name__)
            return False
        return True

    def _maybe_build_fused_step(self):
        """Compile forward+backward+optimizer into one donated XLA program
        when the configuration allows it."""
        self._flush_fused()  # re-init must not revert trained weights
        if self._fused_step is not None:
            self._fused_step.detach_metric()
        self._fused_step = None
        if not self._fused_eligible(self._optimizer, self._kvstore):
            return
        from ..train_step import CompiledTrainStep

        try:
            self._fused_step = CompiledTrainStep(
                self._exec_group, self._optimizer,
                compute_dtype=self._compute_dtype)
        except MXNetError as exc:
            self.logger.info("fused train step unavailable (%s); using "
                             "eager update path", exc)

    def borrow_optimizer(self, shared_module):
        """Share optimizer state with another module (bucketing).

        When the shared module owns a fused step, this module adopts the
        SAME master-weight store — its own executor graph gets a
        shape-specialized program inside that store on first run, so every
        bucket trains through the fused path against one set of weights.
        """
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._fused_step = shared_module._fused_step
        if self._fused_step is None:
            self._opt_owner = "eager"
        # (with a shared step, _opt_owner reads the store's flag directly)
        self.optimizer_initialized = True

    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        """One training forward+backward.  With a fused step compiled, this
        runs the entire donated program (including the optimizer update —
        the following ``update()`` call is then a no-op)."""
        if self._fused_step is not None:
            self._run_fused(data_batch)
        else:
            self.forward(data_batch, is_train=True)
            self.backward()

    def _run_fused(self, data_batch):
        from .. import ndarray as _nd

        if self._pending_metric is not None:
            # arm device-side metric accumulation once; a metric the step
            # can't host stays on the classic update_metric path
            self._fused_step.attach_metric(self._pending_metric)
            self._pending_metric = None
        if self._step_stale:
            self._fused_step.load_from_executor()
            self._step_stale = False
        if self._opt_owner == "eager":
            # momentum/Adam moments accumulated on the eager path carry over
            if self._updater is not None and self._updater.states:
                self._fused_step.import_updater_states(
                    self._updater.states, self._exec_group.param_names)
            self._opt_owner = "fused"
        outs = self._fused_step.run(data_batch, group=self._exec_group)
        ctx = self._context[0]
        self._fused_outputs = [_nd.NDArray(o, ctx) for o in outs]
        self._fused_update_done = True
        self._exec_stale = True
        self._params_dirty = True

    def _flush_fused(self):
        """Bring the executor's NDArray buffers up to date with the fused
        step's master state (eval / checkpoint / classic-path boundary)."""
        if self._fused_step is not None and self._exec_stale:
            self._fused_step.flush_to_executor()
            self._exec_stale = False

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._flush_fused()
        self._fused_outputs = None
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Optimizer step (reference: module.py:553).  No-op when the
        preceding forward_backward already ran the fused program."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        if self._fused_update_done:
            self._fused_update_done = False
            return
        self._params_dirty = True
        if self._fused_step is not None:
            self._handoff_fused_to_eager()
            self._step_stale = True
        group = self._exec_group
        if self._update_on_kvstore:
            for idx, (name, w, g) in enumerate(zip(group.param_names,
                                                   group.param_arrays,
                                                   group.grad_arrays)):
                if g is None:
                    continue
                self._kvstore.push(idx, g)
                self._kvstore.pull(idx, out=w)
        else:
            if self._kvstore:
                for idx, (w, g) in enumerate(zip(group.param_arrays,
                                                 group.grad_arrays)):
                    if g is None:
                        continue
                    self._kvstore.push(idx, g)
                    self._kvstore.pull(idx, out=g)
            # one fused whole-model update call (TPU: dispatch latency would
            # dominate a per-parameter loop)
            idxs, ws, gs = [], [], []
            for idx, (w, g) in enumerate(zip(group.param_arrays, group.grad_arrays)):
                if g is None:
                    continue
                idxs.append(idx)
                ws.append(w)
                gs.append(g)
            self._updater.update_multi(idxs, gs, ws)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused_outputs is not None:
            return list(self._fused_outputs)
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._fused_outputs is not None:
            step = self._fused_step
            acc = step._metric_acc if step is not None else None
            if acc is not None and acc.metric is eval_metric:
                # already accumulated INSIDE the step program — no host
                # read; the accumulator applies the periodic-drain policy
                acc.maybe_drain(step.num_steps)
                return
            from .. import metric as metric_mod

            eval_metric.update(labels, metric_mod.select_outputs(
                eval_metric, self._fused_outputs))
        else:
            self._exec_group.update_metric(eval_metric, labels)

    def _bind_metric(self, eval_metric):
        from .. import config as _config

        self._pending_metric = None
        if self._fused_step is None:
            return
        if not _config.get("MXNET_DEVICE_METRICS"):
            # knob turned off between fits: a previously armed accumulator
            # must actually come off the program, not linger
            self._fused_step.detach_metric()
            return
        acc = self._fused_step._metric_acc
        if acc is not None and acc.metric is not eval_metric:
            # don't keep accumulating into the previous fit's metric
            self._fused_step.detach_metric()
        self._pending_metric = eval_metric

    def _bind_eval_metric(self, eval_metric):
        """Arm device-side metric accumulation for score(): the eval pass
        runs one jitted forward+accumulate program per batch and never
        materializes outputs on the host (ROADMAP PR-3 open item)."""
        from .. import config as _config

        if not _config.get("MXNET_DEVICE_METRICS"):
            return None
        if _config.get("MXNET_ENGINE_TYPE") == "NaiveEngine":
            return None
        if self._monitor is not None:
            return None  # per-op taps need the eager executor path
        if not self.binded or not self.params_initialized:
            return None
        from ..metric import DeviceMetricAccumulator

        if not DeviceMetricAccumulator.supported(eval_metric):
            return None
        # fit() defaults validation_metric to the TRAIN metric instance,
        # whose drain/reset hooks the fused step's accumulator owns;
        # installing eval hooks over them (and uninstalling at pass end)
        # would orphan the train-side device sums — such shared metrics
        # score through the host path, as before
        if any(getattr(m, "_device_sync", None) is not None
               for m in DeviceMetricAccumulator._flatten(eval_metric)):
            return None
        # the program reads the executor's parameter buffers — bring them
        # up to date with the fused step's master state first (forward()
        # would have done the same)
        self._flush_fused()
        # one compiled eval step per (executor, metric) pair: repeated
        # score() calls — fit's per-epoch validation — reuse it
        cached = getattr(self, "_eval_step_cache", None)
        if cached is not None and cached[0] is self._exec_group.exec_ \
                and cached[1] is eval_metric:
            return cached[2].rearm()
        from ..train_step import CompiledEvalStep

        try:
            step = CompiledEvalStep(self._exec_group, eval_metric)
        except MXNetError as exc:
            self.logger.info("device-side eval metrics unavailable (%s); "
                             "using the host path", exc)
            return None
        self._eval_step_cache = (self._exec_group.exec_, eval_metric, step)
        return step

    def program_artifacts(self):
        """The module's compiled programs as analysis artifacts.

        Returns ``{name: ProgramArtifact}`` for every program this module
        currently holds compiled: the fused train step (after its first
        run) and the cached compiled eval step (after a device-metric
        ``score``).  The uniform probe surface ``tools/mxlint.py`` and
        custom audits consume — see docs/static_analysis.md.
        """
        arts = {}
        if self._fused_step is not None:
            art = self._fused_step.artifact(group=self._exec_group)
            if art is not None:
                arts[art.name] = art
        cached = getattr(self, "_eval_step_cache", None)
        if cached is not None:
            art = cached[2].artifact()
            if art is not None:
                arts[art.name] = art
        return arts

    def _wrap_train_data(self, train_data):
        from .. import config as _config
        from ..io import DevicePrefetchIter

        if self._fused_step is None \
                or not _config.get("MXNET_DEVICE_PREFETCH") \
                or isinstance(train_data, DevicePrefetchIter):
            return train_data
        return DevicePrefetchIter(train_data, module=self)

    def _dispatch_fence(self):
        if self._fused_outputs is None or not self._fused_outputs:
            return None
        return self._fused_outputs[0].data

    def _sync_params_from_devices(self):
        self._flush_fused()
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def _handoff_fused_to_eager(self):
        """Move live state (params + optimizer slots) from the fused step to
        the eager path so momentum/moments survive the switch."""
        if self._fused_step is None or self._opt_owner != "fused":
            return
        self._flush_fused()
        self._fused_step.detach_metric()  # drains pending device sums
        self._pending_metric = None
        if self._updater is not None:
            self._fused_step.export_updater_states(
                self._updater, self._exec_group.param_names,
                self._context[0])
        self._opt_owner = "eager"

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._fused_step is not None and self._opt_owner == "fused":
            with open(fname, "wb") as fout:
                fout.write(self._fused_step.get_states())
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._fused_step is not None:
            with open(fname, "rb") as fin:
                self._fused_step.set_states(fin.read())
            self._opt_owner = "fused"
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        """Per-op output taps require the interpreted executor path, so a
        monitored module drops back to eager forward/backward/update."""
        assert self.binded
        self._monitor = mon
        if self._fused_step is not None:
            self._handoff_fused_to_eager()
            self._fused_step = None
        self._exec_group.install_monitor(mon)
