"""BaseModule — the high-level train/eval/predict interface.

API parity with the reference's ``python/mxnet/module/base_module.py``
(fit/score/predict/forward_backward and the abstract surface below), with
the training loop rebuilt around this framework's compiled-step execution
model: ``fit`` is a thin driver over ``_fit_epoch``, and evaluation /
prediction share one padded-batch iterator helper instead of three copies
of the reset/limit/pad logic.
"""
from __future__ import annotations

import logging
import time
from collections import deque, namedtuple

from .. import metric as metric_mod
from .. import ndarray as nd

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _callbacks(cb):
    """Normalize a callback argument to an iterable."""
    if cb is None:
        return ()
    return cb if isinstance(cb, (list, tuple)) else (cb,)


def _fire(cbs, *args):
    for cb in _callbacks(cbs):
        cb(*args)


def _block_on(fence):
    """Block until a dispatched step's result is materialized on device."""
    import jax

    jax.block_until_ready(fence)


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0
        # fault-tolerance sidecar (mxnet_tpu.elastic.ElasticController),
        # armed by fit() for the duration of a training run
        self._elastic = None

    # ------------------------------------------------------------------
    # High-level interface
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def _eval_batches(self, eval_data, num_batch, reset):
        """Yield (nbatch, batch) honoring the batch limit; resets first."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch >= num_batch:
                return
            yield nbatch, batch

    @staticmethod
    def _unpadded(batch, outputs):
        """Strip the iterator's tail padding from a batch's outputs.

        Each output is sliced by its own leading dim, so a scalar/aggregated
        loss output alongside per-sample outputs is not mis-sliced.
        """
        return [out[:out.shape[0] - batch.pad] if out.ndim > 0 else out
                for out in outputs]

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Run an evaluation pass, returning the metric's name/value list.

        Drivers with a compiled forward may arm device-side metric
        accumulation (``_bind_eval_metric``): the whole pass then performs
        no per-batch device→host transfer — the classic path materializes
        label + pred on the host for every batch.  A metric/graph pair the
        device path rejects falls back to the host path mid-loop with
        everything already accumulated preserved.
        """
        eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        eval_step = self._bind_eval_metric(eval_metric)
        nbatch = -1
        try:
            for nbatch, batch in self._eval_batches(eval_data, num_batch,
                                                    reset):
                if eval_step is not None:
                    try:
                        eval_step.run(batch)
                    except Exception as exc:
                        # demote to the host path; device sums drain into
                        # the metric so nothing accumulated is lost
                        self.logger.info(
                            "device-side eval metrics unavailable (%s); "
                            "using the host path", exc)
                        eval_step.finish()
                        eval_step = None
                if eval_step is None:
                    self.forward(batch, is_train=False)
                    self.update_metric(eval_metric, batch.label)
                _fire(batch_end_callback,
                      BatchEndParam(epoch, nbatch, eval_metric, locals()))
        finally:
            if eval_step is not None:
                eval_step.finish()
        _fire(score_end_callback,
              BatchEndParam(epoch, nbatch + 1, eval_metric, locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Generator over (outputs, nbatch, batch) with padding stripped."""
        for nbatch, batch in self._eval_batches(eval_data, num_batch, reset):
            self.forward(batch, is_train=False)
            yield (self._unpadded(batch, self.get_outputs()), nbatch, batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Collect forward outputs over a dataset.  With ``merge_batches``
        the per-batch output lists are concatenated along axis 0."""
        collected = [list(outs) for outs, _, _
                     in self.iter_predict(eval_data, num_batch, reset)]
        if not collected or not merge_batches:
            return collected
        widths = {len(outs) for outs in collected}
        if len(widths) != 1:
            raise ValueError("Cannot merge batches: mismatched number of outputs")
        merged = [nd.concatenate([outs[i] for outs in collected])
                  for i in range(widths.pop())]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    # ------------------------------------------------------------------
    # Training
    # ------------------------------------------------------------------
    def prepare_fit(self, train_data, initializer=None, arg_params=None,
                    aux_params=None, allow_missing=False, force_rebind=False,
                    force_init=False, kvstore="local", optimizer="sgd",
                    optimizer_params=(("learning_rate", 0.01),), monitor=None):
        """Bind + init params + init optimizer for training on
        ``train_data``'s shapes.  Split out of fit() so custom loops can
        reuse the setup."""
        from ..initializer import Uniform

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer or Uniform(0.01),
                         arg_params=arg_params, aux_params=aux_params,
                         allow_missing=allow_missing, force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

    # ------------------------------------------------------------------
    # async-loop hooks (overridden by drivers with compiled steps)
    # ------------------------------------------------------------------
    def _bind_metric(self, eval_metric):
        """Give the driver a chance to fold ``eval_metric``'s accumulation
        into its compiled step (device-side metrics).  Default: host path."""

    def _bind_eval_metric(self, eval_metric):
        """Return a ``CompiledEvalStep``-like object (``run(batch)`` /
        ``finish()``) accumulating ``eval_metric`` on device during
        ``score``, or None for the classic host path.  Default: host."""
        return None

    def _wrap_train_data(self, train_data):
        """Optionally wrap the training iterator (device prefetch).  The
        wrapper must preserve reset(); fit() closes it when it adds one."""
        return train_data

    def _dispatch_fence(self):
        """A device array that completes when the most recently dispatched
        training step has finished, or None when the driver executes
        synchronously.  fit() bounds the number of outstanding steps by
        blocking on the step-K-behind fence."""
        return None

    def _fit_epoch(self, epoch, train_data, eval_metric, batch_end_callback,
                   monitor):
        """One pass over train_data; returns the wall-clock cost.

        The loop rides JAX's async dispatch: with a compiled step and
        device-side metric accumulation the body performs no host sync, so
        up to ``MXNET_MAX_STEPS_IN_FLIGHT`` steps stay outstanding and the
        host prepares batch n+K while the device runs step n.  Device
        memory is bounded by blocking on the step-K-behind fence rather
        than the current result (the dependency-engine analog: the host
        throttles on an OLD variable's WaitToRead, never the newest).
        Input-pipeline stalls and host waits are recorded in
        ``profiler.step_stats`` for the bench contract.
        """
        from contextlib import ExitStack

        from .. import config as _config
        from .. import profiler as _prof

        start = time.time()
        eval_metric.reset()
        limit = max(1, int(_config.get("MXNET_MAX_STEPS_IN_FLIGHT")))
        fences = deque()
        nbatch = 0
        if self._elastic is not None:
            # resuming into this epoch: metric sums back to the fence
            # values, iterator fast-forwarded past the already-done batches
            nbatch = self._elastic.on_epoch_start(self, epoch, train_data,
                                                  eval_metric)
        it = iter(train_data)
        # MXNET_TRANSFER_GUARD arms jax's device->host transfer guard for
        # the whole epoch body: with device-side metrics + prefetch + the
        # fence deque, the hot loop performs no d2h at all, and 'disallow'
        # turns that invariant into a runtime error on the TPU rig (the
        # analysis host-sync pass is the static half).  Thread-local, so
        # the prefetch worker's h2d device_puts are unaffected.
        guard = str(_config.get("MXNET_TRANSFER_GUARD") or "off").lower()
        stack = ExitStack()
        if guard not in ("", "off"):
            import jax

            stack.enter_context(jax.transfer_guard_device_to_host(guard))
        # one timeline span per epoch (always-on, bounded ring): the
        # host_wait/input_wait/ckpt_* loop spans nest under it
        from .. import obs as _obs

        stack.enter_context(_obs.span("fit_epoch", cat="loop",
                                      args={"epoch": int(epoch)}))
        with stack:
            while True:
                # one fit_step span per iteration; its children are
                # input_wait, the step's own train_step program span,
                # metric_update, host_wait and batch_end_callback
                with _obs.top_span("fit_step", cat="loop",
                                   args={"step": nbatch}):
                    t0 = time.perf_counter()
                    with _obs.mirror("input_wait"):
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                    _prof.record_input_wait(time.perf_counter() - t0, t0)
                    if monitor is not None:
                        monitor.tic()
                    self.forward_backward(batch)
                    self.update()
                    with _obs.span("metric_update", cat="loop"):
                        self.update_metric(eval_metric, batch.label)
                    fence = self._dispatch_fence()
                    if fence is not None:
                        fences.append(fence)
                        # at most `limit` dispatched-but-unfinished steps:
                        # with limit=1 this waits on the step just issued
                        # (synchronous)
                        if len(fences) >= limit:
                            t0 = time.perf_counter()
                            with _obs.mirror("host_wait"):
                                _block_on(fences.popleft())
                            _prof.record_host_wait(
                                time.perf_counter() - t0, t0)
                    if monitor is not None:
                        monitor.toc_print()
                    _prof.record_step()
                    with _obs.span("batch_end_callback", cat="loop"):
                        _fire(batch_end_callback,
                              BatchEndParam(epoch, nbatch, eval_metric,
                                            locals()))
                    if self._elastic is not None:
                        # fault injection, the periodic fence checkpoint,
                        # and the liveness poll (which drains `fences` and
                        # raises ReconfigureSignal when the mesh must
                        # re-form).  After the callback, so user callbacks
                        # observe every completed batch exactly once even
                        # across a resume.
                        self._elastic.on_step(self, epoch, nbatch, fences)
                    nbatch += 1
        if fences:
            # steps chain through donated params, so the newest fence
            # transitively covers every outstanding step
            t0 = time.perf_counter()
            _block_on(fences[-1])
            _prof.record_host_wait(time.perf_counter() - t0, t0)
            fences.clear()
        return time.time() - start

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, elastic=None):
        """Train for ``num_epoch`` epochs: compiled train steps per batch,
        optional validation pass and checkpoints per epoch.

        ``elastic`` is an optional
        :class:`~mxnet_tpu.elastic.ElasticController` (auto-created from
        ``MXNET_CKPT_DIR``/``MXNET_CKPT_PERIOD`` when unset): async fenced
        checkpoints at step boundaries, auto-resume from the last
        committed fence, and — with a failure monitor — mid-fit mesh
        shrink/regrow on heartbeat transitions (docs/elasticity.md).
        """
        from .. import elastic as elastic_mod

        assert num_epoch is not None, "please specify number of epochs"
        self.prepare_fit(train_data, initializer=initializer,
                         arg_params=arg_params, aux_params=aux_params,
                         allow_missing=allow_missing,
                         force_rebind=force_rebind, force_init=force_init,
                         kvstore=kvstore, optimizer=optimizer,
                         optimizer_params=optimizer_params, monitor=monitor)
        eval_metric = metric_mod.create(eval_metric)
        validation_metric = validation_metric or eval_metric
        # async loop setup: device-side metric accumulation in the compiled
        # step, and device prefetch of upcoming batches (both no-ops for
        # drivers/configs without a fused step)
        self._bind_metric(eval_metric)
        fit_data = self._wrap_train_data(train_data)
        if elastic is None:
            elastic = elastic_mod.from_env()
            if elastic is not None and \
                    getattr(self, "_exec_group", None) is None:
                # env-armed checkpointing on a driver without executor-
                # group state to fence (Bucketing/Sequential/Python
                # modules): train WITHOUT checkpoints rather than abort —
                # the env knobs are ambient, not a per-call opt-in.  An
                # explicitly passed controller still fails loudly.
                self.logger.warning(
                    "MXNET_CKPT_DIR is set but %s has no executor-group "
                    "state to fence; training without elastic "
                    "checkpoints", type(self).__name__)
                elastic = None
        self._elastic = elastic
        if elastic is not None:
            # auto-resume: a committed fence in the checkpoint directory
            # restores params/slots/RNG and advances the starting epoch
            begin_epoch = elastic.attach(self, eval_metric, begin_epoch)

        try:
            epoch = begin_epoch
            first_epoch = True
            while epoch < num_epoch:
                if not first_epoch:
                    # reset at epoch START: after the last epoch there is
                    # no reset, so a prefetching wrapper's worker is not
                    # restarted just to have its read-ahead thrown away
                    fit_data.reset()
                first_epoch = False
                try:
                    cost = self._fit_epoch(epoch, fit_data, eval_metric,
                                           batch_end_callback, monitor)
                except elastic_mod.ReconfigureSignal as sig:
                    # a heartbeat transition: in-flight steps are already
                    # drained; re-form the mesh on the survivors, restore
                    # the last fence, and continue from its epoch
                    epoch = elastic.handle_reconfigure(self, sig,
                                                       eval_metric)
                    continue
                # reading the metric drains any pending device accumulation
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, cost)

                # materialize params host-side once per epoch: checkpoints
                # and user callbacks observe a consistent snapshot
                arg_snap, aux_snap = self.get_params()
                self.set_params(arg_snap, aux_snap)
                _fire(epoch_end_callback, epoch, self.symbol, arg_snap,
                      aux_snap)

                if eval_data:
                    for name, val in self.score(
                            eval_data, validation_metric,
                            score_end_callback=eval_end_callback,
                            batch_end_callback=eval_batch_end_callback,
                            epoch=epoch):
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                epoch += 1
        finally:
            if elastic is not None:
                elastic.finish()
            self._elastic = None
            if fit_data is not train_data and hasattr(fit_data, "close"):
                fit_data.close()
            # fit() leaves the caller's iterator fresh (the pre-async loop
            # reset after every epoch; a second fit() must not silently
            # iterate zero batches)
            train_data.reset()

    # ------------------------------------------------------------------
    # Parameter persistence
    # ------------------------------------------------------------------
    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        blob = {"arg:%s" % k: v for k, v in arg_params.items()}
        blob.update({"aux:%s" % k: v for k, v in aux_params.items()})
        nd.save(fname, blob)

    def load_params(self, fname):
        arg_params, aux_params = {}, {}
        for key, value in nd.load(fname).items():
            kind, _, name = key.partition(":")
            if kind == "arg":
                arg_params[name] = value
            elif kind == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    # ------------------------------------------------------------------
    # Abstract surface (implemented by Module / BucketingModule / ...)
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
