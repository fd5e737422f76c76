"""DataParallelExecutorGroup — data parallelism over a device mesh.

Reference: `python/mxnet/module/executor_group.py` (651 LoC): one executor
per device, batch sliced along axis 0 (`decide_slices`:207), gradients
reduced through KVStore.  TPU-native re-design: ONE executor jitted over a
``jax.sharding.Mesh`` whose 'data' axis spans the bound contexts; the batch
is device_put with a NamedSharding on axis 0 and parameters are replicated.
XLA's SPMD partitioner then inserts the psum collectives over ICI that the
reference's Comm::Reduce/Broadcast performed explicitly — gradients arrive
at `update()` already globally summed.

Note one intentional deviation: BatchNorm statistics are computed over the
global (mesh-wide) batch, i.e. sync-BN, where the reference normalizes
per-device (SURVEY §7f).  For contexts==1 they coincide.
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from .. import ndarray as nd
from ..executor import Executor
from ..io import DataDesc


def _as_desc_list(shapes):
    out = []
    for s in shapes or []:
        if isinstance(s, DataDesc):
            out.append(s)
        else:
            name, shape = s[0], s[1]
            out.append(DataDesc(name, shape))
    return out


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad, shared_group=None,
                 logger=logging, fixed_param_names=None, grad_req="write",
                 state_names=None, mesh_config=None):
        self.symbol = symbol
        self.contexts = contexts
        self.mesh_config = mesh_config
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = set(fixed_param_names or [])
        self.logger = logger

        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()

        self.data_shapes = _as_desc_list(data_shapes)
        self.label_shapes = _as_desc_list(label_shapes) if label_shapes else []
        self.data_names = [d.name for d in self.data_shapes]
        self.label_names = [d.name for d in self.label_shapes]

        self.batch_size = self.data_shapes[0].shape[0]
        self._data_par = len(contexts)
        if mesh_config is not None:
            sizes = mesh_config.resolve(len(contexts))
            self._data_par = sizes[mesh_config.names.index("data")]
        if self.batch_size % max(1, self._data_par) != 0:
            raise MXNetError("batch size %d must be divisible by the data-"
                             "parallel degree %d" % (self.batch_size,
                                                     self._data_par))

        # gradient requests
        if isinstance(grad_req, str):
            base_req = grad_req
        else:
            base_req = None
        self.grad_req = {}
        for name in self.arg_names:
            if name in self.param_names:
                req = (base_req or (grad_req.get(name, "write")
                                    if isinstance(grad_req, dict) else "write"))
                if not for_training or name in self.fixed_param_names:
                    req = "null"
            elif name in self.data_names:
                req = "write" if (for_training and inputs_need_grad) else "null"
            else:
                req = "null"
            self.grad_req[name] = req

        self._mesh = None
        self._data_sharding = None
        self._rep_sharding = None
        self._input_shardings = {}
        self._param_mesh_axes = {}
        self._model_par = 1
        self._seq_par = 1
        self._expert_par = 1
        # params (and their aux/grads) eligible for tensor-parallel
        # annotation; inputs/labels never are
        self._tp_param_names = set(self.param_names) | set(self.aux_names)
        if len(contexts) > 1:
            self._build_mesh()

        self._bind_exec(shared_group)

    # ------------------------------------------------------------------
    def _build_mesh(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devices = [c.jax_device for c in self.contexts]
        if len(set(devices)) != len(devices):
            if any(c.device_type not in ("cpu", "cpu_pinned")
                   for c in self.contexts):
                raise MXNetError("contexts %s name %d distinct device(s); "
                                 "a multi-chip bind needs one chip per "
                                 "context" % (self.contexts,
                                              len(set(devices))))
            # fake mx.cpu(N) multi-context on one host device (reference
            # test trick): single-device execution, semantics unchanged
            self.logger.debug("contexts map to %d physical device(s); running "
                              "unsharded", len(set(devices)))
            return
        if self.mesh_config is not None:
            from ..parallel.mesh import build_mesh

            self._mesh = build_mesh(self.mesh_config, devices)
            axis_sizes = dict(zip(self.mesh_config.names,
                                  self.mesh_config.resolve(len(devices))))
            self._model_par = axis_sizes["model"]
            self._seq_par = axis_sizes.get("seq", 1)
            self._expert_par = axis_sizes.get("expert", 1)
        else:
            self._mesh = Mesh(np.array(devices), ("data",))
            self._model_par = 1
            self._seq_par = 1
            self._expert_par = 1
        self._data_sharding = NamedSharding(self._mesh, P("data"))
        self._rep_sharding = NamedSharding(self._mesh, P())
        # per-input shardings from the DataDesc layouts, fixed at bind time:
        # the batch axis (N) shards on 'data'; with seq>1 the time axis (T)
        # shards on 'seq' — sequence/context parallelism, GSPMD inserting
        # the collectives (leapfrogs SURVEY §2.5 'Sequence-length scaling':
        # the reference buckets, the TPU build shards time)
        self._input_shardings = {}
        for desc in self.data_shapes + (self.label_shapes or []):
            layout = getattr(desc, "layout", None) or ""
            if self._seq_par > 1 and "T" in layout and "N" in layout:
                spec = [None] * len(desc.shape)
                spec[layout.index("N")] = "data"
                spec[layout.index("T")] = "seq"
                self._input_shardings[desc.name] = \
                    NamedSharding(self._mesh, P(*spec))
        # op-declared param mesh axes (OpDef.mesh_axes, e.g. MoE expert
        # stacks): walk the graph once and map each variable that feeds such
        # an argument to its axis
        axis_sizes = dict(self._mesh.shape)
        # per-param placement records for the sharding-coverage lint
        # pass (analysis.passes.ShardingCoveragePass): which params a
        # plan claimed, which silently degraded to replication
        self._sharding_coverage = {}
        self._param_mesh_axes = {}
        for node in self.symbol._topo():
            if node.is_variable or not node.op.mesh_axes:
                continue
            arg_names = node.op.list_arguments(node.parsed_attrs())
            for (inode, _), arg in zip(node.inputs, arg_names):
                axis = node.op.mesh_axes.get(arg)
                if axis and inode.is_variable \
                        and axis_sizes.get(axis, 1) > 1:
                    self._param_mesh_axes[inode.name] = axis
        # Megatron column/row pairing for the 'model' axis, derived from one
        # graph walk (parallel/tp_rules.py) — one psum per FC/Conv pair
        # instead of the naive plan's per-layer all-gathers
        # None = planner didn't run (naive mode); {} = planner ran and found
        # nothing shardable (replicate, do NOT fall back to the naive
        # per-layer all-gather plan megatron mode exists to avoid)
        self._tp_plan = None
        if self._model_par > 1:
            from .. import config as _config

            if _config.get("MXNET_TP_MODE") != "naive":
                from ..parallel.tp_rules import plan_tensor_parallel

                self._tp_plan = plan_tensor_parallel(self.symbol)

    def _input_sharding(self, name):
        return self._input_shardings.get(name, self._data_sharding)

    def _param_sharding(self, name, shape):
        """Tensor-parallel sharding rule over the 'model' mesh axis.

        The scaling-book recipe rather than hand-written psums: weights are
        annotated and the GSPMD partitioner derives activation shardings and
        inserts the collectives.  Which weights, and along which dim, comes
        from per-op graph metadata — OpDef.mesh_axes (expert stacks) first,
        then the Megatron column/row plan (parallel/tp_rules.py) that pairs
        FC1-column with FC2-row so one psum per pair replaces per-layer
        all-gathers.  MXNET_TP_MODE=naive restores the round-3 blanket
        dim-0 heuristic for A/B measurement.  Params whose sharded dim
        doesn't divide the axis stay replicated (correctness unaffected).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        # coverage record for the sharding-coverage pass: every exit
        # below stamps what happened to this param (matched spec,
        # intentional replicate, or a silent degrade)
        rec = {"shape": [int(d) for d in shape or ()],
               "source": "scalar" if not shape else "default"}
        self._sharding_coverage[name] = rec
        # op-declared axes first (OpDef.mesh_axes — e.g. MoE expert stacks
        # shard dim 0 on 'expert'); graph metadata, not name matching
        axis = self._param_mesh_axes.get(name)
        if axis is not None and shape:
            if shape[0] % dict(self._mesh.shape)[axis] == 0:
                spec = [axis] + [None] * (len(shape) - 1)
                rec["source"], rec["spec"] = "mesh_axes", list(spec)
                return NamedSharding(self._mesh, P(*spec))
            # the op DECLARED this axis — losing it to divisibility is
            # the silent degrade the coverage pass turns into an error
            rec["source"], rec["degrade"] = "mesh_axes", "indivisible"
        if self._model_par <= 1 or not shape:
            return self._rep_sharding
        if self._tp_plan is not None:
            spec = self._tp_plan.get(name)
            if spec is None:
                return self._rep_sharding
            if len(spec) != len(shape):
                rec["source"], rec["degrade"] = "plan", "rank-mismatch"
                return self._rep_sharding
            for dim, ax in enumerate(spec):
                if ax is not None and shape[dim] % self._model_par != 0:
                    rec["source"], rec["degrade"] = "plan", "indivisible"
                    return self._rep_sharding  # unshardable: replicate
            rec["source"], rec["spec"] = "plan", list(spec)
            rec.pop("degrade", None)
            return NamedSharding(self._mesh, P(*spec))
        # naive mode: blanket dim-0 column sharding
        if shape[0] % self._model_par != 0:
            return self._rep_sharding
        spec = ["model"] + [None] * (len(shape) - 1)
        if rec.get("degrade") is None:
            rec["source"], rec["spec"] = "naive", list(spec)
        return NamedSharding(self._mesh, P(*spec))

    def _place(self, arr, sharded, name=None):
        """device_put an NDArray's buffer onto the bound device(s): data
        sharding for batch inputs, the tensor-parallel rule for named
        params (replicated when model==1), else replicated.  No-op when
        already placed."""
        import jax

        if self._mesh is None:
            target = self.contexts[0].jax_device
        elif sharded:
            target = self._input_sharding(name) if name is not None \
                else self._data_sharding
        elif name is not None and (self._model_par > 1
                                   or self._param_mesh_axes) \
                and name in self._tp_param_names:
            target = self._param_sharding(name, arr.shape)
        else:
            target = self._rep_sharding
        arr._set_data(jax.device_put(arr.data, target))
        return arr

    # ------------------------------------------------------------------
    def _bind_exec(self, shared_group):
        kwargs = {d.name: d.shape for d in self.data_shapes + self.label_shapes}
        type_dict = {d.name: d.dtype for d in self.data_shapes + self.label_shapes}
        shared_exec = shared_group.execs[0] if shared_group is not None else None
        ctx = self.contexts[0]
        exec_ = Executor.simple_bind(self.symbol, ctx, grad_req=self.grad_req,
                                     type_dict=type_dict, shared_exec=shared_exec,
                                     **kwargs)
        # ops with GSPMD-opaque fast paths (pallas kernels) must fall back
        # when this executor's buffers are mesh-sharded; ops with
        # mesh-aware shardings (sparse MoE dispatch) get the mesh itself
        exec_._mesh_active = self._mesh is not None
        exec_._mesh = self._mesh
        # uint8 DATA inputs (compact image batches) cast to float at the
        # graph boundary; other uint8 args keep their dtype
        exec_._u8_cast_names = set(self.data_names)
        # shard data args on the mesh; params replicate (or shard on the
        # model axis under tensor parallelism), grads/aux follow their param
        for name, arr in exec_.arg_dict.items():
            self._place(arr, sharded=name in self.data_names
                        or name in self.label_names, name=name)
        for name, arr in exec_.aux_dict.items():
            self._place(arr, sharded=False, name=name)
        for name, arr in exec_.grad_dict.items():
            self._place(arr, sharded=False, name=name)
        self.execs = [exec_]
        self.exec_ = exec_
        self.data_arrays = [exec_.arg_dict[n] for n in self.data_names]
        self.label_arrays = [exec_.arg_dict[n] for n in self.label_names
                             if n in exec_.arg_dict]
        self.param_arrays = [exec_.arg_dict[n] for n in self.param_names]
        self.grad_arrays = [exec_.grad_dict.get(n) for n in self.param_names]
        self.aux_arrays = [exec_.aux_dict[n] for n in self.aux_names]
        self.input_grad_arrays = [exec_.grad_dict.get(n) for n in self.data_names] \
            if self.inputs_need_grad else []

    # ------------------------------------------------------------------
    def reshape(self, data_shapes, label_shapes):
        if _as_desc_list(data_shapes) == self.data_shapes and \
                _as_desc_list(label_shapes or []) == self.label_shapes:
            return

        # share the old executor so parameter buffers (same shapes) carry
        # over — only shape-changed inputs/outputs are reallocated
        class _Shared:
            pass

        shared = _Shared()
        shared.execs = list(self.execs)
        self.__init__(self.symbol, self.contexts, None, data_shapes, label_shapes,
                      self.param_names, self.for_training, self.inputs_need_grad,
                      shared_group=shared,
                      fixed_param_names=self.fixed_param_names,
                      grad_req=self.grad_req, mesh_config=self.mesh_config)

    def set_params(self, arg_params, aux_params):
        for name, arr in arg_params.items():
            if name in self.exec_.arg_dict:
                arr.copyto(self.exec_.arg_dict[name])
                self._place(self.exec_.arg_dict[name], sharded=False,
                            name=name)
        for name, arr in (aux_params or {}).items():
            if name in self.exec_.aux_dict:
                arr.copyto(self.exec_.aux_dict[name])
                self._place(self.exec_.aux_dict[name], sharded=False,
                            name=name)

    def get_params(self, arg_params, aux_params):
        for name in self.param_names:
            self.exec_.arg_dict[name].copyto(arg_params[name])
        for name in self.aux_names:
            self.exec_.aux_dict[name].copyto(aux_params[name])

    # ------------------------------------------------------------------
    def load_data_batch(self, data_batch):
        for name, arr in zip(self.data_names, data_batch.data):
            dst = self.exec_.arg_dict[name]
            dst._set_data(arr.data.astype(dst.dtype) if arr.dtype != dst.dtype
                          else arr.data)
            self._place(dst, sharded=True, name=name)
        if self.label_names and data_batch.label:
            for name, arr in zip(self.label_names, data_batch.label):
                if name in self.exec_.arg_dict:
                    dst = self.exec_.arg_dict[name]
                    dst._set_data(arr.data.astype(dst.dtype)
                                  if arr.dtype != dst.dtype else arr.data)
                    self._place(dst, sharded=True, name=name)

    def _ensure_placement(self):
        """Re-pin params/grads/aux to the mesh (replicated).  Eager optimizer
        updates and kvstore pulls commit results to a single device; this
        restores the mesh sharding before the next compiled step.  device_put
        with an unchanged sharding is a no-op, so the steady-state cost is
        nil."""
        if self._mesh is None:
            return
        for name, arr in zip(self.param_names + self.aux_names,
                             self.param_arrays + self.aux_arrays):
            self._place(arr, sharded=False, name=name)
        for name, arr in zip(self.param_names + self.data_names,
                             self.grad_arrays + self.input_grad_arrays):
            if arr is not None:
                self._place(arr, sharded=False, name=name)

    def forward(self, data_batch, is_train=None):
        self.load_data_batch(data_batch)
        self._ensure_placement()
        if is_train is None:
            is_train = self.for_training
        self.exec_.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to run backward"
        self.exec_.backward(out_grads)

    def get_outputs(self, merge_multi_context=True):
        return list(self.exec_.outputs)

    def get_input_grads(self, merge_multi_context=True):
        return [self.exec_.grad_dict[n] for n in self.data_names]

    def update_metric(self, eval_metric, labels):
        from .. import metric as metric_mod

        # pull only the output heads the metric actually consumes
        # (metric.output_indices); every head it doesn't name stays an
        # unmaterialized device array instead of riding a d2h transfer
        eval_metric.update(
            labels, list(metric_mod.select_outputs(eval_metric,
                                                   self.exec_.outputs)))

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
