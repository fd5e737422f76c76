"""Executor — binds a Symbol to devices and runs it.

TPU-native re-design of GraphExecutor (`src/executor/graph_executor.cc`) and
`python/mxnet/executor.py`.  Where the reference runs a hand-built pipeline
(Gradient pass → PlaceDevice → InferShape → PlanMemory → per-node engine
ops), here the whole graph lowers into ONE jitted XLA program:

* forward  = jit(run_graph)                          — XLA fuses + plans memory
* backward = jit(vjp(run_graph)) w.r.t. grad-args    — the nnvm Gradient pass
* bulk-exec segments (graph_executor.cc:678) are implicit: the entire
  program is a single segment.
* grad_req add/write = functional accumulate, write-back into grad buffers.
* data-parallelism lives one level up: executor_group device_puts the batch
  with a mesh NamedSharding and replicates params, and jit propagates those
  committed input shardings — XLA inserts the psum collectives that the
  reference's KVStore Reduce performed.  The executor itself is
  sharding-agnostic.

Training forward runs the combined (outputs, grads, new_aux) program with
ones head-gradients — loss heads carry custom_vjp so this reproduces the
reference's Backward() semantics; ``backward(out_grads)`` with explicit head
gradients re-runs the combined program with those cotangents.
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError
from .context import Context, current_context
from .registry import OpContext, producers_of
from . import ndarray as nd

__all__ = ["Executor"]


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = group2ctx or {}
        self._placement = self._plan_placement(symbol, self._group2ctx)
        self._monitor_callback = None

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        # -- argument arrays
        if isinstance(args, dict):
            self.arg_dict = {n: args[n] for n in arg_names}
        else:
            if len(args) != len(arg_names):
                raise MXNetError("Length of args does not match arguments: %s"
                                 % arg_names)
            self.arg_dict = dict(zip(arg_names, args))
        self.arg_arrays = [self.arg_dict[n] for n in arg_names]

        # -- gradient request
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in arg_names}

        # -- gradient arrays
        if args_grad is None:
            self.grad_dict = {}
        elif isinstance(args_grad, dict):
            self.grad_dict = dict(args_grad)
        else:
            self.grad_dict = {n: g for n, g in zip(arg_names, args_grad)
                              if g is not None}
        for n in arg_names:
            if self.grad_req.get(n, "null") != "null" and n not in self.grad_dict:
                self.grad_req[n] = "null"
        self.grad_arrays = [self.grad_dict.get(n) for n in arg_names]

        # -- aux arrays
        if aux_states is None:
            aux_states = {}
        if isinstance(aux_states, dict):
            self.aux_dict = {n: aux_states[n] for n in aux_names}
        else:
            self.aux_dict = dict(zip(aux_names, aux_states))
        self.aux_arrays = [self.aux_dict[n] for n in aux_names]

        if self._placement:
            self._place_buffers()
        self._arg_names = arg_names
        self._aux_names = aux_names
        self._grad_names = [n for n in arg_names
                            if self.grad_req.get(n, "null") != "null"]
        self._outputs = None
        self._cached_grads = None
        self._fn_cache = {}
        self.outputs_ready = False

    # ------------------------------------------------------------------
    # model-parallel placement (group2ctx)
    # ------------------------------------------------------------------
    @staticmethod
    def _plan_placement(symbol, group2ctx):
        """Map node name -> jax.Device from ``ctx_group`` attrs.

        The reference's AssignContext/PlaceDevice pass
        (graph_executor.cc:242-331): nodes carrying a ``ctx_group`` attr run
        on the mapped device and ``_CrossDeviceCopy`` is inserted at cut
        edges — here the copies are ``jax.device_put`` at op boundaries
        (see _run_graph), and XLA async dispatch provides the cross-device
        overlap the reference got from its engine.  A group with no mapping
        raises rather than silently replicating.  Returns None when no
        placement is requested.
        """
        if not group2ctx:
            return None
        placement = {}
        for node in symbol._topo():
            group = node.attrs.get("ctx_group") if node.attrs else None
            if group is None:
                continue
            if group not in group2ctx:
                raise MXNetError(
                    "ctx_group %r on node %r has no entry in group2ctx "
                    "(mapped groups: %s)" % (group, node.name,
                                             sorted(group2ctx)))
            ctx = group2ctx[group]
            ctx = ctx if isinstance(ctx, Context) else Context(ctx)
            placement[node.name] = ctx.jax_device
        return placement or None

    @property
    def _default_device(self):
        """Device for nodes with no ctx_group under placement."""
        dev = getattr(self, "_default_dev_cache", None)
        if dev is None:
            dev = self._ctx.jax_device
            self._default_dev_cache = dev
        return dev

    def _place_buffers(self):
        """Make parameter/gradient NDArrays resident on their placed device
        so steady-state steps do no cross-device parameter traffic (the
        reference allocates each node's arrays on its assigned device)."""
        import jax

        for pool in (self.arg_dict, self.grad_dict, self.aux_dict):
            for name, arr in pool.items():
                dev = self._placement.get(name)
                if dev is not None and arr.data.devices() != {dev}:
                    arr._set_data(jax.device_put(arr.data, dev))

    # ------------------------------------------------------------------
    # graph execution as a pure function
    # ------------------------------------------------------------------
    def _run_graph(self, env_args, env_aux, rng, is_train, tap=None):
        """Topologically execute the node DAG on jnp values.

        ``tap(name, value)``, when given, is invoked with every node
        output — the analog of the reference's per-node monitor callback
        (`graph_executor.cc:758-778`).  Taps only make sense outside jit
        (eager execution), where intermediate values are materialized.
        """
        import jax

        from . import profiler as _prof
        from .obs.scopes import node_scope as _node_scope

        sym = self._symbol
        # per-node profiler spans are only meaningful when executing
        # eagerly on concrete values (under jit this loop runs once, at
        # trace time); XLA-side op attribution comes from the layer scope
        # (obs.scopes: mx.<layer>/<node name>, HLO metadata only)
        spans = False
        if _prof.is_running():
            probe = next(iter(env_args.values()), None)
            try:
                spans = not isinstance(probe, jax.core.Tracer)
            except AttributeError:
                spans = False
        values = {}
        new_aux = dict(env_aux)
        for seq, node in enumerate(sym._topo()):
            if node.is_variable:
                if node.is_aux_var:
                    values[(id(node), 0)] = env_aux[node.name]
                else:
                    values[(id(node), 0)] = env_args[node.name]
                continue
            attrs = node.parsed_attrs()
            n_args = node.op.n_inputs(attrs)
            ins = [values[(id(s), i)] for s, i in node.inputs[:n_args]]
            aux_ins = [values[(id(s), i)] for s, i in node.inputs[n_args:]]
            node_rng = jax.random.fold_in(rng, seq) if rng is not None \
                else None
            if self._placement is not None:
                # cut-edge transfer (the _CrossDeviceCopy analog): inputs
                # move to this node's device — unannotated nodes run on the
                # bind ctx, like the reference's PlaceDevice default.
                # device_put is a no-op for values already in place, and
                # its transpose moves cotangents back, so backward
                # transfers fall out of vjp
                dev = self._placement.get(node.name, self._default_device)
                ins = [jax.device_put(v, dev) for v in ins]
                aux_ins = [jax.device_put(v, dev) for v in aux_ins]
                if node_rng is not None:
                    node_rng = jax.device_put(node_rng, dev)
            octx = OpContext(is_train=is_train, rng=node_rng,
                             mesh_active=getattr(self, "_mesh_active",
                                                 False),
                             mesh=getattr(self, "_mesh", None),
                             producers=producers_of(node))
            with _node_scope(node):
                if spans:
                    with _prof.Scope(node.name):
                        outs, node_new_aux = node.op.fcompute(
                            attrs, ins, aux_ins, octx)
                else:
                    outs, node_new_aux = node.op.fcompute(
                        attrs, ins, aux_ins, octx)
            for i, o in enumerate(outs):
                values[(id(node), i)] = o
            if tap is not None:
                onames = node.op.list_outputs(attrs)
                for i in range(node.op.n_visible_outputs(attrs)):
                    suffix = onames[i] if i < len(onames) else str(i)
                    tap("%s_%s" % (node.name, suffix), outs[i])
            for (anode, _), val in zip(node.inputs[n_args:], node_new_aux):
                new_aux[anode.name] = val
        outputs = [values[(id(n), i)] for n, i in sym._outputs]
        return outputs, new_aux

    def _cast_u8(self, vals):
        """uint8 DATA inputs are compactly-shipped image bytes (ImageIter
        dtype='uint8'): cast to float at the graph boundary — same rule as
        the fused train step's on-device cast (train_step.py).  Only names
        in ``_u8_cast_names`` (set by the executor group from the bound
        data descriptors) are touched, so deliberately-integral uint8
        args (masks, custom-op bytes) keep their dtype."""
        import jax.numpy as jnp

        names = getattr(self, "_u8_cast_names", ())
        if not names:
            return vals
        return [v.astype(jnp.float32)
                if n in names and v.dtype == jnp.uint8 else v
                for n, v in zip(self._arg_names, vals)]

    def _fwd_impl(self, arg_vals, aux_vals, rng, is_train, tap=None):
        env_args = dict(zip(self._arg_names, self._cast_u8(arg_vals)))
        env_aux = dict(zip(self._aux_names, aux_vals))
        outs, new_aux = self._run_graph(env_args, env_aux, rng, is_train, tap)
        return outs, [new_aux[n] for n in self._aux_names]

    def _combined_impl(self, arg_vals, aux_vals, old_grads, head_grads, rng,
                       tap=None):
        import jax

        from . import config as _config

        grad_names = self._grad_names
        arg_names = self._arg_names
        aux_names = self._aux_names
        reqs = self.grad_req
        env_aux_in = dict(zip(aux_names, aux_vals))
        arg_vals = self._cast_u8(arg_vals)
        nograd = {n: v for n, v in zip(arg_names, arg_vals)
                  if n not in set(grad_names)}

        def fwd(gvals):
            env_args = dict(nograd)
            env_args.update(zip(grad_names, gvals))
            outs, new_aux = self._run_graph(env_args, env_aux_in, rng, True,
                                            tap)
            return outs, [new_aux[n] for n in aux_names]

        if tap is None and _config.get("MXNET_BACKWARD_DO_MIRROR"):
            # memonger analog: rematerialize activations in the backward
            # pass instead of keeping them live (reference mirror option)
            fwd = jax.checkpoint(fwd)
        gvals = [v for n, v in zip(arg_names, arg_vals) if n in set(grad_names)]
        outs, vjp_fn, new_aux = jax.vjp(fwd, gvals, has_aux=True)
        if head_grads is None:
            import jax.numpy as jnp

            cts = [jnp.ones_like(o) for o in outs]
        else:
            cts = list(head_grads)
        (grads,) = vjp_fn(cts)
        out_grads = []
        for gname, g in zip(grad_names, grads):
            if reqs[gname] == "add":
                out_grads.append(old_grads[grad_names.index(gname)] + g)
            else:
                out_grads.append(g)
        return outs, new_aux, out_grads

    def _get_fn(self, kind):
        """kind: 'fwd_test' | 'fwd_train' | 'combined'"""
        fn = self._fn_cache.get(kind)
        if fn is not None:
            return fn
        import jax

        from . import config as _config

        # MXNET_ENGINE_TYPE=NaiveEngine: run everything eagerly op-by-op
        # (the reference's debugging engine); bulk-exec-inference off does
        # the same for inference graphs only.  group2ctx placement also
        # runs eagerly: each op dispatches async onto its own device (the
        # engine-overlap model), since one jit program owns one device set.
        compiled = _config.get("MXNET_ENGINE_TYPE") != "NaiveEngine" \
            and self._placement is None
        if kind == "fwd_test" and not _config.get("MXNET_EXEC_BULK_EXEC_INFERENCE"):
            compiled = False

        if kind in ("fwd_test", "fwd_train"):
            is_train = kind == "fwd_train"

            def run(arg_vals, aux_vals, rng):
                return self._fwd_impl(arg_vals, aux_vals, rng, is_train)

            fn = jax.jit(run) if compiled else run
        else:
            def combined(arg_vals, aux_vals, old_grads, head_grads, rng):
                return self._combined_impl(arg_vals, aux_vals, old_grads,
                                           head_grads, rng)

            fn = jax.jit(combined) if compiled else combined
        self._fn_cache[kind] = fn
        return fn

    # ------------------------------------------------------------------
    # public API (reference: python/mxnet/executor.py)
    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        import jax

        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("Unknown argument %s" % k)
            self.arg_dict[k]._set_data(
                v.data if isinstance(v, nd.NDArray) else v)

        arg_vals = [self.arg_dict[n].data for n in self._arg_names]
        aux_vals = [self.aux_dict[n].data for n in self._aux_names]
        from . import random as _rnd

        rng = _rnd.split_key()
        self._last_rng = rng  # reused by backward(out_grads): same dropout masks

        tap = None
        if self._monitor_callback is not None and \
                getattr(self._monitor_callback, "active", True):
            # monitored runs execute eagerly (the NaiveEngine analog) so
            # every op's output exists to be observed — reference taps each
            # node in graph_executor.cc:758-778.  A disarmed tap (Monitor
            # between intervals) keeps the fast jitted path.
            cb = self._monitor_callback

            def tap(name, value):
                cb(name, nd.NDArray(value, self._ctx))

        from . import profiler as _prof

        if is_train and self._grad_names:
            old_grads = [self.grad_dict[n].data for n in self._grad_names]
            if tap is not None:
                # vjp tracing would hand the tap abstract tracers, so the
                # observation pass runs separately on concrete values
                self._fwd_impl(arg_vals, aux_vals, rng, True, tap)
            with _prof.Scope("forward_backward", "executor"):
                outs, new_aux, grads = self._get_fn("combined")(
                    arg_vals, aux_vals, old_grads, None, rng)
            self._cached_grads = grads
        else:
            if tap is not None:
                outs, new_aux = self._fwd_impl(arg_vals, aux_vals, rng,
                                               is_train, tap)
            else:
                with _prof.Scope("forward", "executor"):
                    outs, new_aux = self._get_fn(
                        "fwd_train" if is_train else "fwd_test")(
                        arg_vals, aux_vals, rng)
            self._cached_grads = None
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._set_data(v)
        self._outputs = [nd.NDArray(o, self._ctx) for o in outs]
        self.outputs_ready = True
        return self._outputs

    def backward(self, out_grads=None):
        if not self._grad_names:
            return
        if out_grads is not None:
            if isinstance(out_grads, nd.NDArray):
                out_grads = [out_grads]
            import jax

            arg_vals = [self.arg_dict[n].data for n in self._arg_names]
            aux_vals = [self.aux_dict[n].data for n in self._aux_names]
            old_grads = [self.grad_dict[n].data for n in self._grad_names]
            # reuse the forward pass's key so stochastic ops (Dropout) apply
            # the same mask the caller's observed outputs came from
            rng = getattr(self, "_last_rng", None)
            if rng is None:
                from . import random as _rnd

                rng = _rnd.split_key()
            fn = self._get_fn("combined")
            outs, new_aux, grads = fn(arg_vals, aux_vals, old_grads,
                                      [g.data for g in out_grads], rng)
        else:
            if self._cached_grads is None:
                raise MXNetError(
                    "backward() called before forward(is_train=True)")
            grads = self._cached_grads
        for n, g in zip(self._grad_names, grads):
            self.grad_dict[n]._set_data(g.astype(self.grad_dict[n].data.dtype))
        self._cached_grads = None

    @property
    def outputs(self):
        if self._outputs is None:
            raise MXNetError("Executor has not been run")
        return self._outputs

    def compiled_hlo(self, kind="combined"):
        """Optimized-HLO text of a cached compiled step (None when eager).

        The XLA-era analog of the reference's bandwidth probe: collectives
        are explicit ops in the compiled program, so communication per step
        is statically countable — feed this to
        ``parallel.hlo_stats.collective_stats``.  Avals (+shardings) are
        rebuilt from the live buffers at call time, so nothing is retained
        on the training hot path for this probe.
        """
        import jax

        fn = self._fn_cache.get(kind)
        if fn is None or not hasattr(fn, "lower"):
            return None
        rng = getattr(self, "_last_rng", None)
        if rng is None:
            return None

        def _aval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)

        arg_vals = [_aval(self.arg_dict[n].data) for n in self._arg_names]
        aux_vals = [_aval(self.aux_dict[n].data) for n in self._aux_names]
        if kind == "combined":
            old_grads = [_aval(self.grad_dict[n].data)
                         for n in self._grad_names]
            args = (arg_vals, aux_vals, old_grads, None, _aval(rng))
        else:
            args = (arg_vals, aux_vals, _aval(rng))
        return fn.lower(*args).compile().as_text()

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in arguments" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    array.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise MXNetError("Found name %r not in aux states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes; jit specializes per shape the same
        way bucketing shares memory pools in the reference.

        Contract (reference executor.py reshape): shapes of arguments *not*
        named in kwargs may only change when ``partial_shaping`` is set, and
        any array may only grow when ``allow_up_sizing`` is set (the
        reference reuses the old buffer's memory, so growth needs opt-in;
        here growth allocates a fresh buffer but the contract is enforced
        identically so programs behave the same on both frameworks).
        """
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args, new_grads = {}, {}
        for name, shape, arr in zip(self._arg_names, arg_shapes, self.arg_arrays):
            if tuple(shape) == arr.shape:
                new_args[name] = arr
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                if not partial_shaping and name not in kwargs:
                    raise MXNetError(
                        "Shape of unspecified argument %r changed (%s -> %s);"
                        " pass partial_shaping=True to allow this" %
                        (name, arr.shape, tuple(shape)))
                if not allow_up_sizing and \
                        int(np.prod(shape)) > int(np.prod(arr.shape)):
                    raise MXNetError(
                        "New shape of %r is larger than the original (%s -> "
                        "%s); pass allow_up_sizing=True to allow this" %
                        (name, arr.shape, tuple(shape)))
                new_args[name] = nd.zeros(shape, self._ctx, dtype=arr.dtype)
                if name in self.grad_dict:
                    new_grads[name] = nd.zeros(shape, self._ctx, dtype=arr.dtype)
        new_aux = {}
        for name, shape, arr in zip(self._aux_names, aux_shapes, self.aux_arrays):
            new_aux[name] = arr if tuple(shape) == arr.shape else \
                nd.zeros(shape, self._ctx, dtype=arr.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self.grad_req, new_aux, group2ctx=self._group2ctx)

    # ------------------------------------------------------------------
    @staticmethod
    def simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, **kwargs):
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("Cannot infer shapes with inputs %s" % kwargs)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        # propagate dtypes through the graph from the (optional) type_dict
        # seeds, so int inputs stay int and fp16/bf16 flows into weights
        # instead of every buffer defaulting to float32
        arg_types, _, aux_types = symbol.infer_type(**(type_dict or {}))
        type_dict = dict(zip(arg_names, arg_types))
        type_dict.update(zip(aux_names, aux_types))
        args = {}
        grads = {}
        if isinstance(grad_req, str):
            req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            req = dict(zip(arg_names, grad_req))
        else:
            req = {n: grad_req.get(n, "null") for n in arg_names}
        for name, shape in zip(arg_names, arg_shapes):
            dtype = type_dict.get(name, np.float32)
            # reuse shared executor buffers when shapes match (bucketing)
            if shared_exec is not None and name in shared_exec.arg_dict and \
                    shared_exec.arg_dict[name].shape == tuple(shape):
                args[name] = shared_exec.arg_dict[name]
                if name in shared_exec.grad_dict and req.get(name, "null") != "null":
                    grads[name] = shared_exec.grad_dict[name]
                    continue
            else:
                args[name] = nd.zeros(shape, ctx, dtype=dtype)
            if req.get(name, "null") != "null":
                grads[name] = nd.zeros(shape, ctx, dtype=dtype)
        aux = {}
        for name, shape in zip(aux_names, aux_shapes):
            dtype = type_dict.get(name, np.float32)
            if shared_exec is not None and name in shared_exec.aux_dict and \
                    shared_exec.aux_dict[name].shape == tuple(shape):
                aux[name] = shared_exec.aux_dict[name]
            else:
                aux[name] = nd.zeros(shape, ctx, dtype=dtype)
        return Executor(symbol, ctx, args, grads, req, aux, group2ctx=group2ctx)
