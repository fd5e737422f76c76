"""The operator registry.

TPU-native analog of the reference's two op registries (legacy
``MXNET_REGISTER_OP_PROPERTY`` `include/mxnet/operator.h:166` and NNVM
``NNVM_REGISTER_OP`` `include/mxnet/op_attr_types.h:59`), unified into one:
an :class:`OpDef` bundles

* a declarative parameter schema (`attrs.ParamSchema`, the dmlc::Parameter
  analog),
* ``fcompute`` — a pure JAX function ``(attrs, inputs, aux, octx) ->
  (outputs, new_aux)``; JAX tracing replaces the reference's separate
  CPU/GPU kernels, and jax AD replaces hand-written backward passes
  (loss-style ops install ``jax.custom_vjp`` internally),
* shape/type inference (explicit fn for ops whose *parameter* shapes must be
  deduced from data shapes; abstract-eval fallback otherwise),
* argument/output/aux naming for Symbol binding.

Every imperative invoke and every executor node dispatches through here.
Op-level fusion comes from caching ``jax.jit`` per (op, attrs, is_train):
this is the analog of the reference's engine pushing one compiled kernel
per op (`src/c_api/c_api_ndarray.cc:233` PushFCompute).
"""
from __future__ import annotations

import functools

from .attrs import FrozenAttrs, ParamSchema
from .base import MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "invoke", "OpContext",
           "producers_of"]

_OPS = {}


class OpContext:
    """Per-invocation execution context: train flag + PRNG key +
    whether the enclosing executor runs over a device mesh (ops with
    GSPMD-opaque fast paths, e.g. pallas kernels, bail out when set).
    ``mesh`` carries the executor's Mesh (or None) for ops that place
    sharding constraints themselves — e.g. sparse MoE dispatch pinning
    its expert-major tensors to the 'expert' axis.  ``producers`` names
    the ops whose outputs are this node's inputs (None for a variable;
    empty where the caller walks no graph), for an op whose lowering
    depends on what hands it its operand."""

    __slots__ = ("is_train", "rng", "mesh_active", "mesh", "producers")

    def __init__(self, is_train=False, rng=None, mesh_active=False,
                 mesh=None, producers=()):
        self.is_train = is_train
        self.rng = rng
        self.mesh_active = mesh_active
        self.mesh = mesh
        self.producers = producers


def producers_of(node):
    """``OpContext.producers`` of one graph node."""
    return tuple(src.op.name if src.op is not None else None
                 for src, _ in node.inputs)


def _default_arg_names(n):
    if n == 1:
        return ["data"]
    if n == 2:
        return ["lhs", "rhs"]
    return ["arg%d" % i for i in range(n)]


class OpDef:
    """A registered operator."""

    def __init__(
        self,
        name,
        fcompute,
        schema=None,
        num_inputs=1,
        num_outputs=1,
        num_visible_outputs=None,
        arguments=None,
        outputs=None,
        aux=None,
        infer_shape=None,
        infer_type=None,
        needs_rng=False,
        needs_train=False,
        key_var_num_args=None,
        hint=None,
        doc="",
        visible=True,
        mesh_axes=None,
        user_defined=False,
    ):
        self.name = name
        self.fcompute = fcompute
        self.schema = schema or ParamSchema()
        self.num_inputs = num_inputs  # int or callable(attrs) -> int
        self.num_outputs = num_outputs  # int or callable(attrs) -> int
        self.num_visible_outputs = num_visible_outputs  # defaults to num_outputs
        self._arguments = arguments
        self._outputs = outputs
        self._aux = aux
        self.infer_shape_fn = infer_shape
        self.infer_type_fn = infer_type
        self.needs_rng = needs_rng
        self.needs_train = needs_train
        self.key_var_num_args = key_var_num_args
        self.hint = hint or name.lstrip("_").lower()
        self.doc = doc
        self.visible = visible
        # runtime-registered user kernels (mx.rtc): exempt from the
        # first-party registry-coverage sweep
        self.user_defined = user_defined
        # {argument_name: mesh_axis} — weights whose leading dim belongs on
        # a named mesh axis (e.g. MoE expert stacks on 'expert'); the mesh
        # executor reads this to shard the bound variables (op-level
        # metadata, not parameter-name matching)
        self.mesh_axes = dict(mesh_axes or {})

    # -- introspection -----------------------------------------------------
    def n_inputs(self, attrs):
        n = self.num_inputs
        return n(attrs) if callable(n) else n

    def n_outputs(self, attrs):
        n = self.num_outputs
        return n(attrs) if callable(n) else n

    def n_visible_outputs(self, attrs):
        n = self.num_visible_outputs
        if n is None:
            return self.n_outputs(attrs)
        return n(attrs) if callable(n) else n

    def list_arguments(self, attrs):
        if self._arguments is not None:
            a = self._arguments
            return list(a(attrs)) if callable(a) else list(a)
        return _default_arg_names(self.n_inputs(attrs))

    def list_outputs(self, attrs):
        if self._outputs is not None:
            o = self._outputs
            return list(o(attrs)) if callable(o) else list(o)
        n = self.n_outputs(attrs)
        return ["output"] if n == 1 else ["output%d" % i for i in range(n)]

    def list_aux(self, attrs):
        if self._aux is None:
            return []
        a = self._aux
        return list(a(attrs)) if callable(a) else list(a)

    def parse_attrs(self, raw):
        return raw if isinstance(raw, FrozenAttrs) else self.schema.parse(raw)

    # -- shape/type inference ----------------------------------------------
    def infer_shape(self, attrs, in_shapes, aux_shapes=None):
        """Returns (in_shapes, out_shapes, aux_shapes); fills unknown inputs.

        Mirrors the nnvm InferShape pass contract
        (`src/executor/graph_executor.cc:425`).
        """
        if self.infer_shape_fn is not None:
            return self.infer_shape_fn(attrs, in_shapes, aux_shapes)
        if any(s is None for s in in_shapes):
            raise MXNetError(
                "Op %s cannot infer missing input shapes (got %s)" % (self.name, in_shapes)
            )
        out_shapes = self._abstract_eval_shapes(attrs, in_shapes)
        return in_shapes, out_shapes, aux_shapes or []

    def _abstract_eval_shapes(self, attrs, in_shapes, dtype="float32"):
        import jax
        import jax.numpy as jnp

        ins = [jax.ShapeDtypeStruct(tuple(s), jnp.float32) for s in in_shapes]

        def fn(*xs):
            octx = OpContext(is_train=False, rng=jax.random.PRNGKey(0) if self.needs_rng else None)
            outs, _ = self.fcompute(attrs, list(xs), [], octx)
            return outs

        outs = jax.eval_shape(fn, *ins)
        return [tuple(o.shape) for o in outs]

    def __repr__(self):
        return "OpDef(%s)" % self.name


def simple_compute(fn, num_outputs=1):
    """Adapt ``fn(attrs, *inputs) -> array|tuple`` to canonical fcompute."""

    def fcompute(attrs, inputs, aux, octx):
        out = fn(attrs, *inputs)
        if not isinstance(out, (tuple, list)):
            out = [out]
        return list(out), list(aux)

    return fcompute


def register(name, aliases=(), simple=True, **kwargs):
    """Decorator registering a compute function under ``name`` (+aliases)."""

    def deco(fn):
        fcompute = simple_compute(fn) if simple else fn
        opdef = OpDef(name, fcompute, **kwargs)
        _register_opdef(opdef, aliases)
        return fn

    return deco


def _register_opdef(opdef, aliases=()):
    _OPS[opdef.name] = opdef
    for a in aliases:
        _OPS[a] = opdef
    return opdef


def register_op(opdef, aliases=()):
    return _register_opdef(opdef, aliases)


def get_op(name):
    op = _OPS.get(name)
    if op is None:
        raise MXNetError("Operator %s is not registered" % name)
    return op


def has_op(name):
    return name in _OPS


def list_ops():
    return sorted(_OPS.keys())


# ---------------------------------------------------------------------------
# Cached jit dispatch — the imperative fast path.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted(opdef, attrs, is_train, n_aux, with_rng):
    import jax

    def run(inputs, aux, rng):
        octx = OpContext(is_train=is_train, rng=rng)
        outs, new_aux = opdef.fcompute(attrs, list(inputs), list(aux), octx)
        return list(outs), list(new_aux)

    return jax.jit(run)


_DUMMY_KEY = None


def _dummy_key():
    global _DUMMY_KEY
    if _DUMMY_KEY is None:
        import jax

        _DUMMY_KEY = jax.random.PRNGKey(0)
    return _DUMMY_KEY


def invoke(opdef, inputs, attrs=None, is_train=False, rng=None, aux=()):
    """Execute an op on raw jax arrays. Returns (outputs, new_aux).

    The analog of MXImperativeInvoke (`src/c_api/c_api_ndarray.cc:322`):
    dispatch is async (XLA), results are futures the same way engine-tracked
    NDArrays are.
    """
    attrs = opdef.parse_attrs(attrs or {})
    if rng is None and opdef.needs_rng:
        from . import random as _rnd

        rng = _rnd.split_key()
    if rng is None:
        # unused placeholder, keeps the jit signature static without paying a
        # per-call PRNGKey device allocation
        rng = _dummy_key()
    fn = _jitted(opdef, attrs, bool(is_train), len(aux), opdef.needs_rng)
    from . import profiler as _prof

    if _prof.is_running():
        # per-op dispatch span, the engine OprExecStat analog
        with _prof.Scope(opdef.name, "imperative"):
            return fn(list(inputs), list(aux), rng)
    return fn(list(inputs), list(aux), rng)
