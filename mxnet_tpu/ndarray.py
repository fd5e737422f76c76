"""NDArray — the imperative array type.

TPU-native re-design of the reference NDArray
(`include/mxnet/ndarray.h:376-433`, `python/mxnet/ndarray.py`).  Instead of a
ref-counted Chunk over Storage + an engine variable, an NDArray owns an
immutable ``jax.Array``; XLA's async dispatch plays the role of the
dependency engine (every op returns immediately with a future-backed array;
``wait_to_read`` == ``block_until_ready``).  Mutation (`+=`, ``x[:] = v``,
aux-state updates) rebinds the underlying buffer — the ownership protocol
that replaces in-place writes (SURVEY §7 hard part (a)).

Operator functions (``mxnet_tpu.ndarray.relu`` etc.) are generated from the
op registry at import, mirroring `_init_ndarray_module`
(`python/mxnet/ndarray.py:2120+`).
"""
from __future__ import annotations

import functools
import struct
from collections import deque

import numpy as np

from .base import MXNetError, numeric_types
from .context import Context, cpu, current_context
from . import registry as _reg

__all__ = ["NDArray", "array", "empty", "zeros", "ones", "full", "arange",
           "save", "load", "concatenate", "imperative_invoke", "waitall"]

_DTYPE_ALIASES = {
    "float16": np.float16, "float32": np.float32, "float64": np.float64,
    "uint8": np.uint8, "int32": np.int32, "int8": np.int8, "int64": np.int64,
    "bool": np.bool_, "bfloat16": "bfloat16",
}

# ring buffer of recently produced arrays, so waitall() has something to block on
_RECENT = deque(maxlen=128)

# generated op functions (slice, abs, sum, ...) shadow builtins at module
# level, exactly as in the reference's mx.nd namespace — keep real ones here
_py_slice = slice
_py_abs = abs


def _np_dtype(dtype):
    if dtype is None:
        return np.float32
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            import jax.numpy as jnp
            return jnp.bfloat16
        return np.dtype(dtype).type
    return dtype


def _jax_put(value, ctx):
    import jax

    return jax.device_put(value, ctx.jax_device)


class NDArray:
    """Multi-dimensional array on a device context."""

    __slots__ = ("_data", "_ctx", "_base", "_idx", "writable")

    def __init__(self, data, ctx=None, base=None, idx=None, writable=True):
        self._ctx = ctx if ctx is not None else current_context()
        self._data = data
        self._base = base   # parent NDArray when this is a write-through view
        self._idx = idx
        self.writable = writable

    # -- data access -------------------------------------------------------
    @property
    def data(self):
        """The underlying jax.Array (re-sliced from base for views)."""
        if self._base is not None:
            return self._base.data[self._idx]
        return self._data

    def _set_data(self, new_data):
        # commit host arrays to this context's device immediately: leaving
        # numpy in _data would re-upload it on EVERY jitted call that takes
        # it as an argument
        if isinstance(new_data, np.ndarray):
            new_data = _jax_put(new_data, self._ctx)
        if self._base is not None:
            self._base._set_data(self._base.data.at[self._idx].set(new_data))
        else:
            self._data = new_data
        _RECENT.append(new_data)

    @property
    def handle(self):
        return self  # ctypes-handle compat shim

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def dtype(self):
        dt = self.data.dtype
        try:
            return np.dtype(dt).type
        except TypeError:
            return dt

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return self._ctx

    @property
    def T(self):
        from . import ndarray as nd
        return nd.transpose(self)

    # -- synchronization (engine facade) -----------------------------------
    def wait_to_read(self):
        """Block until the value is computed (reference: ndarray.h:153)."""
        import jax
        jax.block_until_ready(self.data)

    wait_to_write = wait_to_read

    # -- conversions -------------------------------------------------------
    def asnumpy(self):
        return np.asarray(self.data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype):
        import jax.numpy as jnp
        return NDArray(jnp.asarray(self.data, dtype=_np_dtype(dtype)), self._ctx)

    def copy(self):
        # jax buffers are immutable and mutation rebinds, so aliasing is a
        # correct copy: later writes to either NDArray cannot affect the other
        return NDArray(self.data, self._ctx)

    def copyto(self, other):
        """Copy to another NDArray or a context (reference: ndarray.py:533)."""
        if isinstance(other, NDArray):
            other._set_data(_jax_put(self.data, other._ctx))
            return other
        elif isinstance(other, Context):
            return NDArray(_jax_put(self.data, other), other)
        raise TypeError("copyto expects NDArray or Context")

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def reshape(self, shape, **kwargs):
        import jax.numpy as jnp
        if isinstance(shape, int):
            shape = (shape,)
        # support -1 and 0 (copy-dim) semantics of mxnet Reshape
        shape = tuple(self.shape[i] if s == 0 else s for i, s in enumerate(shape)) \
            if 0 in shape else tuple(shape)
        return NDArray(jnp.reshape(self.data, shape), self._ctx)

    def broadcast_to(self, shape):
        import jax.numpy as jnp
        return NDArray(jnp.broadcast_to(self.data, tuple(shape)), self._ctx)

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key.asnumpy()
        if isinstance(key, _py_slice) and key.step is not None and key.step != 1:
            raise ValueError("slice step cannot be non-unit")
        # base is self (not the root): chained views write through recursively
        # with each key kept relative to its own parent
        return NDArray(self.data[key], self._ctx, base=self, idx=key)

    def __setitem__(self, key, value):
        if not self.writable:
            raise MXNetError("trying to write to an immutable NDArray")
        import jax.numpy as jnp
        if isinstance(value, NDArray):
            value = value.data
        elif isinstance(value, (np.ndarray, list, tuple)) or np.isscalar(value):
            value = jnp.asarray(value, dtype=self.data.dtype)
        if isinstance(key, _py_slice) and key == _py_slice(None):
            value = jnp.broadcast_to(value, self.shape).astype(self.data.dtype)
            self._set_data(jnp.asarray(value))
        else:
            if isinstance(key, NDArray):
                key = key.asnumpy()
            self._set_data(self.data.at[key].set(value))

    # -- arithmetic (dispatches through the op registry so autograd sees it)
    def _binary(self, other, op, scalar_op, rop=False):
        from . import ndarray as nd
        if isinstance(other, NDArray):
            lhs, rhs = (other, self) if rop else (self, other)
            return getattr(nd, op)(lhs, rhs)
        elif isinstance(other, numeric_types):
            return getattr(nd, scalar_op)(self, scalar=float(other))
        raise TypeError("unsupported operand type %s" % type(other))

    def __add__(self, other):
        return self._binary(other, "broadcast_plus", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "broadcast_minus", "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, "broadcast_minus", "_rminus_scalar", rop=True)

    def __mul__(self, other):
        return self._binary(other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __div__(self, other):
        return self._binary(other, "broadcast_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, other):
        return self._binary(other, "broadcast_div", "_rdiv_scalar", rop=True)

    __rtruediv__ = __rdiv__

    def __pow__(self, other):
        return self._binary(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binary(other, "broadcast_power", "_rpower_scalar", rop=True)

    def __mod__(self, other):
        return self._binary(other, "broadcast_mod", "_mod_scalar")

    def __neg__(self):
        from . import ndarray as nd
        return nd.negative(self)

    def __eq__(self, other):
        if isinstance(other, (NDArray,) + numeric_types):
            return self._binary(other, "broadcast_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, other):
        if isinstance(other, (NDArray,) + numeric_types):
            return self._binary(other, "broadcast_not_equal", "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, other):
        return self._binary(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binary(other, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binary(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binary(other, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements is ambiguous")

    # in-place: rebind buffer (ownership protocol; engine would track WAR here)
    def __iadd__(self, other):
        self._set_data((self + other).data.astype(self.data.dtype))
        return self

    def __isub__(self, other):
        self._set_data((self - other).data.astype(self.data.dtype))
        return self

    def __imul__(self, other):
        self._set_data((self * other).data.astype(self.data.dtype))
        return self

    def __idiv__(self, other):
        self._set_data((self / other).data.astype(self.data.dtype))
        return self

    __itruediv__ = __idiv__

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return "<NDArray %s @%s>\n%s" % (
            "x".join(str(s) for s in self.shape), self._ctx, self.asnumpy())

    # -- serialization helpers (see save/load below) -----------------------


# ---------------------------------------------------------------------------
# Creation
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    else:
        src = np.asarray(source_array)
    if dtype is None:
        dtype = src.dtype if src.dtype != np.float64 else np.float32
    src = src.astype(_np_dtype(dtype) if not isinstance(dtype, str) or dtype != "bfloat16"
                     else _np_dtype(dtype), copy=False)
    return NDArray(_jax_put(src, ctx), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=None):
    import jax.numpy as jnp
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_jax_put(jnp.zeros(shape, dtype=_np_dtype(dtype)), ctx), ctx)


def ones(shape, ctx=None, dtype=None):
    import jax.numpy as jnp
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_jax_put(jnp.ones(shape, dtype=_np_dtype(dtype)), ctx), ctx)


def full(shape, val, ctx=None, dtype=None):
    import jax.numpy as jnp
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_jax_put(jnp.full(shape, val, dtype=_np_dtype(dtype)), ctx), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    import jax.numpy as jnp
    ctx = ctx or current_context()
    arr = np.arange(start, stop, step, dtype=_np_dtype(dtype) or np.float32)
    if repeat != 1:
        arr = np.repeat(arr, repeat)
    return NDArray(_jax_put(jnp.asarray(arr, dtype=_np_dtype(dtype)), ctx), ctx)


def concatenate(arrays, axis=0, always_copy=True):
    import jax.numpy as jnp
    assert arrays
    return NDArray(jnp.concatenate([a.data for a in arrays], axis=axis), arrays[0]._ctx)


@functools.lru_cache(maxsize=None)
def _fence_fn():
    import jax

    return jax.jit(lambda v: v + 1)


def waitall():
    """Block on ALL dispatched work (reference: Engine::WaitForAll).

    Two layers: drain the ring of recently produced arrays, then push a
    trivial fence computation onto every local device and block on it —
    XLA's per-device execution streams are FIFO, so the fence completing
    means everything enqueued before it has completed, including work whose
    result arrays fell out of the ring.
    """
    import jax
    import jax.numpy as jnp

    while _RECENT:
        jax.block_until_ready(_RECENT.popleft())
    fence = _fence_fn()
    for dev in jax.local_devices():
        x = jax.device_put(jnp.zeros((), jnp.float32), dev)
        jax.block_until_ready(fence(x))


# ---------------------------------------------------------------------------
# Serialization — .params format: magic, count, names, dtype/shape headers,
# raw little-endian bytes.  (API-compatible with reference save/load,
# src/ndarray/ndarray.cc:605-700; byte format is our own.)
# ---------------------------------------------------------------------------

_MAGIC = b"MXTPU001"


def save(fname, data):
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names, arrays = list(data.keys()), list(data.values())
    else:
        names, arrays = [""] * len(data), list(data)
    with open(fname, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack("<q", len(arrays)))
        for name, arr in zip(names, arrays):
            nb = name.encode()
            npy = arr.asnumpy()
            dt = str(npy.dtype).encode()
            f.write(struct.pack("<i", len(nb)))
            f.write(nb)
            f.write(struct.pack("<i", len(dt)))
            f.write(dt)
            f.write(struct.pack("<i", npy.ndim))
            f.write(struct.pack("<%dq" % npy.ndim, *npy.shape))
            raw = np.ascontiguousarray(npy).tobytes()
            f.write(struct.pack("<q", len(raw)))
            f.write(raw)


def load(fname):
    """Load from a .params path or an in-memory ``bytes`` blob (the latter
    serves the predict API, reference c_predict_api.h:59-77)."""
    import io as _io

    if isinstance(fname, (bytes, bytearray, memoryview)):
        return _load_stream(_io.BytesIO(bytes(fname)), "<bytes>")
    with open(fname, "rb") as f:
        return _load_stream(f, fname)


def _load_stream(f, fname):
    magic = f.read(8)
    if magic != _MAGIC:
        raise MXNetError("Invalid NDArray file format: %s" % fname)
    (count,) = struct.unpack("<q", f.read(8))
    names, arrays = [], []
    for _ in range(count):
        (nlen,) = struct.unpack("<i", f.read(4))
        name = f.read(nlen).decode()
        (dlen,) = struct.unpack("<i", f.read(4))
        dt = np.dtype(f.read(dlen).decode())
        (ndim,) = struct.unpack("<i", f.read(4))
        shape = struct.unpack("<%dq" % ndim, f.read(8 * ndim)) if ndim else ()
        (rawlen,) = struct.unpack("<q", f.read(8))
        buf = np.frombuffer(f.read(rawlen), dtype=dt).reshape(shape)
        names.append(name)
        arrays.append(array(buf, dtype=dt.type))
    if any(names):
        return dict(zip(names, arrays))
    return arrays


# ---------------------------------------------------------------------------
# Imperative dispatch — generated op functions
# ---------------------------------------------------------------------------

def imperative_invoke(opdef, nd_inputs, raw_attrs, out=None, is_train=None):
    """The single imperative dispatch path (MXImperativeInvoke analog)."""
    from . import autograd

    if opdef.key_var_num_args and opdef.key_var_num_args not in raw_attrs:
        raw_attrs = dict(raw_attrs)
        raw_attrs[opdef.key_var_num_args] = str(len(nd_inputs))
    attrs = opdef.parse_attrs(raw_attrs)
    n_aux = len(opdef.list_aux(attrs))
    if n_aux and len(nd_inputs) == opdef.n_inputs(attrs) + n_aux:
        nd_aux = nd_inputs[-n_aux:]
        nd_inputs = nd_inputs[:-n_aux]
    else:
        nd_aux = []
    if is_train is None:
        is_train = autograd.is_training()
    rng = None
    if opdef.needs_rng:
        from . import random as _rnd

        rng = _rnd.split_key()
    outs, new_aux = _reg.invoke(
        opdef,
        [a.data for a in nd_inputs],
        attrs,
        is_train=is_train,
        rng=rng,
        aux=[a.data for a in nd_aux],
    )
    recorded_aux = list(nd_aux)
    for nd_a, new_a in zip(nd_aux, new_aux):
        nd_a._set_data(new_a)
    ctx = nd_inputs[0]._ctx if nd_inputs else current_context()
    out_nds = [NDArray(o, ctx) for o in outs]
    # hide internal outputs (Dropout mask, BatchNorm mean/var) as the
    # reference's num_visible_outputs does
    n_vis = opdef.n_visible_outputs(attrs)
    out_nds = out_nds[:n_vis]
    for o in out_nds:
        _RECENT.append(o.data)
    if out is not None:
        # write into the destination arrays and record THOSE on the tape, so
        # downstream ops consuming `out` stay connected in autograd replay
        outs_req = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs_req, out_nds):
            dst._set_data(src.data)
        if autograd.is_recording():
            autograd.record_op(opdef, attrs, nd_inputs, list(outs_req), rng,
                               aux=recorded_aux)
        return out
    if autograd.is_recording():
        autograd.record_op(opdef, attrs, nd_inputs, out_nds, rng,
                           aux=recorded_aux)
    if len(out_nds) == 1:
        return out_nds[0]
    return out_nds


def _make_op_func(opdef):
    def op_func(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        nd_args = list(args)
        # accept NDArray kwargs by argument name (e.g. data=, weight=)
        if any(isinstance(v, NDArray) for v in kwargs.values()):
            probe = {k: v for k, v in kwargs.items() if not isinstance(v, NDArray)}
            attrs0 = opdef.parse_attrs(probe)
            names = opdef.list_arguments(attrs0) + opdef.list_aux(attrs0)
            for n in names:
                if n in kwargs and isinstance(kwargs[n], NDArray):
                    nd_args.append(kwargs.pop(n))
        return imperative_invoke(opdef, nd_args, kwargs, out)

    op_func.__name__ = opdef.name
    op_func.__doc__ = opdef.doc + "\n\nParameters\n----------\n" + opdef.schema.doc()
    return op_func


def _init_ndarray_module():
    """Generate module-level functions for every registered op."""
    import sys

    mod = sys.modules[__name__]
    for name in _reg.list_ops():
        opdef = _reg.get_op(name)
        setattr(mod, name, _make_op_func(opdef))


def onehot_encode(indices, out):
    """Legacy one-hot into `out` (reference: ndarray.py:986)."""
    from . import ndarray as nd
    depth = out.shape[1]
    res = nd.one_hot(indices, depth=depth)
    out._set_data(res.data)
    return out
