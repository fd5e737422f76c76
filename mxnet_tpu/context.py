"""Device context.

Reference: `/root/reference/python/mxnet/context.py` and
`include/mxnet/base.h` (Context struct).  TPU-native redesign: a Context is a
named handle onto a JAX device.  ``mx.cpu(i)`` maps to host (XLA-CPU)
devices; ``mx.tpu(i)`` maps to TPU chips.  ``mx.gpu(i)`` is accepted as an
alias for ``tpu`` so reference-era scripts run unchanged — on this framework
the accelerator is a TPU.

CPU device ids beyond the number of host devices wrap around (the reference
uses fake `mx.cpu(N)` contexts to test model parallelism on one box —
tests/python/unittest/test_multi_device_exec.py:20 — and we keep that trick:
distinct contexts remain distinct keys for placement even when they share
hardware).  Accelerator ids never wrap and never fall back: ``mx.tpu(i)``
on a machine without chip ``i`` raises, so a run that asked for the chip
cannot quietly execute on the host.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_devices"]

_devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
_devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}


class Context:
    """A device context (reference: python/mxnet/context.py:8-88)."""

    _state = threading.local()
    devtype2str = _devtype2str
    devstr2type = _devstr2type

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = _devstr2type[device_type]
            self.device_id = device_id

    @property
    def device_type(self):
        return _devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- JAX mapping ------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete ``jax.Device`` this context maps onto.

        Contexts address this process's devices: under multi-process
        (jax.distributed) only local devices are addressable, so the lookup
        is over ``local_devices`` — matching the reference, where each
        worker's ``mx.gpu(i)`` is a local ordinal.
        """
        import jax

        multiproc = jax.process_count() > 1
        if self.device_type in ("cpu", "cpu_pinned"):
            try:
                devs = jax.local_devices(backend="cpu") if multiproc \
                    else jax.devices("cpu")
            except RuntimeError:
                devs = jax.local_devices() if multiproc else jax.devices()
            return devs[self.device_id % len(devs)]
        devs = _accelerator_devices(local=multiproc)
        if not 0 <= self.device_id < len(devs):
            raise MXNetError("%s: this process has %d TPU chip(s)"
                             % (self, len(devs)))
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._state, "stack"):
            Context._state.stack = []
        Context._state.stack.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        Context._state.stack.pop()


def _accelerator_devices(local=False):
    """This process's TPU chips; raises when jax has no TPU backend."""
    import jax

    try:
        return jax.local_devices(backend="tpu") if local \
            else jax.devices("tpu")
    except RuntimeError as exc:
        raise MXNetError(
            "no TPU backend (default jax platform is %r): mx.tpu()/mx.gpu() "
            "contexts need a chip; use mx.cpu() on the host"
            % jax.default_backend()) from exc


def cpu(device_id=0):
    """Return a CPU context (reference: context.py:90)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator context; on this framework 'gpu' means a TPU chip."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context("tpu", device_id)


def num_devices(device_type="tpu"):
    import jax

    if device_type in ("cpu", "cpu_pinned"):
        try:
            return len(jax.devices("cpu"))
        except RuntimeError:
            return len(jax.devices())
    try:
        return len(_accelerator_devices())
    except MXNetError:
        return 0


def current_context():
    """The default context (reference: context.py:103)."""
    if not hasattr(Context._state, "stack") or not Context._state.stack:
        return Context("cpu", 0)
    return Context._state.stack[-1]
