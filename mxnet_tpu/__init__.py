"""mxnet_tpu — a TPU-native deep learning framework.

A from-scratch re-design of the reference MXNet (v0.9.5) API surface for TPU
hardware: JAX/XLA replaces mshadow kernels, the memory planner, and the
dependency engine; jit-compiled graph programs replace the graph executor;
XLA collectives over a device mesh replace KVStore comm.  See SURVEY.md at
the repo root for the capability map.

Typical usage matches the reference:

    import mxnet_tpu as mx
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=128)
    mod = mx.mod.Module(net, ...)
    mod.fit(train_iter, ...)
"""
import time as _time

_t0 = _time.perf_counter_ns()
# the process accounts for its own start (obs/startup.py): this file from
# here to its last line is phase `import.self`, `import jax` apart from it
from .obs import startup as _startup  # noqa: E402

_importing = _startup.begin_import(_t0)

from . import base
from .base import MXNetError, AttrScope, NameManager
from .context import Context, cpu, gpu, tpu, current_context, num_devices
from . import attrs
from . import registry
from . import ops  # registers all operators
from . import operator  # registers the Custom user-op framework
from . import ndarray
from . import ndarray as nd
from . import random
from . import autograd
from . import symbol
from . import symbol as sym
from .symbol import Variable, Group

ndarray._init_ndarray_module()
symbol._init_symbol_module()

from . import executor
from .executor import Executor
from . import initializer
from .initializer import init_registry  # noqa: F401
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import image
from . import recordio
from . import kvstore
from . import kvstore_server
from . import callback
from . import monitor
from . import module
from . import module as mod
from . import model
from .model import FeedForward
from . import predictor
from . import rtc
from .predictor import Predictor
from . import decode
from .decode import DecodePredictor, DecodeServer
from . import rnn
from . import parallel
from . import analysis
from . import checkpoint
from . import obs
from . import profiler
from . import visualization
from . import visualization as viz
from . import contrib
from . import test_utils

__version__ = "0.1.0"

_importing.__exit__(None, None, None)
del _time, _t0, _startup, _importing
