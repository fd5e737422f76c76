"""Optimizers (reference: python/mxnet/optimizer.py, 755 LoC).

Each ``update(index, weight, grad, state)`` dispatches to the fused update
ops (`mxnet_tpu/ops/optimizer_ops.py` ↔ reference `src/operator/
optimizer_op.cc`) — one jitted XLA fusion per update, with state tensors
written back in place of the reference's engine-mutated NDArrays.
"""
from __future__ import annotations

import math

import numpy as np

from . import ndarray as nd
from .ndarray import NDArray, zeros
from .base import MXNetError

__all__ = ["Optimizer", "SGD", "NAG", "SGLD", "ccSGD", "DCASGD", "Adam",
           "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Test", "create",
           "get_updater", "register"]


class Optimizer:
    """Base optimizer (reference: optimizer.py:10-135)."""

    opt_registry = {}

    @staticmethod
    def register(klass):
        Optimizer.opt_registry[klass.__name__.lower()] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        if param_idx2name is None:
            param_idx2name = {}
        self.idx2name = param_idx2name.copy()
        self.sym = sym
        self._set_lr_wd_mult_from_sym(sym)

    def _set_lr_wd_mult_from_sym(self, sym):
        self.sym_lr_mult = {}
        self.sym_wd_mult = {}
        if sym is not None:
            attr = sym.attr_dict()
            for name in sym.list_arguments():
                if name in attr:
                    if "__lr_mult__" in attr[name]:
                        self.sym_lr_mult[name] = float(attr[name]["__lr_mult__"])
                    if "__wd_mult__" in attr[name]:
                        self.sym_wd_mult[name] = float(attr[name]["__wd_mult__"])

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    def update_multi(self, indices, weights, grads, states):
        """Update many parameters in one step.  Subclasses with a fused
        whole-model kernel (SGD, Adam) override this: ONE jitted XLA call
        replaces the reference's per-parameter engine pushes — essential on
        TPU where per-op dispatch latency would dominate the step."""
        for i, w, g, s in zip(indices, weights, grads, states):
            self.update(i, w, g, s)

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = args_lr_mult.copy()

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            # reference convention: no weight decay on bias/gamma/beta
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        name = self.idx2name.get(index, index if isinstance(index, str) else None)
        if name is not None and name in self.sym_lr_mult:
            lr *= self.sym_lr_mult[name]
        if index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif name is not None and name in self.lr_mult:
            lr *= self.lr_mult[name]
        return lr

    def _get_wd(self, index):
        wd = self.wd
        name = self.idx2name.get(index, index if isinstance(index, str) else None)
        if name is not None and name in self.sym_wd_mult:
            wd *= self.sym_wd_mult[name]
        if index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif name is not None and name in self.wd_mult:
            wd *= self.wd_mult[name]
        return wd

    def _common_kwargs(self):
        kw = {"rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        return kw

    # -- functional form, for the fused (donated, jitted) train step --------
    def fused_kernel(self):
        """Pure-functional form of this optimizer, traceable inside jax.jit.

        Returns ``(make_slots, apply)`` or None when unsupported (Module then
        falls back to the eager update path):

        * ``make_slots(w)``: jnp weight -> tuple of jnp slot arrays
        * ``apply(w, g, slots, lr, wd, rescale, clip, extra)``: all-jnp
          update; ``lr`` arrives already bias-corrected/scheduled
          (host-side, like the eager ``update()``); ``rescale``/``clip``
          and the ``extra`` vector (``fused_extra()`` — momentum/betas/
          epsilon) are runtime scalars so later mutation of
          ``self.momentum`` etc. is honored without recompiling
          (clip <= 0 means no clipping).  Only *structural* choices
          (whether momentum slots exist at all, centered RMSProp) are
          baked at build time.
        """
        return None

    def fused_extra(self):
        """Runtime hyper-vector consumed by ``apply``'s ``extra`` argument.

        Re-read from ``self`` every step, so mutating hyperparameters after
        the fused step compiled keeps fused and eager paths in agreement.
        """
        return np.zeros(0, np.float32)

    def fused_hyper(self, indices):
        """Host-side per-step hyperparams for the fused step: bumps update
        counts exactly as the eager path does (same integer index keys, so
        fused<->eager handoffs see one consistent count) and returns
        ``(lrs, wds, rescale, clip)`` numpy arrays/scalars, one lr/wd per
        entry in ``indices``."""
        for idx in indices:
            self._update_count(idx)
        lrs = np.array([self._get_lr(i) for i in indices], np.float32)
        wds = np.array([self._get_wd(i) for i in indices], np.float32)
        clip = np.float32(self.clip_gradient
                          if self.clip_gradient is not None else -1.0)
        return lrs, wds, np.float32(self.rescale_grad), clip

    def pack_state(self, arrays):
        """Assemble a ``create_state``-shaped value from a flat list of
        state arrays — the inverse of flattening into fused slots.  The
        default maps 0 -> None, 1 -> bare array, n -> tuple; optimizers
        whose create_state is a 1-tuple (RMSProp) override this."""
        if not arrays:
            return None
        if len(arrays) == 1:
            return arrays[0]
        return tuple(arrays)


register = Optimizer.register


@register
class SGD(Optimizer):
    """SGD with momentum, via fused sgd(_mom)_update (reference: :279)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self._fused_fn = None

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        kw = self._common_kwargs()
        if state is not None:
            new_w, new_m = nd.sgd_mom_update(weight, grad, state, lr=lr, wd=wd,
                                             momentum=self.momentum, **kw)
            state._set_data(new_m.data)
        else:
            new_w = nd.sgd_update(weight, grad, lr=lr, wd=wd, **kw)
        weight._set_data(new_w.data)

    def _fused(self):
        import jax
        import jax.numpy as jnp

        if self._fused_fn is not None:
            return self._fused_fn
        momentum = self.momentum
        rescale = self.rescale_grad
        clip = self.clip_gradient

        def fused(ws, gs, ms, lrwd):
            new_ws, new_ms = [], []
            for i, (w, g, m) in enumerate(zip(ws, gs, ms)):
                lr = lrwd[0, i]
                wd = lrwd[1, i]
                g = g * rescale
                if clip is not None:
                    g = jnp.clip(g, -clip, clip)
                if momentum != 0.0:
                    m = momentum * m - lr * (g + wd * w)
                    w = w + m
                    new_ms.append(m)
                else:
                    w = w - lr * (g + wd * w)
                    new_ms.append(m)
                new_ws.append(w)
            return new_ws, new_ms

        # no donation: NDArray facade may hold other refs to the old buffers
        self._fused_fn = jax.jit(fused)
        return self._fused_fn

    def fused_kernel(self):
        import jax.numpy as jnp

        # slot *structure* is compile-time; the momentum value itself rides
        # in `extra` so post-compile mutation stays honored
        has_momentum = self.momentum != 0.0

        def make_slots(w):
            return (jnp.zeros_like(w),) if has_momentum else ()

        def apply(w, g, slots, lr, wd, rescale, clip, extra):
            g = g * rescale
            g = jnp.where(clip > 0, jnp.clip(g, -clip, clip), g)
            if has_momentum:
                momentum = extra[0]
                (m,) = slots
                m = momentum * m - lr * (g + wd * w)
                return w + m, (m,)
            return w - lr * (g + wd * w), ()

        return make_slots, apply

    def fused_extra(self):
        return np.array([self.momentum], np.float32)

    def update_multi(self, indices, weights, grads, states):
        for i in indices:
            self._update_count(i)
        # one (2, n) host array for all lr/wd scalars: a single transfer
        # instead of 2n tiny ones
        lrwd = np.stack([
            np.array([self._get_lr(i) for i in indices], np.float32),
            np.array([self._get_wd(i) for i in indices], np.float32)])
        ms = [s.data if s is not None else w.data
              for s, w in zip(states, weights)]
        new_ws, new_ms = self._fused()([w.data for w in weights],
                                       [g.data for g in grads], ms, lrwd)
        for w, nw in zip(weights, new_ws):
            w._set_data(nw)
        if self.momentum != 0.0:
            for s, nm in zip(states, new_ms):
                s._set_data(nm)


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference: :330)."""

    def fused_kernel(self):
        import jax.numpy as jnp

        has_momentum = self.momentum != 0.0

        def make_slots(w):
            return (jnp.zeros_like(w),) if has_momentum else ()

        def apply(w, g, slots, lr, wd, rescale, clip, extra):
            g = g * rescale
            g = jnp.where(clip > 0, jnp.clip(g, -clip, clip), g)
            g = g + wd * w
            if has_momentum:
                momentum = extra[0]
                (m,) = slots
                m = momentum * m + g
                return w - lr * (g + momentum * m), (m,)
            return w - lr * g, ()

        return make_slots, apply

    # SGD's whole-model kernel is plain momentum; the eager path takes
    # ``update`` below, one parameter at a time
    update_multi = Optimizer.update_multi

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        if state is not None:
            mom = state
            mom *= self.momentum
            grad += wd * weight
            mom += grad
            grad += self.momentum * mom
            weight += -lr * grad
        else:
            weight += -lr * (grad + wd * weight)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (reference: :365)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        noise = nd.normal(loc=0.0, scale=math.sqrt(lr), shape=weight.shape,
                          ctx=weight.context)
        weight += -lr / 2 * (grad + wd * weight) + noise


@register
class ccSGD(SGD):
    """Alias of SGD in this framework (reference ccSGD was a C++ fast path)."""


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: :398)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, weight.context), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        mom, previous_weight = state
        delta = -lr * (grad + wd * weight +
                       self.lamda * grad * grad * (weight - previous_weight))
        if mom is not None:
            mom *= self.momentum
            mom += delta
            delta = mom
        previous_weight._set_data(weight.data)
        weight += delta


@register
class Adam(Optimizer):
    """Adam, via fused adam_update (reference: :451)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def fused_kernel(self):
        import jax.numpy as jnp

        def make_slots(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def apply(w, g, slots, lr, wd, rescale, clip, extra):
            beta1, beta2, eps = extra[0], extra[1], extra[2]
            mean, var = slots
            g = g * rescale
            g = jnp.where(clip > 0, jnp.clip(g, -clip, clip), g)
            g = g + wd * w
            mean = beta1 * mean + (1 - beta1) * g
            var = beta2 * var + (1 - beta2) * jnp.square(g)
            return w - lr * mean / (jnp.sqrt(var) + eps), (mean, var)

        return make_slots, apply

    def fused_extra(self):
        return np.array([self.beta1, self.beta2, self.epsilon], np.float32)

    def fused_hyper(self, indices):
        lrs, wds, rescale, clip = super().fused_hyper(indices)
        # fold the bias correction into lr host-side, as eager update() does
        for i, idx in enumerate(indices):
            t = self._index_update_count[idx]
            lrs[i] *= math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        return lrs, wds, rescale, clip

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        mean, var = state
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        new_w, new_mean, new_var = nd.adam_update(
            weight, grad, mean, var, lr=lr, wd=wd, beta1=self.beta1,
            beta2=self.beta2, epsilon=self.epsilon, **self._common_kwargs())
        weight._set_data(new_w.data)
        mean._set_data(new_mean.data)
        var._set_data(new_var.data)


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference: :513)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def fused_kernel(self):
        import jax.numpy as jnp

        def make_slots(w):
            return (jnp.zeros_like(w),)

        def apply(w, g, slots, lr, wd, rescale, clip, extra):
            eps = extra[0]
            (h,) = slots
            g = g * rescale
            g = jnp.where(clip > 0, jnp.clip(g, -clip, clip), g)
            h = h + g * g
            return w - lr * (g / jnp.sqrt(h + eps) + wd * w), (h,)

        return make_slots, apply

    def fused_extra(self):
        return np.array([self.float_stable_eps], np.float32)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        history = state
        history += grad * grad
        weight += -lr * (grad / nd.sqrt(history + self.float_stable_eps) + wd * weight)


@register
class RMSProp(Optimizer):
    """RMSProp; centered=True uses Alex Graves' variant (reference: :553)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def pack_state(self, arrays):
        # create_state is a tuple even in the single-slot (uncentered) case
        return tuple(arrays)

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, weight.context),
                    zeros(weight.shape, weight.context),
                    zeros(weight.shape, weight.context))
        return (zeros(weight.shape, weight.context),)

    def fused_kernel(self):
        import jax.numpy as jnp

        centered = self.centered  # structural: decides the slot count

        def make_slots(w):
            n = 3 if centered else 1
            return tuple(jnp.zeros_like(w) for _ in range(n))

        def apply(w, g, slots, lr, wd, rescale, clip, extra):
            rho, mom, eps, cw = extra[0], extra[1], extra[2], extra[3]
            g = g * rescale
            g = jnp.where(clip > 0, jnp.clip(g, -clip, clip), g)
            g = g + wd * w
            if centered:
                n, gbar, delta = slots
                n = rho * n + (1 - rho) * jnp.square(g)
                gbar = rho * gbar + (1 - rho) * g
                delta = mom * delta - lr * g / jnp.sqrt(n - jnp.square(gbar) + eps)
                w = w + delta
                new_slots = (n, gbar, delta)
            else:
                (n,) = slots
                n = rho * n + (1 - rho) * jnp.square(g)
                w = w - lr * g / jnp.sqrt(n + eps)
                new_slots = (n,)
            w = jnp.where(cw > 0, jnp.clip(w, -cw, cw), w)
            return w, new_slots

        return make_slots, apply

    def fused_extra(self):
        cw = self.clip_weights if self.clip_weights else -1.0
        return np.array([self.gamma1, self.gamma2, self.epsilon, cw],
                        np.float32)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        kw = self._common_kwargs()
        if self.clip_weights:
            kw["clip_weights"] = self.clip_weights
        if not self.centered:
            (n,) = state
            new_w, new_n = nd.rmsprop_update(
                weight, grad, n, lr=lr, wd=wd, gamma1=self.gamma1,
                epsilon=self.epsilon, **kw)
            n._set_data(new_n.data)
        else:
            n, g, delta = state
            new_w, new_n, new_g, new_delta = nd.rmspropalex_update(
                weight, grad, n, g, delta, lr=lr, wd=wd, gamma1=self.gamma1,
                gamma2=self.gamma2, epsilon=self.epsilon, **kw)
            n._set_data(new_n.data)
            g._set_data(new_g.data)
            delta._set_data(new_delta.data)
        weight._set_data(new_w.data)


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference: :608)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        acc_g, acc_delta = state
        acc_g._set_data((self.rho * acc_g + (1 - self.rho) * grad * grad).data)
        current_delta = (nd.sqrt(acc_delta + self.epsilon) /
                         nd.sqrt(acc_g + self.epsilon)) * grad
        acc_delta._set_data(
            (self.rho * acc_delta + (1 - self.rho) * current_delta * current_delta).data)
        weight += -current_delta - wd * weight


@register
class Ftrl(Optimizer):
    """FTRL (reference: :652)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        lr = self._get_lr(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient, a_max=self.clip_gradient)
        dn, n = state
        dn += grad - (nd.sqrt(n + grad * grad) - nd.sqrt(n)) * weight / lr
        n += grad * grad
        w_np = (nd.sign(dn) * self.lamda1 - dn) / \
            ((self.beta + nd.sqrt(n)) / lr + wd) * (nd.abs(dn) > self.lamda1)
        weight._set_data(w_np.data)


@register
class Test(Optimizer):
    """Test optimizer: w += rescale_grad * grad (reference: :700)."""

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight += grad * self.rescale_grad
        state._set_data(weight.data)


create = Optimizer.create_optimizer


class Updater:
    """Closure applying an optimizer to (index, grad, weight) pairs —
    worker-side update (reference: optimizer.py:720 get_updater)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state(index, weight)
        self.optimizer.update(index, weight, grad, self.states[index])

    def update_multi(self, indices, grads, weights):
        for index, weight in zip(indices, weights):
            if index not in self.states:
                self.states[index] = self.optimizer.create_state(index, weight)
        self.optimizer.update_multi(indices, weights, grads,
                                    [self.states[i] for i in indices])

    def set_states(self, states):
        import pickle

        loaded = pickle.loads(states)
        # fused-step payloads are keyed by param NAME with numpy-tuple
        # values; translate via the optimizer's idx2name so a checkpoint
        # saved on the fused path resumes on the eager one
        name2idx = {n: i for i, n in self.optimizer.idx2name.items()}
        converted = {}
        for key, state in loaded.items():
            idx = name2idx.get(key, key) if isinstance(key, str) else key
            if isinstance(idx, str):
                # an unmapped name key would silently shadow-miss in
                # __call__ (which looks up integer indices) and restart the
                # state from zeros — losing momentum/moments on resume
                import logging

                detail = ("optimizer.idx2name is empty — was the optimizer "
                          "passed to init_optimizer as an instance?"
                          if not name2idx else
                          "known names: %s" % sorted(name2idx))
                logging.warning(
                    "optimizer state key %r has no index mapping (%s); its "
                    "saved state will not be applied", key, detail)
            if isinstance(state, tuple) and all(
                    isinstance(s, np.ndarray) for s in state):
                import jax.numpy as jnp

                state = self.optimizer.pack_state(
                    [NDArray(jnp.asarray(s)) for s in state])
            converted[idx] = state
        self.states = converted

    def get_states(self):
        import pickle

        return pickle.dumps(self.states)


def get_updater(optimizer):
    return Updater(optimizer)
