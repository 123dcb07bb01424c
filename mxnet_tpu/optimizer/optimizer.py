"""Optimizers.

Reference: python/mxnet/optimizer/optimizer.py — 17 optimizers dispatching to
fused C++ update kernels (src/operator/optimizer_op.cc) when available, with
``Updater`` state management (save/load at :1504) and multi-precision fp16
support via fp32 master weights (SGD at :451).

TPU-native: the fused kernels are registered ops in ops/optimizer_ops.py; an
update is one jit-cached XLA call per (shape, dtype).  Multi-precision keeps
bfloat16 weights with fp32 master copies (``multi_precision=True``) — the
natural TPU dtype policy.
"""
from __future__ import annotations

import math
import pickle
import numpy as _np

from ..ndarray import NDArray, invoke, zeros, array
from ..ndarray import ndarray as _nd_mod

__all__ = ["Optimizer", "SGD", "NAG", "Signum", "FTML", "LBSGD", "DCASGD", "SGLD",
           "Adam", "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam",
           "Test", "Updater", "get_updater", "create", "register"]


class Optimizer:
    """Base optimizer: lr/wd multipliers, per-index state, lr scheduling."""

    opt_registry = {}

    # Whether ``update_multi_precision`` is safe to capture inside a single
    # traced training step (module/compiled_step.py): the update math must be
    # expressible as a pure function of (weight, grad, state, lr, t) — no host
    # syncs (``asscalar``), no python-side state that accumulates across steps
    # beyond the step counter, no entropy drawn outside the framework key.
    # Per-step hyperparameters are threaded as traced scalars: ``lr`` comes in
    # through ``_get_lr`` (patched during the trace) and the step count
    # through ``_index_update_count`` — so ``t``-dependent math must stay
    # tracer-clean (use ``_sqrt`` below, never ``math.sqrt``, on anything
    # derived from ``t``).  Default False: an optimizer must opt in.
    trace_safe = False

    # Whether the update rule is per-element: new_weight[i] and every state
    # slot depend only on (weight[i], grad[i], state[i], scalars).  The ZeRO
    # sharded update (parallel/zero.py, fit(shard_update=True)) relies on
    # this to run the SAME update on each replica's flat 1/N slice —
    # slice -> update -> all_gather is then the identity rearrangement of
    # the full update (bitwise at fp32).  Optimizers that couple elements
    # (global norms: LARS/LAMB-style scaling, DCASGD's previous-weight
    # term) must leave this False.
    elementwise = False

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        # scalar hyperparameters
        self.lr = learning_rate
        self.wd = wd
        self.rescale_grad = rescale_grad
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        # schedule bookkeeping: num_update tracks the furthest step any
        # parameter index has reached
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            lr_scheduler.base_lr = learning_rate
        self.begin_num_update = self.num_update = begin_num_update
        self._index_update_count = {}
        # per-parameter multiplier sources, highest precedence first
        # (see _get_lr): gluon Parameter objects, explicit mult tables,
        # names resolved through idx2name
        self.param_dict = dict(param_dict) if param_dict else {}
        self.idx2name = dict(param_idx2name) if param_idx2name else {}
        self.sym_info = () if sym is None \
            else (sym.attr_dict(), sym.list_arguments())
        self.set_lr_mult({})
        self.set_wd_mult({})

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    # --- state -----------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (_np.float16,) or \
           (self.multi_precision and str(weight.dtype) == "bfloat16"):
            weight_master_copy = weight.astype("float32")
            return (self.create_state(index, weight_master_copy), weight_master_copy)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and (weight.dtype == _np.float16 or
                                     str(weight.dtype) == "bfloat16"):
            orig_state, weight32 = state
            grad32 = grad.astype("float32")
            self.update(index, weight32, grad32, orig_state)
            weight[:] = weight32.astype(weight.dtype)
        else:
            self.update(index, weight, grad, state)

    # --- lr/wd ----------------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been defined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            is_weight = n.endswith("_weight")
            if not is_weight:
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _index_mult(self, index, table, param_attr):
        """Per-parameter multiplier for ``index``: a gluon Parameter's own
        attribute wins, then an explicit table entry under the raw index,
        then one under the index's mapped name; default 1."""
        param = self.param_dict.get(index)
        if param is not None:
            return getattr(param, param_attr)
        if index in table:
            return table[index]
        return table.get(self.idx2name.get(index, index), 1.0)

    def _get_lr(self, index):
        base = self.lr if self.lr_scheduler is None \
            else self.lr_scheduler(self.num_update)
        return base * self._index_mult(index, self.lr_mult, "lr_mult")

    def _get_wd(self, index):
        return self.wd * self._index_mult(index, self.wd_mult, "wd_mult")

    def __getstate__(self):
        ret = self.__dict__.copy()
        del ret["lr_scheduler"]
        return ret

    def __setstate__(self, state):
        self.__dict__ = state
        self.lr_scheduler = None


register = Optimizer.register


def _sqrt(x):
    """Tracer-safe sqrt: python floats take math.sqrt, traced step-count
    derived scalars (compiled train step) stay in jnp."""
    if isinstance(x, (int, float)):
        return math.sqrt(x)
    import jax.numpy as jnp
    return jnp.sqrt(x)


def _common_attrs(opt, index):
    attrs = {"lr": opt._get_lr(index), "wd": opt._get_wd(index),
             "rescale_grad": opt.rescale_grad}
    if opt.clip_gradient is not None:
        attrs["clip_gradient"] = opt.clip_gradient
    return attrs


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision (reference :451)."""

    trace_safe = True
    elementwise = True

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = _common_attrs(self, index)
        if state is not None:
            attrs["momentum"] = self.momentum
            invoke("sgd_mom_update", [weight, grad, state], attrs,
                   out=[weight, state])
        else:
            invoke("sgd_update", [weight, grad], attrs, out=weight)

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and (weight.dtype == _np.float16 or
                                     str(weight.dtype) == "bfloat16"):
            mom_state, weight32 = state
            attrs = _common_attrs(self, index)
            if mom_state is not None:
                attrs["momentum"] = self.momentum
                invoke("mp_sgd_mom_update", [weight, grad, mom_state, weight32],
                       attrs, out=[weight, mom_state, weight32])
            else:
                invoke("mp_sgd_update", [weight, grad, weight32], attrs,
                       out=[weight, weight32])
        else:
            self.update(index, weight, grad, state)


@register
class NAG(SGD):
    """Nesterov accelerated SGD."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        if state is not None:
            state[:] = self.momentum * state + grad + wd * weight
            weight[:] = weight - lr * (grad + self.momentum * state)
        else:
            weight[:] = weight - lr * (grad + wd * weight)


@register
class Signum(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = _common_attrs(self, index)
        attrs["wd_lh"] = self.wd_lh
        if state is not None:
            attrs["momentum"] = self.momentum
            invoke("signum_update", [weight, grad, state], attrs, out=[weight, state])
        else:
            invoke("signsgd_update", [weight, grad], attrs, out=weight)


@register
class FTML(Optimizer):
    trace_safe = True   # t rides through ftml_update's dynamic_attrs
    elementwise = True

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = _common_attrs(self, index)
        attrs.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
                     t=self._index_update_count[index])
        d, v, z = state
        invoke("ftml_update", [weight, grad, d, v, z], attrs,
               out=[weight, d, v, z])


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise adaptive rate (reference LBSGD)."""

    def __init__(self, momentum=0.0, multi_precision=False, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32,
                 begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(momentum=momentum, multi_precision=multi_precision, **kwargs)
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.adaptive = True

    # asscalar() of weight/grad norms is a host sync — not capturable;
    # the LARS layer-wise norm also couples elements, so no sharded update
    trace_safe = False
    elementwise = False

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        if self.adaptive:
            wnorm = float(weight.norm().asscalar())  # mxflow: sync-ok(LBSGD is eager-only, trace_safe=False: norms drive host-side lr)
            gnorm = float(g.norm().asscalar())  # mxflow: sync-ok(LBSGD is eager-only, trace_safe=False: norms drive host-side lr)
            if wnorm > 0 and gnorm > 0:
                lr = lr * 0.001 * wnorm / (gnorm + wd * wnorm + 1e-9) * self.batch_scale
        if state is not None:
            state[:] = self.momentum * state - lr * (g + wd * weight)
            weight[:] = weight + state
        else:
            weight[:] = weight - lr * (g + wd * weight)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        d = g + wd * weight + self.lamda * g * g * (weight - previous_weight)
        if mom is not None:
            mom[:] = self.momentum * mom - lr * d
            update = mom
            weight_new = weight + update
        else:
            weight_new = weight - lr * d
        previous_weight[:] = weight
        weight[:] = weight_new


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        from ..ndarray import array, random as ndrandom
        # under a schedule lr changes every update: it enters the kernels
        # as an operand, not as a constant to compile against anew
        half_lr = array([lr / 2], ctx=weight.context)
        noise_std = array([math.sqrt(lr)], ctx=weight.context)
        noise = ndrandom.normal(0, 1, shape=weight.shape, dtype="float32",
                                ctx=weight.context)
        weight[:] = weight - half_lr * (g + wd * weight) + noise_std * noise


@register
class Adam(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        attrs = _common_attrs(self, index)
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        attrs["lr"] = attrs["lr"] * _sqrt(coef2) / coef1
        attrs.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
                     lazy_update=self.lazy_update)
        mean, var = state
        invoke("adam_update", [weight, grad, mean, var], attrs,
               out=[weight, mean, var])


@register
class AdaGrad(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        # history accumulates the rescaled gradient only; wd applies as a
        # direct decay term outside it (reference optimizer.py AdaGrad.update)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        state[:] = state + g * g
        weight[:] = weight - lr * (
            g / ((state + self.float_stable_eps) ** 0.5) + wd * weight)


@register
class RMSProp(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8,
                 centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                    zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                    zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = _common_attrs(self, index)
        attrs.update(gamma1=self.gamma1, epsilon=self.epsilon)
        if self.clip_weights:
            attrs["clip_weights"] = self.clip_weights
        if not self.centered:
            (n,) = state
            invoke("rmsprop_update", [weight, grad, n], attrs, out=[weight, n])
        else:
            n, g, delta = state
            attrs["gamma2"] = self.gamma2
            invoke("rmspropalex_update", [weight, grad, n, g, delta], attrs,
                   out=[weight, n, g, delta])


@register
class AdaDelta(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1.0 - self.rho) * g * g
        current_delta = ((acc_delta + self.epsilon) ** 0.5
                         / (acc_g + self.epsilon) ** 0.5) * g
        acc_delta[:] = self.rho * acc_delta + (1.0 - self.rho) * current_delta * current_delta
        weight[:] = weight - current_delta - wd * weight


@register
class Ftrl(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = _common_attrs(self, index)
        attrs.update(lamda1=self.lamda1, beta=self.beta)
        z, n = state
        invoke("ftrl_update", [weight, grad, z, n], attrs, out=[weight, z, n])


@register
class Adamax(Optimizer):
    trace_safe = True
    elementwise = True

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        lr /= (1.0 - self.beta1 ** t)
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        m_t, u_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * g
        from .. import ndarray as ndmod
        u_t[:] = ndmod.maximum(self.beta2 * u_t, g.abs())
        weight[:] = weight - lr * m_t / u_t


@register
class Nadam(Optimizer):
    # self.m_schedule is a host-side recurrence over steps with no closed
    # form in t — it cannot be threaded through a fixed trace
    trace_safe = False

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)),
                zeros(weight.shape, ctx=weight.context, dtype=str(weight.dtype)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * g
        v_t[:] = self.beta2 * v_t + (1.0 - self.beta2) * g * g
        grad_prime = g / (1.0 - self.m_schedule)
        m_t_prime = m_t / (1.0 - m_schedule_next)
        v_t_prime = v_t / (1.0 - self.beta2 ** t)
        m_t_bar = ((1.0 - momentum_t) * grad_prime + momentum_t_1 * m_t_prime)
        weight[:] = weight - lr * m_t_bar / ((v_t_prime ** 0.5) + self.epsilon)


@register
class Test(Optimizer):
    trace_safe = True
    elementwise = True

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.context)

    def update(self, index, weight, grad, state):
        weight[:] = weight + grad * self.rescale_grad
        state[:] = weight


create = Optimizer.create_optimizer


class Updater:
    """Apply optimizer to (index, grad, weight) with per-index state.

    Reference: optimizer.py:1504 ``Updater`` incl. get/set_states used by
    Module.save_optimizer_states."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        elif not self.states_synced.get(index, True):
            self.states[index] = self._to_nd(self.states[index], weight.context)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad, self.states[index])

    @staticmethod
    def _to_nd(s, ctx):
        if isinstance(s, _np.ndarray):
            return array(s, ctx=ctx)
        if isinstance(s, (list, tuple)):
            return type(s)(Updater._to_nd(x, ctx) for x in s)
        return s

    def set_states(self, states):
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, opt_dict = states
            self.optimizer.__dict__.update(opt_dict)
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        def to_np(s):
            if isinstance(s, NDArray):
                return s.asnumpy()  # mxflow: sync-ok(checkpoint serialization: optimizer state dumps to host)
            if isinstance(s, (list, tuple)):
                return type(s)(to_np(x) for x in s)
            return s
        states = {k: to_np(v) for k, v in self.states.items()}
        return pickle.dumps((states, self.optimizer.__dict__.copy())
                            if dump_optimizer else states)


def get_updater(optimizer):
    return Updater(optimizer)
