"""Plain float32 building blocks that every reference network shares.

Nothing here imports the program under test.  A network file beside this one
describes one family in terms of an ``Ops`` object: the layers' equations as
published, in straightforward ``jax.numpy``, NCHW activations and OIHW
weights.  ``Ops`` carries the two things a caller may vary: the dtype the
network computes in and the matmul precision.  The reference proper is
float32 at precision ``highest``; the control (checks/) asks for bfloat16.

Training semantics are those of the job the benchmark times, as MXNet
defines them: BatchNorm with biased batch variance, eps 1e-5 and running
statistics folded with momentum 0.9.  Three things are found by name: the
input (``example_input``) and the loss (``loss_of``) are the family's, an
image and the mean softmax cross-entropy over the batch where the family says
nothing; the optimizer is the file under ``optimizers/`` that the
configuration's ``optimizer`` key names.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BN_EPS = 1e-5
BN_MOMENTUM = 0.9
CE_EPS = 1e-12      # the program's cross-entropy metric adds it under the log


class Ops:
    """The layer equations.  ``dtype`` is what activations and weights are
    cast to inside the network; ``precision`` goes to every convolution and
    matrix product."""

    def __init__(self, dtype=jnp.float32, precision="highest"):
        self.dtype = dtype
        self.precision = precision

    def conv(self, x, w, stride=1, pad=0, groups=1, bias=None):
        y = lax.conv_general_dilated(
            x, w.astype(self.dtype), (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups, precision=self.precision)
        if bias is not None:
            y = y + bias.astype(self.dtype).reshape(1, -1, 1, 1)
        return y

    def dense(self, x, w, bias=None):
        y = jnp.matmul(x, w.astype(self.dtype).T, precision=self.precision)
        if bias is not None:
            y = y + bias.astype(self.dtype)
        return y

    def einsum(self, spec, a, b):
        """Any other product of two operands (an attention score, an expert's
        matrix), by its subscripts; no ellipsis, so that flops.py can count
        its multiply-accumulates from the shapes."""
        if "." in spec or spec.count(",") != 1:
            raise ValueError("einsum of two operands with every axis "
                             "lettered, not %r" % spec)
        return jnp.einsum(spec, a.astype(self.dtype), b.astype(self.dtype),
                          precision=self.precision)

    def batch_norm(self, x, gamma, beta, running, train):
        """Returns (y, (new running mean, new running variance))."""
        mean_r, var_r = running
        if train:
            mean = jnp.mean(x, axis=(0, 2, 3))
            var = jnp.var(x, axis=(0, 2, 3))
            new = (BN_MOMENTUM * mean_r
                   + (1 - BN_MOMENTUM) * mean.astype(mean_r.dtype),
                   BN_MOMENTUM * var_r
                   + (1 - BN_MOMENTUM) * var.astype(var_r.dtype))
        else:
            mean, var, new = mean_r.astype(x.dtype), var_r.astype(x.dtype), \
                running
        shape = (1, -1, 1, 1)
        inv = gamma.astype(x.dtype).reshape(shape) * lax.rsqrt(
            var.reshape(shape) + jnp.asarray(BN_EPS, x.dtype))
        y = (x - mean.reshape(shape)) * inv + beta.astype(x.dtype).reshape(shape)
        return y, new

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0)

    @staticmethod
    def relu6(x):
        return jnp.clip(x, 0, 6)

    @staticmethod
    def max_pool(x, window, stride, pad):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1, window, window),
            (1, 1, stride, stride), [(0, 0), (0, 0), (pad, pad), (pad, pad)])

    @staticmethod
    def global_avg_pool(x):
        return jnp.mean(x, axis=(2, 3))


def family(config):
    """The reference module of the configuration's family."""
    return importlib.import_module(
        "benchmark.reference." + config["reference"])


def optimizer(config):
    """The reference module of the configuration's optimizer."""
    return importlib.import_module(
        "benchmark.reference.optimizers." + config["optimizer"])


def example_input(config, traffic):
    """What one sample's forward pass takes after ``aux``, as
    ``ShapeDtypeStruct``s with a batch of 1: the family's
    ``example_input(config, traffic)`` (the traffic file has a sequence's
    length), else one image."""
    module = family(config)
    if hasattr(module, "example_input"):
        return tuple(module.example_input(config, traffic))
    size = config["image_size"]
    return (jax.ShapeDtypeStruct((1, 3, size, size), jnp.float32),)


def loss_of(config):
    """``loss(ops, params, aux, batch) -> (loss, new aux)`` of the
    configuration's family, ``batch`` a tuple passed whole: the family's
    ``loss(config, ops, params, aux, batch)``, else the classifier's mean
    softmax cross-entropy on ``batch = (x, y)``."""
    module = family(config)
    if hasattr(module, "loss"):
        return functools.partial(module.loss, config)
    forward = functools.partial(module.forward, config)
    return lambda ops, params, aux, batch: loss_and_logits(
        ops, forward, params, aux, *batch)


def host_norm(v):
    """The norm of a host array, summed in float64."""
    return float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))


def program_readings(config, hyper, seed, losses, opt_state, end):
    """What the comparison reads of the program, from an entry's snapshots:
    each compared step's loss, the program's optimizer state after its first
    step (``opt_state[leaf]``: the arrays in the program's order) and its
    parameters and statistics after the last compared step (``end``), all on
    the host and keyed as ``param_shapes`` names them."""
    start = xavier_init(config, seed)
    start = jax.device_get({**start[0], **start[1]})
    grad_norms = optimizer(config).first_gradient_norms(hyper, opt_state)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: host_norm(end[k] - start[k])
                             for k in end if k in start}}


def xavier_init(config, seed):
    """All parameters and BatchNorm statistics of ``config``, made on the
    default device in one jitted call from ``seed``.  Convolution and dense
    weights are Xavier-uniform (magnitude 3 over the mean of fan-in and
    fan-out, MXNet's default Xavier), biases and betas 0, gammas 1, running
    mean 0 and variance 1.  Returns (params, aux): two dicts keyed by the
    parameter names without the network's prefix.

    One draw for all the weights, cut up leaf by leaf: a draw per leaf gives
    the tracer some fifty shapes of the generator to lower, 3 to 21 s of
    every run's set-up on the chip's host (PERF.md, PR 24)."""
    shapes = family(config).param_shapes(config)
    sizes = {n: math.prod(s) for n, s in shapes.items()
             if n.endswith("_weight")}

    def make(key):
        flat = jax.random.uniform(key, (sum(sizes.values()),), jnp.float32,
                                  -1.0, 1.0)
        params, aux, at = {}, {}, 0
        for name, shape in shapes.items():
            if name.endswith("_weight"):
                fan = (shape[0] + shape[1]) * math.prod(shape[2:]) / 2.0
                leaf = flat[at:at + sizes[name]].reshape(shape)
                params[name] = leaf * jnp.float32((3.0 / fan) ** 0.5)
                at += sizes[name]
            elif name.endswith(("_gamma", "_running_var")):
                target = aux if name.endswith("_running_var") else params
                target[name] = jnp.ones(shape, jnp.float32)
            else:
                target = aux if name.endswith("_running_mean") else params
                target[name] = jnp.zeros(shape, jnp.float32)
        return params, aux

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def loss_and_logits(ops, forward, params, aux, x, y):
    logits, new_aux = forward(ops, params, aux, x.astype(ops.dtype), True)
    prob = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(prob, y[:, None].astype(jnp.int32), axis=1)
    return -jnp.mean(jnp.log(picked[:, 0] + CE_EPS)), new_aux


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_train_step(config, hyper, dtype=jnp.float32, precision="highest",
                    keep=None):
    """One jitted step of the family's network under the configuration's
    optimizer: ``step(params, state, aux, batch) -> (params, state, aux,
    loss, norms of the gradient's leaves)``.  ``hyper`` is the cell's traffic
    file, which holds the optimizer's parameters.  ``keep`` (a slice) plants
    the half-batch fault: the step sees only those rows of every array of the
    batch and takes its mean over them."""
    loss_fn, update = loss_of(config), optimizer(config).update
    ops = Ops(dtype, precision)

    def step(params, state, aux, batch):
        if keep is not None:
            batch = tuple(a[keep] for a in batch)
        (loss, new_aux), grads = jax.value_and_grad(
            lambda p: loss_fn(ops, p, aux, batch), has_aux=True)(params)
        params, state = update(hyper, params, state, grads)
        return params, state, new_aux, loss, leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_readings(config, seed, batches, hyper, state_dtype=None,
                   **variant):
    """Drive ``len(batches)`` reference steps from the seed's weights and
    return what the comparison reads: each step's loss, the norm of each
    leaf of the first gradient, and the norm of each parameter's and each
    BatchNorm statistic's change over all the steps, from the seed's float32
    weights.  ``state_dtype`` holds the weights and the optimizer's state in
    another dtype (the control without float32 master weights)."""
    params, aux = xavier_init(config, seed)
    start = {k: jnp.copy(v) for k, v in {**params, **aux}.items()}
    if state_dtype is not None:
        params = {k: v.astype(state_dtype) for k, v in params.items()}
    state = optimizer(config).init(params)
    step = make_train_step(config, hyper, **variant)
    losses, grad_norms = [], None
    for batch in batches:
        params, state, aux, loss, norms = step(
            params, state, aux, tuple(jnp.asarray(a) for a in batch))
        losses.append(loss)
        if grad_norms is None:
            grad_norms = norms
    end = {**params, **aux}
    change = leaf_norms({k: end[k].astype(jnp.float32) - start[k]
                         for k in end})
    fetched = jax.device_get((losses, grad_norms, change))
    return {"losses": [float(v) for v in fetched[0]],
            "grad_norms": {k: float(v) for k, v in fetched[1].items()},
            "change_norms": {k: float(v) for k, v in fetched[2].items()}}
