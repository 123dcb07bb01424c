"""Adam as MXNet defines it: the bias corrections folded into the step size,
``lr_t = lr * sqrt(1 - beta2**t) / (1 - beta1**t)``, then
``m = beta1 * m + (1 - beta1) * g; v = beta2 * v + (1 - beta2) * g * g;
w = w - lr_t * m / (sqrt(v) + epsilon)``, epsilon outside the root.

``hyper`` (the cell's traffic file) gives ``lr``, ``beta1``, ``beta2`` and
``epsilon``.  See sgd_momentum.py for what an optimizer's file holds.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference.common import host_norm

MXNET = "adam"      # the registered optimizer an entry asks the program for


def mxnet_params(hyper):
    return {"learning_rate": hyper["lr"], "beta1": hyper["beta1"],
            "beta2": hyper["beta2"], "epsilon": hyper["epsilon"], "wd": 0.0}


def init(params):
    """The step count and both moments, in the dtype the weights are held
    in."""
    return {"t": jnp.zeros((), jnp.float32),
            "mean": {k: jnp.zeros_like(v) for k, v in params.items()},
            "var": {k: jnp.zeros_like(v) for k, v in params.items()}}


def update(hyper, params, state, grads):
    beta1, beta2 = hyper["beta1"], hyper["beta2"]
    t = state["t"] + 1
    lr_t = hyper["lr"] * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    mean, var, new = {}, {}, {}
    for k in params:
        g = grads[k].astype(state["mean"][k].dtype)
        mean[k] = beta1 * state["mean"][k] + (1 - beta1) * g
        var[k] = beta2 * state["var"][k] + (1 - beta2) * g * g
        step = lr_t * mean[k] / (jnp.sqrt(var[k]) + hyper["epsilon"])
        new[k] = params[k] - step.astype(params[k].dtype)
    return new, {"t": t, "mean": mean, "var": var}


def first_gradient_norms(hyper, state):
    """From the program's state after one step (``state[leaf]``: the mean,
    then the variance): ``m = (1 - beta1) * g`` after a step from zero."""
    return {k: host_norm(leaves[0]) / (1 - hyper["beta1"])
            for k, leaves in state.items()}
