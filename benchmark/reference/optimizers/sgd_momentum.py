"""SGD with momentum as MXNet defines it: ``m = mu * m - lr * g; w = w + m``.

An optimizer's file (``reference/optimizers/<name>.py``, named by the
configuration's ``optimizer`` key) holds the reference's state and update,
how the first gradient is read back from the program's optimizer state after
one step, and what the entries ask the program for.  ``hyper`` is the cell's
traffic file: this one reads ``lr`` and ``momentum`` from it.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference.common import host_norm

MXNET = "sgd"       # the registered optimizer an entry asks the program for


def mxnet_params(hyper):
    return {"learning_rate": hyper["lr"], "momentum": hyper["momentum"],
            "wd": 0.0}


def init(params):
    """The momenta, in the dtype the weights are held in."""
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def update(hyper, params, moms, grads):
    """(params, moms) after one step.  The update is made in the dtype the
    weights are held in (float32 for the reference; the caller of a bfloat16
    control decides whether it keeps float32 master weights)."""
    lr, momentum = hyper["lr"], hyper["momentum"]
    moms = {k: (momentum * moms[k] - lr * grads[k].astype(moms[k].dtype))
            for k in params}
    params = {k: params[k] + moms[k] for k in params}
    return params, moms


def first_gradient_norms(hyper, state):
    """The norm of each leaf of the first gradient as the optimizer got it,
    from the program's state after one step (``state[leaf]``: that leaf's
    state arrays in the program's order, here the momentum alone):
    ``m = -lr * g`` after a step from zero."""
    return {k: host_norm(leaves[0]) / hyper["lr"]
            for k, leaves in state.items()}
