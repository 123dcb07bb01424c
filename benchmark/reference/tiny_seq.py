"""Family ``tiny_seq``: the smallest network that is not an image classifier.

Token ids ``[batch, seq_len]`` int32 go through an embedding, one gated
feed-forward layer with a residual, and an untied head with a bias; the loss
is the mean cross-entropy over the positions that a mask in the batch marks.
Written for ``checks/test_families.py``, which proves on it that the harness
takes integer inputs, a batch of three arrays, a masked loss, ``Ops.einsum``
and Adam in new files alone; a real sequence family is laid out the same way.

Configuration keys read here: ``vocab``, ``dim``, ``ffn``; the traffic file
has ``seq_len``.  Parameter names are those of the Gluon layers the check's
network (``checks/cells/networks/tiny_seq.py``) is built of.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp


def param_shapes(config):
    vocab, dim, ffn = config["vocab"], config["dim"], config["ffn"]
    return OrderedDict([
        ("embedding0_weight", (vocab, dim)),
        ("dense0_weight", (ffn, dim)),          # gate
        ("dense1_weight", (ffn, dim)),          # up
        ("dense2_weight", (dim, ffn)),          # down
        ("dense3_weight", (vocab, dim)),        # head
        ("dense3_bias", (vocab,)),
    ])


def example_input(config, traffic):
    return (jax.ShapeDtypeStruct((1, traffic["seq_len"]), jnp.int32),)


def forward(config, ops, params, aux, tokens, train):
    x = params["embedding0_weight"].astype(ops.dtype)[tokens]
    gate = ops.einsum("btd,fd->btf", x, params["dense0_weight"])
    up = ops.einsum("btd,fd->btf", x, params["dense1_weight"])
    x = x + ops.einsum("btf,df->btd", jax.nn.silu(gate) * up,
                       params["dense2_weight"])
    logits = ops.einsum("btd,vd->btv", x, params["dense3_weight"])
    return logits + params["dense3_bias"].astype(ops.dtype), aux


def loss(config, ops, params, aux, batch):
    """``batch = (tokens, targets, mask)``: the mean, over the positions
    where ``mask`` is 1, of the cross-entropy of ``targets``."""
    tokens, targets, mask = batch
    logits, new_aux = forward(config, ops, params, aux, tokens, True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * mask) / jnp.sum(mask), new_aux
