"""Comparison ``sdar_layers``: what decides ``correct`` for a block-diffusion
cell of family ``sdar_moe``.

Two parts, all of whose numbers are held to a limit:

* ``train_norms``' three numbers over the compiled step that the window
  drives (the first gradient, the change of every parameter), which catch a
  state left unchanged, part of the loss left out and state or arithmetic in
  a lower precision;
* two numbers of the two mechanisms the family adds, which those norms cannot
  see (one expert of 16, or 4 more keys among some 2,000, move a leaf's norm
  inside a sound run's range): the program's own attention block and expert
  block of its first decoder layer (found in ``build(config)`` by their
  parameters' names, run through ``functional_call`` as the compiled step
  runs them, forward and backward) against the reference's ``attention`` and
  ``moe``, at the cell's own size, on the same input (the normed embeddings of
  the first batch's rows under the seed's weights) and the same cotangent.

  ``attn_rows_gap``: the attention block's output and its input's gradient,
  cut into groups of ``ROWS`` rows; the worst group's ``|program - reference|
  / |reference|``.  A mask that shows a noised row its own clean block, or a
  tile wrongly skipped, is an error of the rows it touches and not of the
  whole: the first group's rows see 4 to 64 keys, and 4 keys more are a fifth
  of its norm.
  ``expert_grad_gap``: each held expert's gradient (its three matrices
  together); the worst expert's ``|program - reference| / max(|reference|,
  the median expert's)``.  An expert whose output is left out, or whose rows
  went elsewhere, reads 1.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from benchmark.comparisons import train_norms

ROWS = 64


def probe_inputs(cell, seed, tokens):
    """(first layer's weights, input rows (B, 2L, hidden), two cotangents),
    on the device, from the seed."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common
    family = common.family(cell.config)
    params, _ = common.xavier_init(cell.config, seed)
    weights = {k: v for k, v in params.items() if k.startswith("layer0_")}
    x = family.rms_norm(params["embed_weight"][jnp.asarray(tokens)],
                        weights["layer0_attn_norm_gamma"],
                        cell.config["rms_norm_eps"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 2)
    return weights, x, [jax.random.normal(k, x.shape, x.dtype) for k in keys]


def _readings(attn, moe, weights, x, cots):
    """Forward and backward of the two blocks, as host arrays."""
    import jax
    out_a, vjp_a = jax.vjp(attn, weights, x)
    dx_a = vjp_a(cots[0])[1]
    _, vjp_m = jax.vjp(moe, weights, x)
    dw = vjp_m(cots[1])[0]
    return jax.device_get({
        "attn_out": out_a, "attn_dx": dx_a,
        "experts": [dw["layer0_moe_%s_weight" % n]
                    for n in ("gate", "up", "down")]})


def reference_probe(cell, weights, x, cots, **ops):
    """The reference's two layers (``ops``: dtype and precision of a
    control; default float32 at ``highest``)."""
    import jax
    from benchmark.reference import common
    family = common.family(cell.config)
    s, ops = family._sizes(cell.config), common.Ops(**ops)

    def attn(w, x):
        return family.attention(s, ops, w, "layer0_", x.astype(ops.dtype),
                                True).astype(x.dtype)

    def moe(w, x):
        return family.moe(s, ops, w, "layer0_", x.reshape(
            -1, x.shape[-1]).astype(ops.dtype), None, True)[0].reshape(
                x.shape).astype(x.dtype)

    return _readings(jax.jit(attn), jax.jit(moe), weights, x, cots)


def _find(block, prefix):
    if block.prefix == prefix:
        return block
    for child in block._children.values():
        found = _find(child, prefix)
        if found is not None:
            return found
    return None


def program_probe(cell, weights, x, cots):
    """The program's two blocks of its first decoder layer."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functional_call
    network = importlib.import_module(cell.config["network"])
    net = network.build(dict(cell.config, num_hidden_layers=1))
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    length = x.shape[1] // 2
    positions = jnp.tile(jnp.arange(length, dtype=jnp.int32), 2)

    def call(name, *more):
        block = _find(net, net.prefix + "layer0_%s_" % name)
        held = block.collect_params()       # the expert block's load too

        def run(w, x):
            values = {k: w.get(k[len(net.prefix):], p.data()._data)
                      for k, p in held.items()}
            return functional_call(block, values, x, *more,
                                   training=True)[0][0]
        return jax.jit(run)

    return _readings(call("attn", positions), call("moe"), weights, x, cots)


def _norm(a):
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def layer_numbers(program, reference, held_experts):
    """The two numbers, each ``(value, where)``."""
    worst = (0.0, "")
    for name in ("attn_out", "attn_dx"):
        got, want = (np.asarray(t[name], np.float64).reshape(
            -1, t[name].shape[-1]) for t in (program, reference))
        for at in range(0, len(want), ROWS):
            gap = _norm(got[at:at + ROWS] - want[at:at + ROWS]) / max(
                _norm(want[at:at + ROWS]), 1e-30)
            if not math.isfinite(gap):
                gap = math.inf
            worst = max(worst, (gap, "%s rows %d.." % (name, at)))

    def per_expert(t):
        return np.concatenate([np.asarray(m, np.float64).reshape(
            held_experts, -1) for m in t["experts"]], axis=1)
    got, want = per_expert(program), per_expert(reference)
    norms = [_norm(w) for w in want]
    floor = max(float(np.median(norms)), 1e-30)
    gaps = [(_norm(g - w) / max(n, floor), "expert %d" % e)
            for e, (g, w, n) in enumerate(zip(got, want, norms))]
    gaps = [(g if math.isfinite(g) else math.inf, e) for g, e in gaps]
    return {"attn_rows_gap": worst, "expert_grad_gap": max(gaps)}


def reference_readings(cell, seed, batches, inputs=None, **variant):
    """``train_norms``' readings of the reference with the two layers' under
    ``"layers"``; ``variant`` as ``common.train_readings`` takes it (a
    control's dtype and precision reach the layers too)."""
    found = train_norms.reference_readings(cell, seed, batches, **variant)
    inputs = inputs or probe_inputs(cell, seed, batches[0][0])
    found["layers"] = reference_probe(cell, *inputs, **{
        k: v for k, v in variant.items() if k in ("dtype", "precision")})
    return found


def numbers(program, reference, cell):
    """(held, observed) as ``train_norms.numbers``, the two layers' numbers
    among the held."""
    held, observed = train_norms.numbers(program, reference)
    held.update(layer_numbers(program["layers"], reference["layers"],
                              cell.config["num_experts"]))
    return held, observed


def compare(cell, seed, program, run):
    batches = run.first_batches()
    inputs = probe_inputs(cell, seed, batches[0][0])
    program = dict(program, layers=program_probe(cell, *inputs))
    held, observed = numbers(
        program, reference_readings(cell, seed, batches, inputs), cell)
    return train_norms.judge(held, cell.limits), {
        k: v if math.isfinite(v) else 1e30 for k, (v, _) in observed.items()}
