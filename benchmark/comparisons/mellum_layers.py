"""Comparison ``mellum_layers``: what decides ``correct`` for a training cell
of family ``mellum_moe``.

Two parts, all of whose numbers are held to a limit:

* ``train_norms``' three numbers over the compiled step that the window
  drives (the first gradient, the change of every parameter), which catch a
  state left unchanged, part of the loss left out and state or arithmetic in
  a lower precision;
* three numbers of the mechanisms the family adds, which those norms cannot
  see (a window's edge, a rotary form or one expert of 8 moves a leaf's norm
  inside a sound run's range): the program's own blocks of a stage of the
  first sliding layer and the full layer (published layers 0 and 3 of
  ``layer_types``; found in ``build`` of that stage by their parameters'
  names, run through ``functional_call`` as the compiled step runs them,
  forward and backward) against the reference's ``attention`` and ``moe``,
  at the cell's own size, on the same input (the normed embeddings of the
  first batch's rows under the stage's seeded weights) and the same
  cotangents.

  ``window_rows_gap``: the sliding layer's attention block's output and its
  input's gradient, cut into groups of ``ROWS`` rows; the worst group's
  ``|program - reference| / |reference|``.  A window left out or widened is
  an error of every group past the window's first.
  ``attn_rows_gap``: the same of the full layer's, over all rows, so that
  the rows past YaRN's original context are among them.  YaRN, its
  attention factor, or the two layers' rotary forms swapped, read here.
  ``expert_grad_gap``: each held expert's gradient (its three matrices
  together) and the router's, of the full layer's expert block; the worst
  one's ``|program - reference| / max(|reference|, the median expert's)``,
  **the reference given the program's own picks**, so that the number reads
  the weights and the experts and not a tie.  An expert left out reads
  here.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from benchmark.comparisons import train_norms
from benchmark.comparisons.keye_layers import _find, _gap, _norm
from benchmark.comparisons.lfm2_layers import _rows_gap

STAGE = (0, 3)      # published layers: the first sliding one, the full one
WINDOW, FULL = "layer0_", "layer1_"


def stage_config(cell):
    """The cell's configuration cut to the two probed layers."""
    deployment = dict(cell.config.get("deployment", {}), layers=list(STAGE))
    kinds = [cell.config["layer_types"][i] for i in STAGE]
    if kinds != ["sliding_attention", "full_attention"]:
        raise ValueError("layers %r of the configuration are %r, not a "
                         "sliding and a full attention layer" % (STAGE, kinds))
    return dict(cell.config, num_hidden_layers=len(STAGE),
                deployment=deployment)


def probe_inputs(cell, seed, tokens):
    """(the stage's weights, input rows (B, L, hidden), three cotangents),
    on the device, from the seed."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common
    config = stage_config(cell)
    family = common.family(config)
    params, _ = common.xavier_init(config, seed)
    weights = {k: v for k, v in params.items() if k.startswith("layer")}
    x = family.rms_norm(params["embed_weight"][jnp.asarray(tokens)],
                        weights[WINDOW + "operator_norm_gamma"],
                        config["rms_norm_eps"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    return weights, x, [jax.random.normal(k, x.shape, x.dtype) for k in keys]


def _block_readings(window, full, moe, picks, weights, x, cots):
    """Forward and backward of the three blocks, as host arrays."""
    import jax
    found = {}
    for name, block, cot in (("window", window, cots[0]),
                             ("attn", full, cots[1])):
        out, vjp = jax.vjp(block, weights, x)
        found[name + "_out"], found[name + "_dx"] = out, vjp(cot)[1]
    _, vjp_m = jax.vjp(moe, weights, x)
    dw = vjp_m(cots[2])[0]
    found.update(picks=picks(weights, x),
                 router=dw[FULL + "moe_router_weight"],
                 experts=[dw[FULL + "moe_%s_weight" % n]
                          for n in ("gate", "up", "down")])
    return jax.device_get(found)


def reference_probe(cell, weights, x, cots, given=None, **ops):
    """The reference's three blocks (``ops``: dtype and precision of a
    control; default float32 at ``highest``).  Its ``picks`` are its own;
    with ``given`` (rows, top_k) its experts use those in their place."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common
    config = stage_config(cell)
    family = common.family(config)
    s, ops = family._sizes(config), common.Ops(**ops)
    given = None if given is None else jnp.asarray(given)

    def rows(x):
        return x.reshape(-1, x.shape[-1]).astype(ops.dtype)

    def attn(prefix, kind):
        return jax.jit(lambda w, x: family.attention(
            s, ops, w, prefix, kind, x.astype(ops.dtype),
            True).astype(x.dtype))

    def moe(w, x):
        return family.moe(s, ops, w, FULL, rows(x), None, True,
                          given)[0].reshape(x.shape).astype(x.dtype)

    def picks(w, x):
        return family.route(s, ops, w, FULL, rows(x))[1]

    return _block_readings(attn(WINDOW, "sliding_attention"),
                           attn(FULL, "full_attention"), jax.jit(moe),
                           jax.jit(picks), weights, x, cots)


def program_probe(cell, weights, x, cots):
    """The program's three blocks of the stage, and its router's picks."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functional_call
    from mxnet_tpu.ndarray import NDArray
    network = importlib.import_module(cell.config["network"])
    net = network.build(stage_config(cell))
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def call(name, *more):
        block = _find(net, net.prefix + name)
        held = block.collect_params()       # the expert block's load too

        def run(w, x):
            values = {k: w.get(k[len(net.prefix):], p.data()._data)
                      for k, p in held.items()}
            return functional_call(block, values, x, *more,
                                   training=True)[0][0]
        return jax.jit(run)

    experts = _find(net, net.prefix + FULL + "moe_")

    def picks(w, x):
        return experts.route(
            mx.nd, NDArray(x), NDArray(w[FULL + "moe_router_weight"]))[1]._data

    return _block_readings(
        call(WINDOW + "attn_", positions), call(FULL + "attn_", positions),
        call(FULL + "moe_"), jax.jit(picks), weights, x, cots)


def layer_numbers(program, reference, held_experts):
    """The three numbers, each ``(value, where)``; ``reference``'s experts
    were given the program's picks."""
    def per_expert(t):
        return np.concatenate([np.asarray(m, np.float64).reshape(
            held_experts, -1) for m in t["experts"]], axis=1)
    got, want = per_expert(program), per_expert(reference)
    floor = max(float(np.median([_norm(w) for w in want])), 1e-30)
    grads = max([(_gap(g, w, floor), "expert %d" % e)
                 for e, (g, w) in enumerate(zip(got, want))]
                + [(_gap(program["router"], reference["router"]), "router")])
    return {"window_rows_gap": _rows_gap(program, reference, "window"),
            "attn_rows_gap": _rows_gap(program, reference, "attn"),
            "expert_grad_gap": grads}


def reference_readings(cell, seed, batches, inputs=None, given=None,
                       **variant):
    """``train_norms``' readings of the reference with the blocks' under
    ``"layers"``; ``variant`` as ``common.train_readings`` takes it (a
    control's dtype and precision reach the blocks too)."""
    found = train_norms.reference_readings(cell, seed, batches, **variant)
    inputs = inputs or probe_inputs(cell, seed, batches[0][0])
    found["layers"] = reference_probe(cell, *inputs, given=given, **{
        k: v for k, v in variant.items() if k in ("dtype", "precision")})
    return found


def numbers(program, reference, cell):
    """(held, observed) as ``train_norms.numbers``, the blocks' numbers among
    the held."""
    held, observed = train_norms.numbers(program, reference)
    held.update(layer_numbers(program["layers"], reference["layers"],
                              cell.config["num_experts"]))
    return held, observed


def compare(cell, seed, program, run):
    batches = run.first_batches()
    inputs = probe_inputs(cell, seed, batches[0][0])
    layers = program_probe(cell, *inputs)
    program = dict(program, layers=layers)
    held, observed = numbers(program, reference_readings(
        cell, seed, batches, inputs, given=layers["picks"]), cell)
    return train_norms.judge(held, cell.limits), {
        k: v if math.isfinite(v) else 1e30 for k, (v, _) in observed.items()}
