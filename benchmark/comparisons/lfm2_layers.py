"""Comparison ``lfm2_layers``: what decides ``correct`` for a training cell of
family ``lfm2_moe``.

Two parts, all of whose numbers are held to a limit:

* ``train_norms``' three numbers over the compiled step that the window
  drives (the first gradient, the change of every parameter), which catch a
  state left unchanged, part of the loss left out and state or arithmetic in
  a lower precision;
* four numbers of the mechanisms the family adds, which those norms cannot
  see (the seed's selection biases are 0, and one expert of 8 or one row of
  a window moves a leaf's norm inside a sound run's range): the program's own
  blocks of a stage of two routed layers, an attention layer and a
  convolution layer (published layers 2 and 3 of ``layer_types``; found in
  ``build`` of that stage by their parameters' names, run through
  ``functional_call`` as the compiled step runs them, forward and backward)
  against the reference's ``attention``, ``short_conv`` and ``moe``, at the
  cell's own size, on the same input (the normed embeddings of the first
  batch's rows under the stage's seeded weights), the same cotangents and
  **a seeded selection bias of the size of the scores' spread**.

  ``conv_rows_gap``: the gated convolution's output and its input's
  gradient, cut into groups of ``ROWS`` rows; the worst group's ``|program -
  reference| / |reference|``.  A window that looks one row ahead, or a gate
  left out, is an error of every group.
  ``attn_rows_gap``: the same of the causal attention block (head size 64).
  ``route_gap``: the pairs of row and expert that one of the program's router
  and the reference's (float32 at ``highest``) picked and the other did not,
  over the pairs the two picked together.  A sound run differs only where two
  experts' biased scores lie within the two sides' rounding of each other.
  ``expert_grad_gap``: each held expert's gradient (its three matrices
  together) and the router's; the worst one's ``|program - reference| /
  max(|reference|, the median expert's)``, **the reference given the
  program's own picks**, so that the number reads the weights and the experts
  and not a tie.  A bias let into the weights, a normalisation left out or an
  expert left out reads here.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from benchmark.comparisons import train_norms
from benchmark.comparisons.keye_layers import _find, _gap, _norm

ROWS = 64
STAGE = (2, 3)      # published layers: full_attention and conv, both routed
ATTN, CONV = "layer0_", "layer1_"


def stage_config(cell):
    """The cell's configuration cut to the two probed layers."""
    deployment = dict(cell.config.get("deployment", {}), layers=list(STAGE))
    kinds = [cell.config["layer_types"][i] for i in STAGE]
    if kinds != ["full_attention", "conv"] \
            or STAGE[0] < cell.config["num_dense_layers"]:
        raise ValueError("layers %r of the configuration are %r, not a "
                         "routed attention layer and a routed convolution "
                         "layer" % (STAGE, kinds))
    return dict(cell.config, num_hidden_layers=len(STAGE),
                deployment=deployment)


def probe_inputs(cell, seed, tokens):
    """(the stage's weights, input rows (B, L, hidden), three cotangents),
    on the device, from the seed; the attention layer's selection bias is
    drawn at the spread of its router's scores over these rows."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common
    config = stage_config(cell)
    family = common.family(config)
    params, _ = common.xavier_init(config, seed)
    weights = {k: v for k, v in params.items() if k.startswith("layer")}
    x = family.rms_norm(params["embed_weight"][jnp.asarray(tokens)],
                        weights[ATTN + "operator_norm_gamma"],
                        config["norm_eps"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    logits = jnp.einsum("bld,ed->ble", x, weights[ATTN + "moe_router_weight"],
                        precision="highest")
    bias = weights[ATTN + "moe_expert_bias"]
    weights[ATTN + "moe_expert_bias"] = jnp.std(
        family.router_scores(logits)) * jax.random.normal(
            keys[3], bias.shape, bias.dtype)
    return weights, x, [jax.random.normal(k, x.shape, x.dtype)
                        for k in keys[:3]]


def _block_readings(conv, attn, moe, picks, weights, x, cots):
    """Forward and backward of the three blocks, as host arrays."""
    import jax
    out_c, vjp_c = jax.vjp(conv, weights, x)
    dx_c = vjp_c(cots[0])[1]
    out_a, vjp_a = jax.vjp(attn, weights, x)
    dx_a = vjp_a(cots[1])[1]
    _, vjp_m = jax.vjp(moe, weights, x)
    dw = vjp_m(cots[2])[0]
    return jax.device_get({
        "conv_out": out_c, "conv_dx": dx_c, "attn_out": out_a,
        "attn_dx": dx_a, "picks": picks(weights, x),
        "router": dw[ATTN + "moe_router_weight"],
        "bias": dw[ATTN + "moe_expert_bias"],
        "experts": [dw[ATTN + "moe_%s_weight" % n]
                    for n in ("gate", "up", "down")]})


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

    def conv(w, x):
        return family.short_conv(s, ops, w, CONV,
                                 x.astype(ops.dtype)).astype(x.dtype)

    def attn(w, x):
        return family.attention(s, ops, w, ATTN, x.astype(ops.dtype),
                                True).astype(x.dtype)

    def moe(w, x):
        return family.moe(s, ops, w, ATTN, rows(x), None, True,
                          given)[0].reshape(x.shape).astype(x.dtype)

    def picks(w, x):
        return family.route(s, ops, w, ATTN, rows(x))[1]

    return _block_readings(jax.jit(conv), jax.jit(attn), jax.jit(moe),
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

    experts = _find(net, net.prefix + ATTN + "moe_")

    def picks(w, x):
        return experts.route(
            mx.nd, NDArray(x), NDArray(w[ATTN + "moe_router_weight"]),
            NDArray(w[ATTN + "moe_expert_bias"]))[1]._data

    return _block_readings(
        call(CONV + "conv_", positions), call(ATTN + "attn_", positions),
        call(ATTN + "moe_"), jax.jit(picks), weights, x, cots)


def _rows_gap(program, reference, block):
    worst = (0.0, "")
    for name in (block + "_out", block + "_dx"):
        got, want = (np.asarray(t[name], np.float64).reshape(
            -1, t[name].shape[-1]) for t in (program, reference))
        for at in range(0, len(want), ROWS):
            worst = max(worst, (_gap(got[at:at + ROWS], want[at:at + ROWS]),
                                "%s rows %d.." % (name, at)))
    return worst


def _pair_set(picks, experts):
    """(rows, top_k) expert ids as booleans (rows, experts)."""
    picks = np.asarray(picks)
    found = np.zeros((picks.shape[0], experts), bool)
    found[np.arange(picks.shape[0])[:, None], picks] = True
    return found


def layer_numbers(program, reference, held_experts, experts):
    """The four numbers, each ``(value, where)``; ``reference``'s experts
    were given the program's picks, its ``picks`` are its own."""
    ours, theirs = (_pair_set(t["picks"], experts)
                    for t in (program, reference))
    picked, apart = int(ours.sum()) + int(theirs.sum()), int(
        (ours ^ theirs).sum())
    route = (apart / picked if picked else math.inf,
             "%d of %d pairs" % (apart, picked))

    def per_expert(t):
        return np.concatenate([np.asarray(m, np.float64).reshape(
            held_experts, -1) for m in t["experts"]], axis=1)
    got, want = per_expert(program), per_expert(reference)
    floor = max(float(np.median([_norm(w) for w in want])), 1e-30)
    grads = max([(_gap(g, w, floor), "expert %d" % e)
                 for e, (g, w) in enumerate(zip(got, want))]
                + [(_gap(program["router"], reference["router"]), "router"),
                   (_norm(program["bias"]) / floor, "selection bias")])
    return {"conv_rows_gap": _rows_gap(program, reference, "conv"),
            "attn_rows_gap": _rows_gap(program, reference, "attn"),
            "route_gap": route, "expert_grad_gap": grads}


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
    held.update(layer_numbers(
        program["layers"], reference["layers"], cell.config["num_experts"],
        cell.config.get("deployment", {}).get("num_experts_total",
                                              cell.config["num_experts"])))
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
