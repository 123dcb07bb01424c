"""Comparison ``nemotron_layers``: what decides ``correct`` for a training cell
of family ``nemotron_h``.

Two parts, all of whose numbers are held to a limit:

* ``train_norms``' three numbers over the compiled step that the window
  drives (the first gradient, the change of every parameter), which catch a
  state left unchanged, part of the loss left out and state or arithmetic in
  a lower precision;
* three numbers of the mechanisms the family adds, which those norms cannot
  see (a chunk's carry, the skip, the gate, the norm's groups, one expert of
  8 or the relu²'s square move a leaf's norm inside a sound run's range):
  the program's own blocks of a stage of a Mamba-2 layer, a routed layer and
  the attention layer (published layers 0, 1 and 5 of
  ``hybrid_override_pattern``; found in ``build`` of that stage by their
  parameters' names, run through ``functional_call`` as the compiled step
  runs them, forward and backward) against the reference's ``mamba``,
  ``moe`` and ``attention``, at the cell's own size, on the same input (the
  normed embeddings of the first batch's rows under the stage's seeded
  weights) and the same cotangents.

  The seed's weights leave the Mamba-2 layer's ``A_log``, ``dt_bias`` and
  ``D`` at 0: ``A`` = -1 and ``dt`` = 0.69, so a chunk of 128 rows decays by
  e^-88 and a state not carried from chunk to chunk would read as sound.
  For the probe they are drawn from the seed by the published
  initialisation instead: ``A`` uniform on [1, 16], ``dt`` log-uniform on
  [1e-3, 1e-1] floored at 1e-4 (``dt_bias`` its inverse softplus), ``D`` 1;
  and the convolution's taps and bias as a depthwise convolution of 4 taps
  is initialised (uniform on +-1/2), where Xavier's fan over 6,144 channels
  makes them 16 times smaller and the scan's rows a sliver of the skip's.
  The routed layer's selection bias is drawn at a tenth of the spread of
  its router's scores over these rows (``BIAS_SPREAD``): enough to move the
  picks, where the whole spread sends most rows to the few experts drawn
  highest and leaves held experts with no pair (5 of the 8, at the cell's
  size, on one seed), whose faults no number can then read.

  ``ssm_rows_gap``: the Mamba-2 block's output and its input's gradient, cut
  into groups of ``ROWS`` rows; the worst group's ``|program - reference| /
  |reference|``.  A state not carried, the skip or the gate left out, the
  norm over one group, or a convolution that sees the next row is an error
  of every group past the first chunk (or of every group).
  ``attn_rows_gap``: the same of the attention block, which applies no
  rotary and no norm of q or k: either applied anyway reads here.
  ``expert_grad_gap``: each held expert's gradient (its two matrices
  together), the router's and the shared expert's (its two together); the
  worst one's ``|program - reference| / max(|reference|, the median
  expert's)``, or the selection bias's gradient (0) over the median expert's,
  **the reference given the program's own picks**, so that the number reads
  the weights and the experts and not a tie.  relu in place of relu², the
  routed scale left out, an expert or the shared expert left out read here.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from benchmark.comparisons import train_norms
from benchmark.comparisons.keye_layers import _find, _gap, _norm
from benchmark.comparisons.lfm2_layers import _rows_gap

STAGE = (0, 1, 5)   # published layers: a Mamba-2, a routed and attention
SSM, MOE, ATTN = "layer0_", "layer1_", "layer2_"
TAPS_BOUND = 0.5    # a depthwise convolution of 4 taps: 1 / sqrt(fan-in)
BIAS_SPREAD = 0.1   # the selection bias, over the spread of the scores


def stage_config(cell):
    """The cell's configuration cut to the three probed layers."""
    deployment = dict(cell.config.get("deployment", {}), layers=list(STAGE))
    kinds = [cell.config["hybrid_override_pattern"][i] for i in STAGE]
    if kinds != ["M", "E", "*"]:
        raise ValueError("layers %r of the configuration are %r, not a "
                         "Mamba-2, a routed and an attention layer"
                         % (STAGE, kinds))
    return dict(cell.config, num_hidden_layers=len(STAGE),
                deployment=deployment)


def probe_inputs(cell, seed, tokens):
    """(the stage's weights, input rows (B, L, hidden), three cotangents),
    on the device, from the seed, the Mamba-2 layer's decays, steps, skip
    and convolution and the routed layer's selection bias drawn as the
    module's docstring says."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common
    config = stage_config(cell)
    family = common.family(config)
    params, _ = common.xavier_init(config, seed)
    weights = {k: v for k, v in params.items() if k.startswith("layer")}
    x = family.rms_norm(params["embed_weight"][jnp.asarray(tokens)],
                        weights[SSM + "norm_gamma"],
                        config["layer_norm_epsilon"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 9)
    p = SSM + "ssm_"
    heads = weights[p + "A_log"].shape
    weights[p + "A_log"] = jnp.log(jax.random.uniform(
        keys[3], heads, jnp.float32, 1.0, 16.0))
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        keys[4], heads, jnp.float32, math.log(1e-3), math.log(1e-1))), 1e-4)
    weights[p + "dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    weights[p + "D"] = jnp.ones(heads, jnp.float32)
    for key, name in ((keys[5], "conv_weight"), (keys[6], "conv_bias")):
        weights[p + name] = jax.random.uniform(
            key, weights[p + name].shape, jnp.float32, -TAPS_BOUND,
            TAPS_BOUND)
    logits = jnp.einsum("bld,ed->ble", x, weights[MOE + "moe_router_weight"],
                        precision="highest")
    bias = weights[MOE + "moe_expert_bias"]
    weights[MOE + "moe_expert_bias"] = BIAS_SPREAD \
        * jnp.std(jax.nn.sigmoid(logits)) \
        * jax.random.normal(keys[7], bias.shape, bias.dtype)
    return weights, x, [jax.random.normal(k, x.shape, x.dtype)
                        for k in keys[:3]]


def _block_readings(ssm, attn, moe, picks, weights, x, cots):
    """Forward and backward of the three blocks, as host arrays."""
    import jax
    found = {}
    for name, block, cot in (("ssm", ssm, cots[0]), ("attn", attn, cots[1])):
        out, vjp = jax.vjp(block, weights, x)
        found[name + "_out"], found[name + "_dx"] = out, vjp(cot)[1]
    _, vjp_m = jax.vjp(moe, weights, x)
    dw = vjp_m(cots[2])[0]
    found.update(picks=picks(weights, x),
                 router=dw[MOE + "moe_router_weight"],
                 bias=dw[MOE + "moe_expert_bias"],
                 experts=[dw[MOE + "moe_%s_weight" % n]
                          for n in ("up", "down")],
                 shared=[dw[MOE + "moe_shared_%s_weight" % n]
                         for n in ("up", "down")])
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

    def ssm(w, x):
        return family.mamba(s, ops, w, SSM, x.astype(ops.dtype),
                            True).astype(x.dtype)

    def attn(w, x):
        return family.attention(s, ops, w, ATTN, x.astype(ops.dtype),
                                True).astype(x.dtype)

    def moe(w, x):
        return family.moe(s, ops, w, MOE, rows(x), None, True,
                          given)[0].reshape(x.shape).astype(x.dtype)

    def picks(w, x):
        return family.route(s, ops, w, MOE, rows(x))[1]

    return _block_readings(jax.jit(ssm), jax.jit(attn), jax.jit(moe),
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

    experts = _find(net, net.prefix + MOE + "moe_")

    def picks(w, x):
        return experts.route(
            mx.nd, NDArray(x), NDArray(w[MOE + "moe_router_weight"]),
            NDArray(w[MOE + "moe_expert_bias"]))[1]._data

    return _block_readings(
        call(SSM + "ssm_", positions), call(ATTN + "attn_", positions),
        call(MOE + "moe_"), jax.jit(picks), weights, x, cots)


def layer_numbers(program, reference, held_experts):
    """The three numbers, each ``(value, where)``; ``reference``'s experts
    were given the program's picks."""
    def per_expert(t):
        return np.concatenate([np.asarray(m, np.float64).reshape(
            held_experts, -1) for m in t["experts"]], axis=1)

    def both(t):
        return np.concatenate([np.asarray(m, np.float64).reshape(-1)
                               for m in t["shared"]])
    got, want = per_expert(program), per_expert(reference)
    floor = max(float(np.median([_norm(w) for w in want])), 1e-30)
    grads = max([(_gap(g, w, floor), "expert %d" % e)
                 for e, (g, w) in enumerate(zip(got, want))]
                + [(_gap(program["router"], reference["router"]), "router"),
                   (_gap(both(program), both(reference)), "shared expert"),
                   (_norm(program["bias"]) / floor, "selection bias")])
    return {"ssm_rows_gap": _rows_gap(program, reference, "ssm"),
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
                              cell.config["n_routed_experts"]))
    return held, observed


def compare(cell, seed, program, run):
    """The reference's steps first, with nothing of the probes on the
    device: at the cell's size its weights, Adam's state and one step's
    temporaries take 12.3 GB of the chip's 16 (the v5e compile)."""
    batches = run.first_batches()
    steps = train_norms.reference_readings(cell, seed, batches)
    inputs = probe_inputs(cell, seed, batches[0][0])
    layers = program_probe(cell, *inputs)
    program = dict(program, layers=layers)
    held, observed = numbers(program, dict(steps, layers=reference_probe(
        cell, *inputs, given=layers["picks"])), cell)
    return train_norms.judge(held, cell.limits), {
        k: v if math.isfinite(v) else 1e30 for k, (v, _) in observed.items()}
