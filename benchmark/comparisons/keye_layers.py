"""Comparison ``keye_layers``: what decides ``correct`` for a training cell of
family ``keye_dsa``.

Two parts, all of whose numbers are held to a limit:

* ``train_norms``' three numbers over the compiled step that the window
  drives (the first gradient, the change of every parameter), which catch a
  state left unchanged, part of the loss left out and state or arithmetic in
  a lower precision;
* four numbers of the mechanisms the family adds, which those norms cannot
  see: the program's own attention block and expert block of its first
  decoder layer (found in ``build(config)`` by their parameters' names, run
  through ``functional_call`` as the compiled step runs them, forward and
  backward) against the reference's ``attention`` and ``moe``, at the cell's
  own size, on the same input (the normed embeddings of the first batch's
  rows under the seed's weights) and the same cotangent.

  ``index_select_gap``: the pairs that one of the program's indexer and the
  reference's (float32 at ``highest``) picked and the other did not, over the
  pairs the two picked together.  Where both pick as many (a sound run) that
  is the share of the program's picks that the reference does not share; a
  selection that picks fewer or more reads high too.  The selection is exact
  on both sides, so a sound run differs only where a score lies within a
  bfloat16 pass of its row's threshold.
  ``attn_rows_gap``: the attention block's output and its input's gradient,
  cut into groups of ``ROWS`` rows; the worst group's ``|program - reference|
  / |reference|``, **the reference given the program's own picks**, so that
  the number reads the attention and not the threshold.
  ``index_grad_gap``: the gradient of the indexer's loss in the indexer's
  three matrices (the reference again given the program's picks); the worst
  matrix's ``|program - reference| / |reference|``, or, if larger, the norm
  of that loss's gradient in the block's input over the reference's norm of
  the first matrix's: the indexer reads its input detached, so that is 0 in
  the reference and in a sound run.  A loss left out reads 1.
  ``expert_grad_gap``: each held expert's gradient (its three matrices
  together); the worst expert's ``|program - reference| / max(|reference|,
  the median expert's)``, as ``sdar_layers`` has it.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

from benchmark.comparisons import train_norms

ROWS = 64
INDEXER = ("index_q", "index_k", "index_w")


def probe_inputs(cell, seed, tokens):
    """(first layer's weights, input rows (B, L, hidden), two cotangents),
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


def _block_readings(attn, moe, weights, x, cots):
    """Forward and backward of the two blocks, as host arrays.  ``attn``
    gives (rows, the indexer's loss, the picked pairs)."""
    import jax
    rows, vjp_rows, pairs = jax.vjp(lambda w, x: attn(w, x)[::2], weights, x,
                                    has_aux=True)
    dx = vjp_rows(cots[0])[1]
    index, index_dx = jax.grad(lambda w, x: attn(w, x)[1].reshape(()),
                               (0, 1))(weights, x)
    _, vjp_moe = jax.vjp(moe, weights, x)
    dw = vjp_moe(cots[1])[0]
    return jax.device_get({
        "attn_out": rows, "attn_dx": dx, "pairs": pairs != 0,
        "index": [index["layer0_attn_%s_weight" % n] for n in INDEXER],
        "index_dx": index_dx,
        "experts": [dw["layer0_moe_%s_weight" % n]
                    for n in ("gate", "up", "down")]})


def reference_probe(cell, weights, x, cots, given=None, **ops):
    """The reference's two layers (``ops``: dtype and precision of a
    control; default float32 at ``highest``).  Its ``pairs`` are its own
    picks; with ``given`` (B, L, L) its attention and its indexer's loss use
    those in their place."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common
    family = common.family(cell.config)
    s, ops = family._sizes(cell.config), common.Ops(**ops)
    given = None if given is None else jnp.asarray(given)

    @jax.jit
    def own_pairs(w, x):
        return family.selected_pairs(s, ops, w, "layer0_", x.astype(ops.dtype))

    @jax.jit
    def block(w, x, pairs):
        rows, index_loss = family.attention(
            s, ops, w, "layer0_", x.astype(ops.dtype), True, pairs)
        return rows.astype(x.dtype), index_loss.astype(x.dtype)

    def attn(w, x):
        pairs = own_pairs(w, x)
        rows, index_loss = block(w, x, pairs if given is None else given)
        return rows, index_loss, pairs

    def moe(w, x):
        return family.moe(s, ops, w, "layer0_", x.reshape(
            -1, x.shape[-1]).astype(ops.dtype), None, True)[0].reshape(
                x.shape).astype(x.dtype)

    return _block_readings(attn, jax.jit(moe), weights, x, cots)


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
    positions = jnp.tile(jnp.arange(x.shape[1], dtype=jnp.int32), (3, 1))

    def call(name, *more):
        block = _find(net, net.prefix + "layer0_%s_" % name)
        held = block.collect_params()       # the blocks' recorded state too

        def run(w, x):
            values = {k: w.get(k[len(net.prefix):], p.data()._data)
                      for k, p in held.items()}
            return functional_call(block, values, x, *more,
                                   training=True)[0]
        return jax.jit(run)

    moe = call("moe")
    return _block_readings(call("attn", positions),
                           lambda w, x: moe(w, x)[0], weights, x, cots)


def _norm(a):
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def _gap(got, want, floor=0.0):
    gap = _norm(np.asarray(got, np.float64) - np.asarray(want, np.float64)) \
        / max(_norm(want), floor, 1e-30)
    return gap if math.isfinite(gap) else math.inf


def layer_numbers(program, reference, held_experts):
    """The four numbers, each ``(value, where)``; ``reference`` was given
    the program's pairs."""
    picked = int(program["pairs"].sum()) + int(reference["pairs"].sum())
    apart = int((program["pairs"] ^ reference["pairs"]).sum())
    select = (apart / picked if picked else math.inf,
              "%d of %d pairs" % (apart, picked))

    rows = (0.0, "")
    for name in ("attn_out", "attn_dx"):
        got, want = (np.asarray(t[name], np.float64).reshape(
            -1, t[name].shape[-1]) for t in (program, reference))
        for at in range(0, len(want), ROWS):
            rows = max(rows, (_gap(got[at:at + ROWS], want[at:at + ROWS]),
                              "%s rows %d.." % (name, at)))

    index = max([(_gap(g, w), "attn_%s_weight" % n) for n, g, w in zip(
        INDEXER, program["index"], reference["index"])] + [
            (_gap(program["index_dx"], reference["index_dx"],
                  _norm(reference["index"][0])), "the block's input")])

    def per_expert(t):
        return np.concatenate([np.asarray(m, np.float64).reshape(
            held_experts, -1) for m in t["experts"]], axis=1)
    got, want = per_expert(program), per_expert(reference)
    floor = max(float(np.median([_norm(w) for w in want])), 1e-30)
    experts = max((_gap(g, w, floor), "expert %d" % e)
                  for e, (g, w) in enumerate(zip(got, want)))
    return {"index_select_gap": select, "attn_rows_gap": rows,
            "index_grad_gap": index, "expert_grad_gap": experts}


def reference_readings(cell, seed, batches, inputs=None, given=None,
                       **variant):
    """``train_norms``' readings of the reference with the two layers' under
    ``"layers"``; ``variant`` as ``common.train_readings`` takes it (a
    control's dtype and precision reach the layers too)."""
    found = train_norms.reference_readings(cell, seed, batches, **variant)
    inputs = inputs or probe_inputs(cell, seed, batches[0][0])
    found["layers"] = reference_probe(cell, *inputs, given=given, **{
        k: v for k, v in variant.items() if k in ("dtype", "precision")})
    return found


def numbers(program, reference, cell):
    """(held, observed) as ``train_norms.numbers``, the layers' numbers among
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
        cell, seed, batches, inputs, given=layers["pairs"]), cell)
    return train_norms.judge(held, cell.limits), {
        k: v if math.isfinite(v) else 1e30 for k, (v, _) in observed.items()}
