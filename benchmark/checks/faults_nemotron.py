"""The faults a ``nemotron_h`` cell's comparison has to catch, planted in the
plain reference (which then stands in the program's place) by replacing one
of its rules for the length of a ``with`` block:

* ``no_carry``: the state not carried from chunk to chunk (each chunk starts
  from 0);
* ``no_skip``: the skip ``D`` left out;
* ``no_gate``: the gate ``silu(z)`` left out of the norm;
* ``one_group``: the gated norm's RMS over one group instead of ``n_groups``;
* ``conv_ahead``: a convolution whose row ``t`` reads row ``t + 1``;
* ``rotary``: rotary applied to the attention's queries and keys anyway, at
  the configuration's ``rope_theta`` over the whole head;
* ``qk_norm``: an RMS norm (weight 1) over each head of the queries and keys
  applied anyway;
* ``relu``: the experts' relu in place of relu²;
* ``no_scale``: the routed weights' factor (2.5) left out;
* ``drop_expert``: the output of the held expert that most pairs land on
  left out;
* ``no_shared``: the shared expert left out;
* ``half_rows``: the second half of the positions left out of the loss.
"""
import contextlib
from unittest import mock

FAULTS = ("no_carry", "no_skip", "no_gate", "one_group", "conv_ahead",
          "rotary", "qk_norm", "relu", "no_scale", "drop_expert", "no_shared",
          "half_rows")


@contextlib.contextmanager
def planted(fault):
    import jax
    import jax.numpy as jnp
    from benchmark.reference import nemotron_h as family
    from benchmark.reference.mellum_moe import rms_norm, rotary
    sound_loss, sound_lands = family.loss, family.lands_here

    def half_rows(config, ops, params, aux, batch):
        tokens, targets, weight = batch
        keep = jnp.arange(weight.shape[1]) < weight.shape[1] // 2
        return sound_loss(config, ops, params, aux,
                          (tokens, targets, weight * keep))

    def rotated(s, q, k):
        rope = {"rope_theta": s["rope_theta"]}
        return tuple(rotary(t, jnp.arange(t.shape[2]), rope) for t in (q, k))

    def normed(s, q, k):
        return tuple(rms_norm(t, jnp.ones(t.shape[-1], t.dtype), s["eps"])
                     for t in (q, k))

    def dropped(local, held):
        """The held expert that most pairs land on, left out: at the cell's
        size a seed's router may send no row to a given one."""
        here = sound_lands(local, held)
        busiest = jnp.argmax(jnp.sum(
            (local[..., None] == jnp.arange(held)) & here[..., None],
            axis=tuple(range(local.ndim))))
        return here & (local != busiest)

    replaced = {
        "no_carry": ("carry_of", jnp.zeros_like),
        "no_skip": ("skip_of", jnp.zeros_like),
        "no_gate": ("gate_of", jnp.ones_like),
        "one_group": ("norm_groups", lambda groups: 1),
        "conv_ahead": ("conv_front", lambda taps: taps - 2),
        "rotary": ("query_key", rotated),
        "qk_norm": ("query_key", normed),
        "relu": ("activation", jax.nn.relu),
        "no_scale": ("routed_scale", lambda scale: 1.0),
        "drop_expert": ("lands_here", dropped),
        "no_shared": ("shared_of", jnp.zeros_like),
        "half_rows": ("loss", half_rows),
    }[fault]
    with mock.patch.object(family, *replaced):
        yield
