"""The faults an ``lfm2_moe`` cell's comparison has to catch, planted in the
plain reference (which then stands in the program's place) by replacing one
of its rules for the length of a ``with`` block:

* ``bias_not_in_selection``: the experts ranked by their scores alone;
* ``bias_in_weights``: the selection bias let into the picked experts'
  weights;
* ``softmax_router``: softmax probabilities for the sigmoid scores;
* ``not_normalised``: the picked experts' scores taken as their weights;
* ``conv_looks_ahead``: the convolution's window moved one row on, so that
  row ``t`` reads row ``t + 1``;
* ``no_output_gate``: the convolution's output not gated by ``C``;
* ``drop_expert``: the first held expert's output left out;
* ``half_rows``: the second half of the positions left out of the loss.
"""
import contextlib
from unittest import mock

FAULTS = ("bias_not_in_selection", "bias_in_weights", "softmax_router",
          "not_normalised", "conv_looks_ahead", "no_output_gate",
          "drop_expert", "half_rows")


@contextlib.contextmanager
def planted(fault):
    import jax
    import jax.numpy as jnp
    from benchmark.reference import lfm2_moe as family
    sound_loss, sound_lands = family.loss, family.lands_here

    def half_rows(config, ops, params, aux, batch):
        tokens, targets, weight = batch
        keep = jnp.arange(weight.shape[1]) < weight.shape[1] // 2
        return sound_loss(config, ops, params, aux,
                          (tokens, targets, weight * keep))

    def looks_ahead(z, taps):
        length = z.shape[1]
        padded = jnp.pad(z, ((0, 0), (taps - 2, 1), (0, 0)))
        return [padded[:, j:j + length] for j in range(taps)]

    replaced = {
        "bias_not_in_selection": ("selection_scores",
                                  lambda scores, bias: scores),
        "bias_in_weights": ("weight_scores",
                            lambda scores, bias: scores + bias),
        "softmax_router": ("router_scores",
                           lambda logits: jax.nn.softmax(logits, axis=-1)),
        "not_normalised": ("normalised", lambda picked: picked),
        "conv_looks_ahead": ("conv_window", looks_ahead),
        "no_output_gate": ("gated", lambda gate, c: c),
        "drop_expert": ("lands_here", lambda local, held:
                        sound_lands(local, held) & (local != 0)),
        "half_rows": ("loss", half_rows),
    }[fault]
    with mock.patch.object(family, *replaced):
        yield
