"""The faults a ``mellum_moe`` cell's comparison has to catch, planted in the
plain reference (which then stands in the program's place) by replacing one
of its rules for the length of a ``with`` block:

* ``no_window``: a sliding layer causal over every earlier key;
* ``window_doubled``: a sliding layer's window twice as wide;
* ``no_yarn``: the full layer's frequencies the default ones (the attention
  factor kept);
* ``no_attention_factor``: the full layer's ``cos`` and ``sin`` unscaled;
* ``rotary_swapped``: each kind of layer with the other's rotary;
* ``drop_expert``: the first held expert's output left out;
* ``half_rows``: the second half of the positions left out of the loss.
"""
import contextlib
from unittest import mock

FAULTS = ("no_window", "window_doubled", "no_yarn", "no_attention_factor",
          "rotary_swapped", "drop_expert", "half_rows")


@contextlib.contextmanager
def planted(fault):
    import jax.numpy as jnp
    from benchmark.reference import mellum_moe as family
    sound_loss, sound_lands = family.loss, family.lands_here
    sound_window = family.window_of

    def half_rows(config, ops, params, aux, batch):
        tokens, targets, weight = batch
        keep = jnp.arange(weight.shape[1]) < weight.shape[1] // 2
        return sound_loss(config, ops, params, aux,
                          (tokens, targets, weight * keep))

    def swapped(kind, parameters):
        other, = (k for k in family.KINDS if k != kind)
        return parameters[other]

    def doubled(kind, window):
        found = sound_window(kind, window)
        return None if found is None else 2 * found

    replaced = {
        "no_window": ("window_of", lambda kind, window: None),
        "window_doubled": ("window_of", doubled),
        "no_yarn": ("frequency_scale",
                    lambda rope, dim: jnp.ones((dim // 2,), jnp.float32)),
        "no_attention_factor": ("attention_factor", lambda rope: 1.0),
        "rotary_swapped": ("rope_of", swapped),
        "drop_expert": ("lands_here", lambda local, held:
                        sound_lands(local, held) & (local != 0)),
        "half_rows": ("loss", half_rows),
    }[fault]
    with mock.patch.object(family, *replaced):
        yield
