"""The faults a block-diffusion cell's comparison has to catch, planted in
the plain reference (which then stands in the program's place) by replacing
one of its rules for the length of a ``with`` block:

* ``half_rows``: the second half of the positions left out of the loss;
* ``drop_expert``: the first held expert's output left out;
* ``own_clean_block``: a noised row sees its own clean block (a label leak).
"""
import contextlib
from unittest import mock

FAULTS = ("half_rows", "drop_expert", "own_clean_block")


@contextlib.contextmanager
def planted(fault):
    import jax.numpy as jnp
    from benchmark.reference import sdar_moe as family
    sound_loss, sound_lands = family.loss, family.lands_here

    def half_rows(config, ops, params, aux, batch):
        tokens, targets, weight = batch
        keep = jnp.arange(weight.shape[1]) < weight.shape[1] // 2
        return sound_loss(config, ops, params, aux,
                          (tokens, targets, weight * keep))

    replaced = {
        "half_rows": ("loss", half_rows),
        "drop_expert": ("lands_here", lambda local, held:
                        sound_lands(local, held) & (local != 0)),
        "own_clean_block": ("noised_sees_clean", lambda clean_block, q_block:
                            clean_block <= q_block),
    }[fault]
    with mock.patch.object(family, *replaced):
        yield
