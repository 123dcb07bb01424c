"""The faults a ``keye_dsa`` cell's comparison has to catch, planted in the
plain reference (which then stands in the program's place) by replacing one
of its rules for the length of a ``with`` block:

* ``dense_causal``: the selection ignored by the attention, every key at or
  before the query attended, whatever the indexer picks;
* ``half_topk``: half of ``topk`` keys picked a query;
* ``no_index_loss``: the indexer's loss left out;
* ``attached_indexer``: the indexer's input not detached, so that its loss
  reaches the rest of the model;
* ``drop_expert``: the first held expert's output left out;
* ``half_rows``: the second half of the positions left out of the loss.
"""
import contextlib
from unittest import mock

FAULTS = ("dense_causal", "half_topk", "no_index_loss", "attached_indexer",
          "drop_expert", "half_rows")


@contextlib.contextmanager
def planted(fault):
    import jax.numpy as jnp
    from benchmark.reference import keye_dsa as family
    sound_loss, sound_lands, sound_picks = \
        family.loss, family.lands_here, family.picks

    def half_rows(config, ops, params, aux, batch):
        tokens, targets, weight = batch
        keep = jnp.arange(weight.shape[1]) < weight.shape[1] // 2
        return sound_loss(config, ops, params, aux,
                          (tokens, targets, weight * keep))

    replaced = {
        "dense_causal": ("attends", lambda valid, causal:
                         jnp.broadcast_to(causal, valid.shape)),
        "half_topk": ("picks", lambda scores, causal, topk:
                      sound_picks(scores, causal, topk // 2)),
        "no_index_loss": ("index_loss", lambda target, log_index, valid:
                          0.0 * jnp.sum(jnp.where(valid, log_index, 0.0))),
        "attached_indexer": ("indexer_input", lambda u: u),
        "drop_expert": ("lands_here", lambda local, held:
                        sound_lands(local, held) & (local != 0)),
        "half_rows": ("loss", half_rows),
    }[fault]
    with mock.patch.object(family, *replaced):
        yield
