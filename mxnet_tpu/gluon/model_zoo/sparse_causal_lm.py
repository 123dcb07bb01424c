"""A causal decoder language model whose attention sees, for each query, the
keys a learned indexer picks (the language model of Keye-VL-2.0-30B-A3B,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type:
KeyeVL2``; the selection is DeepSeek Sparse Attention's), built from its
configuration: embedding, decoder layers of indexed sparse attention and a
mixture-of-experts feed-forward, final RMSNorm, untied head over every row.

The configuration is the model's ``config.json`` (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``rms_norm_eps``,
``rope_theta``, ``rope_scaling.mrope_section`` and ``sa_config``: the
indexer's ``indexer_num_heads``, ``indexer_head_dim`` and ``topk``) with the counts
this chip holds in ``num_hidden_layers``, ``num_experts`` and ``vocab_size``,
``indexer_loss_weight`` (default 1), and under ``deployment`` the router's
width ``num_experts_total`` and ``first_expert`` (default: all experts are
held, from 0).

``build``, ``loss`` and ``N_INPUTS`` are what a training loop over
``CompiledTrainStep.from_block`` needs: the block takes ``tokens``
``[batch, L]`` and gives two outputs, logits ``[batch, L, vocab]`` and the
layers' summed indexer loss (1,), weighted; ``loss`` is the next-token
cross-entropy, each position weighted, over ``batch * L``, plus that.  The
two terms move disjoint leaves: the indexer reads its layer's input detached
and its loss takes the attention's distribution as a constant.
"""
from __future__ import annotations

from ... import nd
from ...ops.decoder_ops import SELECTION_RESIDUALS
from ...ops.pallas_ops import ATTENTION_RESIDUALS
from ..block import HybridBlock
from ..nn import Dense, Embedding
from ..nn.decoder_layers import RMSNorm, SparseDecoderLayer

__all__ = ["SparseCausalLM", "build", "loss", "N_INPUTS"]

N_INPUTS = 1            # of a batch's arrays, how many feed the block


class SparseCausalLM(HybridBlock):
    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        deployment = config.get("deployment", {})
        indexer = config["sa_config"]
        hidden, vocab = config["hidden_size"], config["vocab_size"]
        eps = config["rms_norm_eps"]
        self._loss_weight = float(config.get("indexer_loss_weight", 1.0))
        with self.name_scope():
            self.embed = Embedding(vocab, hidden, prefix="embed_")
            self.layers = []
            for i in range(config["num_hidden_layers"]):
                layer = SparseDecoderLayer(
                    hidden, config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"],
                    indexer["indexer_num_heads"], indexer["indexer_head_dim"],
                    indexer["topk"], config["moe_intermediate_size"],
                    deployment.get("num_experts_total", config["num_experts"]),
                    config["num_experts_per_tok"], config["num_experts"],
                    deployment.get("first_expert", 0),
                    float(config["rope_theta"]),
                    (config.get("rope_scaling") or {}).get("mrope_section"),
                    eps, prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(hidden, eps, prefix="final_norm_")
            self.head = Dense(vocab, in_units=hidden, use_bias=False,
                              flatten=False, prefix="head_")

    def hybrid_forward(self, F, tokens):
        import jax
        # text: the three position rows (temporal, height, width) are equal
        row = F.reshape(F._arange(start=0, stop=tokens.shape[1],
                                  dtype="int32"), shape=(1, -1))
        positions = F.concat(row, row, row, dim=0)
        with jax.named_scope("lm.embed"):
            x = self.embed(tokens)
        index_loss = None
        for layer in self.layers:
            x, loss_i = layer(x, positions)
            index_loss = loss_i if index_loss is None else index_loss + loss_i
        with jax.named_scope("lm.head"):     # the final norm with it
            logits = self.head(self.final_norm(x))
        return logits, index_loss * self._loss_weight


def build(config):
    """The model with every decoder layer recomputed in the backward pass.
    Of what a layer computes, its attention kernel's output and log-sum-exp
    are kept, and each row's selection as two numbers (the threshold's bits
    and the cut among its ties): ``batch * L * (heads * (head_dim + 1) + 2)``
    float32 values a layer (135 MB at 32 heads of 128 over 8,192 rows), so
    the recomputed layer runs neither the forward kernel nor the search for
    the thresholds a second time; it does compute the index scores again,
    which the indexer's gradient needs."""
    net = SparseCausalLM(config)
    for layer in net.layers:
        layer.hybridize(remat=True,
                        remat_policy=ATTENTION_RESIDUALS + SELECTION_RESIDUALS)
    return net


def loss(outputs, targets, weight):
    """``sum_i weight_i * CE(logits_i, targets_i) / (batch * L)`` plus the
    layers' summed indexer loss, as the block weighted it."""
    logp = nd.log_softmax(outputs[0].astype("float32"), axis=-1)
    language = -nd.sum(nd.pick(logp, targets, axis=-1) * weight) / weight.size
    return language + nd.sum(outputs[1])
