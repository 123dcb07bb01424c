"""A decoder language model trained by block diffusion (the ``sdar_moe``
family: SDAR-30B-A3B-Chat, https://huggingface.co/JetLM/SDAR-30B-A3B-Chat),
built from its configuration: embedding, decoder layers of grouped-query
attention under the block-diffusion mask and a mixture-of-experts
feed-forward, final RMSNorm, untied head over the noised rows.

The configuration is the model's ``config.json`` (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``rms_norm_eps``,
``rope_theta``) with the counts this chip holds in ``num_hidden_layers``,
``num_experts`` and ``vocab_size``, ``block_length``, and under
``deployment`` the router's width ``num_experts_total`` and ``first_expert``
(default: all experts are held, from 0).

``build``, ``loss`` and ``N_INPUTS`` are what a training loop over
``CompiledTrainStep.from_block`` needs: the block takes ``tokens``
``[batch, 2L]`` (the noised copy of each sequence, then the clean one) and
gives logits ``[batch, L, vocab]`` for the noised rows; ``loss`` is the
masked-token cross-entropy, each position weighted by ``masked / t`` of its
block, over ``batch * L``.
"""
from __future__ import annotations

from ... import nd
from ...ops.pallas_ops import ATTENTION_RESIDUALS
from ..block import HybridBlock
from ..nn import Dense, Embedding
from ..nn.decoder_layers import BlockDiffusionDecoderLayer, RMSNorm

__all__ = ["BlockDiffusionMoEDecoder", "build", "loss", "N_INPUTS"]

N_INPUTS = 1            # of a batch's arrays, how many feed the block


class BlockDiffusionMoEDecoder(HybridBlock):
    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        deployment = config.get("deployment", {})
        hidden, vocab = config["hidden_size"], config["vocab_size"]
        eps = config["rms_norm_eps"]
        with self.name_scope():
            self.embed = Embedding(vocab, hidden, prefix="embed_")
            self.layers = []
            for i in range(config["num_hidden_layers"]):
                layer = BlockDiffusionDecoderLayer(
                    hidden, config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"],
                    config["block_length"], config["moe_intermediate_size"],
                    deployment.get("num_experts_total", config["num_experts"]),
                    config["num_experts_per_tok"], config["num_experts"],
                    deployment.get("first_expert", 0),
                    float(config["rope_theta"]), eps, prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(hidden, eps, prefix="final_norm_")
            self.head = Dense(vocab, in_units=hidden, use_bias=False,
                              flatten=False, prefix="head_")

    def hybrid_forward(self, F, tokens):
        import jax
        seq_len = tokens.shape[1] // 2
        position = F._arange(start=0, stop=seq_len, dtype="int32")
        positions = F.concat(position, position, dim=0)
        with jax.named_scope("lm.embed"):
            x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x, positions)
        with jax.named_scope("lm.head"):     # the final norm with it
            noised = F.slice_axis(x, axis=1, begin=0, end=seq_len)
            return self.head(self.final_norm(noised))


def build(config):
    """The model with every decoder layer recomputed in the backward pass:
    at 8,192 rows a layer's float32 activations are some 14 GB, its input 64
    MB.  Of what a layer computes, its attention kernel's output and
    log-sum-exp are kept (by the names the attention call gives them), so the
    recomputed layer runs no forward kernel a second time: per layer
    ``batch * heads * 2L * (head_dim + 1)`` float32 values stay live from the
    forward pass to the layer's backward (135 MB at 32 heads of 128 over
    8,192 rows) for one kernel's time (5.5 ms there)."""
    net = BlockDiffusionMoEDecoder(config)
    for layer in net.layers:
        layer.hybridize(remat=True, remat_policy=ATTENTION_RESIDUALS)
    return net


def loss(outputs, targets, weight):
    """``sum_i weight_i * CE(logits_i, targets_i) / (batch * L)``."""
    logp = nd.log_softmax(outputs[0].astype("float32"), axis=-1)
    return -nd.sum(nd.pick(logp, targets, axis=-1) * weight) / weight.size
