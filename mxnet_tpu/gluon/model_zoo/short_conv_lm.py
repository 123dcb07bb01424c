"""Causal decoder language models whose layers differ, and the stack that
builds them from their configuration (``LayerTypedLM``): embedding, one
``DecoderLayer`` for each layer held, whose operator and feed-forward its
published index chooses, final RMSNorm, and a head over every row, tied to the
embedding or a leaf of its own.  ``held_layers`` reads that choice:
``layer_types`` gives a layer's operator, and its feed-forward is dense where
``mlp_layer_types`` says ``dense`` or, where the configuration has no such
list, for the leading ``num_dense_layers`` layers; ``deployment.layers``
holds the published indices of the layers held (default: the first
``num_hidden_layers``).  gluon/model_zoo/window_moe_lm.py builds a second
model on it.

The model of this module is LFM2-24B-A2B
(https://huggingface.co/LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``): a
gated short convolution or grouped-query attention as the token mixer, a
dense gated feed-forward or a mixture of experts behind it, and a head tied
to the embedding.  Its configuration is the model's ``config.json``:
``layer_types`` (``conv`` or ``full_attention``), ``num_dense_layers`` (the
dense ones are of width ``intermediate_size``; the others hold experts of
``moe_intermediate_size``), ``conv_L_cache`` (the convolution's taps),
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``norm_eps``, ``rope_parameters.rope_theta``, ``num_experts_per_tok``,
``routed_scaling_factor`` and ``use_expert_bias`` (the sigmoid router's scale
and selection bias), with the counts this chip holds in
``num_hidden_layers``, ``num_experts`` and ``vocab_size``, and under
``deployment`` the router's width ``num_experts_total`` and ``first_expert``
(default: all experts are held, from 0) and ``layers``.  The head size is
``head_dim`` where given, else hidden over heads.

``build``, ``loss`` and ``N_INPUTS`` are what a training loop over
``CompiledTrainStep.from_block`` needs: the block takes ``tokens``
``[batch, L]`` and gives logits ``[batch, L, vocab]``; ``loss`` is the
next-token cross-entropy, each position weighted, over ``batch * L``.
"""
from __future__ import annotations

from ... import nd
from ...ops.pallas_ops import ATTENTION_RESIDUALS
from ..block import HybridBlock
from ..nn import Dense, Embedding
from ..nn.decoder_layers import (CausalAttention, DecoderLayer, GatedMLP,
                                 GatedShortConv, HeldExpertsMoE, RMSNorm)

__all__ = ["LayerTypedLM", "ShortConvLM", "build", "loss", "N_INPUTS"]

N_INPUTS = 1            # of a batch's arrays, how many feed the block


def _dense(config, index):
    """Whether the feed-forward of published layer ``index`` is dense."""
    if "mlp_layer_types" in config:
        kind = config["mlp_layer_types"][index]
        if kind not in ("dense", "sparse"):
            raise ValueError("a layer's feed-forward is dense or sparse, not "
                             "%r" % (kind,))
        return kind == "dense"
    return index < config["num_dense_layers"]


def held_layers(config):
    """``(operator's kind, whether the feed-forward is dense)`` of each layer
    held here, by its published index."""
    count = config["num_hidden_layers"]
    held = config.get("deployment", {}).get("layers") or range(count)
    if len(held) != count:
        raise ValueError("%d layers held of num_hidden_layers %d"
                         % (len(held), count))
    return [(config["layer_types"][i], _dense(config, i)) for i in held]


class LayerTypedLM(HybridBlock):
    """The stack.  ``operator(kind)`` and ``feed_forward(dense)`` give, for
    a layer of ``held_layers``, what builds its operator and its feed-forward
    (``DecoderLayer`` calls them inside the layer's name scope); ``eps`` is
    the RMSNorms'; ``tied``: the head is the embedding's own matrix, which
    then gets a gradient from the lookup and from the head, else a leaf
    ``head_weight`` of its own."""

    def __init__(self, config, operator, feed_forward, eps, tied, **kwargs):
        super().__init__(**kwargs)
        hidden, vocab = config["hidden_size"], config["vocab_size"]
        with self.name_scope():
            self.embed = Embedding(vocab, hidden, prefix="embed_")
            self.layers = []
            for i, (kind, dense) in enumerate(held_layers(config)):
                layer = DecoderLayer(hidden, operator(kind),
                                     feed_forward(dense), eps,
                                     prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(hidden, eps, prefix="final_norm_")
            if tied:
                self.head = Dense(vocab, in_units=hidden, use_bias=False,
                                  flatten=False, params=self.embed.params)
            else:
                self.head = Dense(vocab, in_units=hidden, use_bias=False,
                                  flatten=False, prefix="head_")

    def hybrid_forward(self, F, tokens):
        import jax
        positions = F._arange(start=0, stop=tokens.shape[1], dtype="int32")
        with jax.named_scope("lm.embed"):
            x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x, positions)
        with jax.named_scope("lm.head"):     # the final norm with it
            return self.head(self.final_norm(x))


class ShortConvLM(LayerTypedLM):
    def __init__(self, config, **kwargs):
        deployment = config.get("deployment", {})
        hidden = config["hidden_size"]
        heads, eps = config["num_attention_heads"], config["norm_eps"]
        for key, said in (("conv_bias", False), ("norm_topk_prob", True)):
            if config.get(key, said) != said:
                raise ValueError("%s %r is not built" % (key, config[key]))

        def operator(kind):
            if kind == "conv":
                return lambda: GatedShortConv(hidden, config["conv_L_cache"],
                                              prefix="conv_")
            if kind == "full_attention":
                return lambda: CausalAttention(
                    hidden, heads, config["num_key_value_heads"],
                    config.get("head_dim") or hidden // heads,
                    float(config["rope_parameters"]["rope_theta"]), eps,
                    prefix="attn_")
            raise ValueError("a layer's operator is conv or full_attention, "
                             "not %r" % (kind,))

        def feed_forward(dense):
            if dense:
                return lambda: GatedMLP(hidden, config["intermediate_size"],
                                        prefix="mlp_")
            return lambda: HeldExpertsMoE(
                hidden, config["moe_intermediate_size"],
                deployment.get("num_experts_total", config["num_experts"]),
                config["num_experts_per_tok"], config["num_experts"],
                deployment.get("first_expert", 0), scoring="sigmoid",
                scale=float(config.get("routed_scaling_factor", 1.0)),
                selection_bias=bool(config.get("use_expert_bias")),
                prefix="moe_")

        super().__init__(config, operator, feed_forward, eps, tied=True,
                         **kwargs)


def recomputed(net):
    """``net`` with every decoder layer recomputed in the backward pass; an
    attention layer keeps its kernel's output and log-sum-exp (``batch *
    heads * L * (head_dim + 1)`` float32 values), so the recomputed layer
    runs no forward kernel a second time; a convolution layer keeps
    nothing."""
    for layer in net.layers:
        layer.hybridize(remat=True, remat_policy=ATTENTION_RESIDUALS)
    return net


def build(config):
    """The model, every decoder layer ``recomputed``."""
    return recomputed(ShortConvLM(config))


def loss(outputs, targets, weight):
    """``sum_i weight_i * CE(logits_i, targets_i) / (batch * L)``, as the
    target's shifted logit less the row's log-sum-exp: no array of every
    row's log-probabilities is made.  (Written as ``log_softmax`` then
    ``pick`` over ``[2, 8192, 8192]`` logits, the row maximum came out of
    the TPU's compiler as a window reduction 16,383 wide, 54.6 ms a step on
    a v5e: PERF.md, PR 35.)"""
    logits = outputs[0].astype("float32")
    shifted = logits - nd.max(logits, axis=-1, keepdims=True)
    lse = nd.log(nd.sum(nd.exp(shifted), axis=-1))
    picked = nd.pick(shifted, targets, axis=-1)
    return -nd.sum((picked - lse) * weight) / weight.size
