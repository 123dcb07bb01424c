"""A causal decoder language model whose attention layers differ in the keys
they see and in their rotary form, over a mixture of experts in every layer
(Mellum2-12B-A2.5B, https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type: mellum``), built by ``short_conv_lm``'s layer-typed stack:
embedding, decoder layers, final RMSNorm, and a head of its own (untied) over
every row.

The configuration is the model's ``config.json``: ``layer_types`` (a layer is
``sliding_attention``, whose queries see the ``sliding_window`` keys up to
themselves, or ``full_attention``, causal over every earlier key),
``mlp_layer_types`` (``sparse`` only: experts of ``moe_intermediate_size``
under a softmax router whose ``num_experts_per_tok`` picked weights are
renormalised), ``rope_parameters`` by layer kind (``rope_theta``,
``rope_type`` "default" or "yarn" with YaRN's ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow`` and
``attention_factor``), ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim`` and ``rms_norm_eps``, with the counts
this chip holds in ``num_hidden_layers``, ``num_experts`` and
``vocab_size``, and ``deployment`` as ``short_conv_lm`` reads it.

``build``, ``loss`` and ``N_INPUTS`` are ``short_conv_lm``'s: the block takes
``tokens`` ``[batch, L]`` and gives logits ``[batch, L, vocab]``.
"""
from __future__ import annotations

from ..nn.decoder_layers import CausalAttention, HeldExpertsMoE
from .short_conv_lm import N_INPUTS, LayerTypedLM, loss, recomputed

__all__ = ["WindowMoELM", "build", "loss", "N_INPUTS"]

YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")


def rotary(parameters):
    """``(base, the rotary operator's other attrs)`` of one layer kind's
    ``rope_parameters``."""
    kind = parameters.get("rope_type", "default")
    base = float(parameters["rope_theta"])
    if kind == "default":
        return base, {}
    if kind == "yarn":
        return base, dict(rope_type="yarn", **{
            key: float(parameters[key]) for key in YARN_KEYS})
    raise ValueError("rope_type is default or yarn, not %r" % (kind,))


class WindowMoELM(LayerTypedLM):
    def __init__(self, config, **kwargs):
        for key, said in (("attention_bias", False), ("norm_topk_prob", True),
                          ("tie_word_embeddings", False),
                          ("use_sliding_window", True), ("hidden_act", "silu")):
            if config.get(key, said) != said:
                raise ValueError("%s %r is not built" % (key, config[key]))
        deployment = config.get("deployment", {})
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        eps = config["rms_norm_eps"]
        windows = {"sliding_attention": config["sliding_window"],
                   "full_attention": None}

        def operator(kind):
            if kind not in windows:
                raise ValueError("a layer's operator is sliding_attention or "
                                 "full_attention, not %r" % (kind,))
            base, rope = rotary(config["rope_parameters"][kind])
            return lambda: CausalAttention(
                hidden, heads, config["num_key_value_heads"],
                config.get("head_dim") or hidden // heads, base, eps,
                window=windows[kind], rope=rope, prefix="attn_")

        def feed_forward(dense):
            if dense:
                raise ValueError("a dense feed-forward is not built here")
            return lambda: HeldExpertsMoE(
                hidden, config["moe_intermediate_size"],
                deployment.get("num_experts_total", config["num_experts"]),
                config["num_experts_per_tok"], config["num_experts"],
                deployment.get("first_expert", 0), prefix="moe_")

        super().__init__(config, operator, feed_forward, eps, tied=False,
                         **kwargs)


def build(config):
    """The model, every decoder layer ``recomputed``: the kernels' output
    and log-sum-exp kept, so that no forward kernel runs twice."""
    return recomputed(WindowMoELM(config))
