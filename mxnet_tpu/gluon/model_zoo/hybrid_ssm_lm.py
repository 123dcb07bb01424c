"""A causal decoder language model whose layers are single blocks of three
kinds: Mamba-2 state-space mixers, mixtures of relu² experts beside a shared
expert, and grouped-query attention without rotary or query/key norm
(NVIDIA-Nemotron-3-Nano-30B-A3B,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type: nemotron_h``), built by ``short_conv_lm``'s layer-typed stack:
embedding, one ``ResidualLayer`` a layer (``x + Block(RMSNorm(x))``), final
RMSNorm, and a head of its own (untied) over every row.

The configuration is the model's ``config.json``: ``hybrid_override_pattern``
(a layer's letter: ``M`` a Mamba-2 mixer, ``E`` experts, ``*`` attention),
``hidden_size``, ``layer_norm_epsilon``; the mixer's ``mamba_num_heads``
heads of ``mamba_head_dim`` (the inner width is their product),
``ssm_state_size``, ``n_groups``, ``conv_kernel`` and ``chunk_size``; the
attention's ``num_attention_heads``, ``num_key_value_heads`` and
``head_dim``; the experts' ``moe_intermediate_size``,
``num_experts_per_tok`` and ``routed_scaling_factor`` under a sigmoid router
with a selection bias, and ``n_shared_experts`` shared experts of
``moe_shared_expert_intermediate_size`` each; with the counts this chip holds
in ``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size``,
``deployment`` as ``short_conv_lm`` reads it, and ``router_norm_eps``, what
the router adds under its normalisation (default
``parallel.moe.SIGMOID_NORM_EPS``).

``build``, ``loss`` and ``N_INPUTS`` are what a training loop over
``CompiledTrainStep.from_block`` needs: the block takes ``tokens`` ``[batch,
L]`` and gives logits ``[batch, L, vocab]``; ``loss`` is ``short_conv_lm``'s.
"""
from __future__ import annotations

from ..nn.decoder_layers import CausalAttention, HeldExpertsMoE, Mamba2Mixer
from .short_conv_lm import N_INPUTS, LayerTypedLM, loss, recomputed

__all__ = ["HybridSSMLM", "build", "loss", "N_INPUTS"]

# what the published form fixes and this module builds no other way
BUILT = (("attention_bias", False), ("mlp_bias", False), ("use_bias", False),
         ("mamba_proj_bias", False), ("use_conv_bias", True),
         ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
         ("tie_word_embeddings", False), ("norm_topk_prob", True),
         ("n_group", 1), ("topk_group", 1), ("residual_in_fp32", False))


class HybridSSMLM(LayerTypedLM):
    def __init__(self, config, **kwargs):
        for key, said in BUILT:
            if config.get(key, said) != said:
                raise ValueError("%s %r is not built" % (key, config[key]))
        deployment = config.get("deployment", {})
        hidden, eps = config["hidden_size"], config["layer_norm_epsilon"]
        heads = config["num_attention_heads"]
        blocks = {
            "M": (lambda: Mamba2Mixer(
                hidden, config["mamba_num_heads"], config["mamba_head_dim"],
                config["ssm_state_size"], config["n_groups"],
                config["conv_kernel"], config["chunk_size"], eps,
                prefix="ssm_"), True),
            "*": (lambda: CausalAttention(
                hidden, heads, config["num_key_value_heads"],
                config.get("head_dim") or hidden // heads, rope_base=None,
                qk_norm=False, prefix="attn_"), True),
            "E": (lambda: HeldExpertsMoE(
                hidden, config["moe_intermediate_size"],
                deployment.get("num_experts_total",
                               config["n_routed_experts"]),
                config["num_experts_per_tok"], config["n_routed_experts"],
                deployment.get("first_expert", 0), scoring="sigmoid",
                scale=float(config["routed_scaling_factor"]),
                selection_bias=True, act="relu2",
                router_eps=config.get("router_norm_eps"),
                shared_width=config["n_shared_experts"]
                * config["moe_shared_expert_intermediate_size"],
                prefix="moe_"), False)}

        def block(kind):
            if kind not in blocks:
                raise ValueError("a layer is M, E or *, not %r" % (kind,))
            return blocks[kind]

        super().__init__(config, None, None, eps, tied=False, block=block,
                         **kwargs)


def build(config):
    """The model, every layer ``recomputed``: an attention layer keeps its
    kernel's output and log-sum-exp, an expert layer its slot table; a
    Mamba-2 layer keeps nothing and runs its scan's forward kernel again.
    Keeping the scan's output and chunk states (268 MB a layer) read 15.64
    GB of a v5e's 16 at the cell's size, over the 15.0 that the benchmark's
    decoder cells keep to; without them the step's temporaries are 1.07 GB
    fewer (PERF.md, PR 41)."""
    return recomputed(HybridSSMLM(config))
