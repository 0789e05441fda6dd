"""Model FLOPs of one training example (one sequence) for configurations of
kind `conv_attn_moe`: forward and backward, no recomputation, no embedding
lookup.

Every weight a token passes through costs 6 FLOPs (2 forward, 4 backward).
A `conv` layer's mixer: the input projection d x 3d, the filter's
`conv_L_cache` taps a channel, the output projection d x d.  A
`full_attention` layer's: query and output projections d x d, key and value
projections d x (key/value heads x head size).  A layer before
`num_dense_layers` has the SwiGLU of `intermediate_size`; every other one the
router over all published experts and the experts held here at their
expectation: a token chooses `num_experts_per_tok` of the published experts, of
which `num_experts` are held, so it passes through k * held / published of them
on average (4 * 8 / 32 = 1); there is no shared expert.  The tied table counts
once, as the head (d x V).  Causal attention computes half of the S x S scores:
QK^T and PV cost 2 * head size each per score and head forward, three times
that with the backward pass, in the attention layers only."""

from __future__ import annotations


def mixer_weights(config: dict, mixer: str) -> int:
    d = int(config["hidden_size"])
    if mixer == "conv":
        return 3 * d * d + int(config["conv_L_cache"]) * d + d * d
    key_value = int(config["num_key_value_heads"]) * (d // int(config["num_attention_heads"]))
    return 2 * d * d + 2 * d * key_value


def routed_tokens_share(config: dict) -> float:
    """Held experts a token passes through, on average."""
    return (
        int(config["num_experts_per_tok"]) * int(config["num_experts"])
        / int(config["published"]["num_experts"])
    )


def feed_forward_weights(config: dict, layer: int) -> float:
    d = int(config["hidden_size"])
    if layer < int(config["num_dense_layers"]):
        return 3 * d * int(config["intermediate_size"])
    experts = 3 * d * int(config["moe_intermediate_size"]) * routed_tokens_share(config)
    return d * int(config["published"]["num_experts"]) + experts


def matmul_weights(config: dict) -> float:
    layers = sum(
        mixer_weights(config, mixer) + feed_forward_weights(config, i)
        for i, mixer in enumerate(config["layer_types"])
    )
    return layers + int(config["hidden_size"]) * int(config["vocab_size"])


def per_example(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    attending = list(config["layer_types"]).count("full_attention")
    attention = 3 * s * s * 2 * int(config["hidden_size"]) * attending  # heads x head size = d
    return 6.0 * matmul_weights(config) * s + attention
