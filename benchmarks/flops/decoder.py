"""Model FLOPs of one training example (one sequence) for configurations
of kind `decoder`: forward and backward, no recomputation, no embedding
lookup.

Every weight matrix a token passes through costs 2 FLOPs per weight in the
forward pass and twice that in the backward pass: 6 per weight per token.
The embedding table is a lookup, not a matmul, and is not counted; the
output head is.  Causal attention computes half of the S x S scores:
QK^T and PV are each 2*hd FLOPs per score and head, so 2*S*S*H*hd forward
per layer and three times that with the backward pass."""

from __future__ import annotations


def matmul_weights(config: dict) -> int:
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    h, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    f = int(config["intermediate_size"])
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    return int(config["num_hidden_layers"]) * per_layer + d * int(config["vocab_size"])


def per_example(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    attention = (
        6 * s * s * int(config["num_attention_heads"]) * int(config["head_dim"])
        * int(config["num_hidden_layers"])
    )
    return 6.0 * matmul_weights(config) * s + attention
