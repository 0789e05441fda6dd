"""Model FLOPs of one training example (one sequence) for configurations of
kind `window_attn_moe`: forward and backward, no recomputation, no embedding
lookup.

Every weight a token passes through costs 6 FLOPs (2 forward, 4 backward).  A
layer's attention: query and output projections d x (its heads x head size),
key and value projections d x (key/value heads x head size), and with `gating`
the gate d x its heads; the heads are the layer's own
(`num_attention_heads_per_layer`).  A `dense` layer has the SwiGLU of
`intermediate_size`; a `sparse` one the router over all published experts, the
shared expert, and the experts held here at their expectation: a token chooses
`num_experts_per_tok` of the published experts, of which `num_experts` are held,
so it passes through k * held / published of them on average (8 * 64 / 256 = 2).
The untied head is d x V.  The scores: QK^T and PV cost 2 * head size each per
score and head forward, three times that with the backward pass; a
`full_attention` layer computes half of the S x S scores (as the other kinds
count the causal triangle), a `sliding_attention` layer the band,
`S W - W (W - 1) / 2` a head (`flops/window_attention.py`), so a pair of blocks
the kernels skip behind the window is not credited."""

from __future__ import annotations


def attention_weights(config: dict, heads: int) -> int:
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    gate = d * heads if config["gating"] else 0
    return 2 * d * heads * hd + 2 * d * int(config["num_key_value_heads"]) * hd + gate


def routed_tokens_share(config: dict) -> float:
    """Held experts a token passes through, on average."""
    return (
        int(config["num_experts_per_tok"]) * int(config["num_experts"])
        / int(config["published"]["num_experts"])
    )


def feed_forward_weights(config: dict, kind: str) -> float:
    d = int(config["hidden_size"])
    if kind == "dense":
        return 3 * d * int(config["intermediate_size"])
    experts = 3 * d * int(config["moe_intermediate_size"]) * routed_tokens_share(config)
    shared = 3 * d * int(config["shared_expert_intermediate_size"])
    return d * int(config["published"]["num_experts"]) + experts + shared


def matmul_weights(config: dict) -> float:
    layers = sum(
        attention_weights(config, int(heads)) + feed_forward_weights(config, ff)
        for heads, ff in zip(config["num_attention_heads_per_layer"], config["mlp_layer_types"])
    )
    return layers + int(config["hidden_size"]) * int(config["vocab_size"])


def attended_scores(config: dict, s: int) -> float:
    """Scores a sequence computes, summed over the layers' heads."""
    w = min(int(config["sliding_window"]), s)
    band, triangle = s * w - w * (w - 1) / 2, s * s / 2
    return sum(
        int(heads) * (band if kind == "sliding_attention" else triangle)
        for kind, heads in zip(config["layer_types"], config["num_attention_heads_per_layer"])
    )


def per_example(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    attention = 3 * 2 * 2 * int(config["head_dim"]) * attended_scores(config, s)
    return 6.0 * matmul_weights(config) * s + attention
