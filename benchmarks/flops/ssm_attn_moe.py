"""Model FLOPs of one training example (one sequence) for configurations of
kind `ssm_attn_moe`: forward and backward, no recomputation, no embedding
lookup.

Every weight a token passes through costs 6 FLOPs (2 forward, 4 backward).  An
`M` block: the input projection d x (2 inner + 2 G N + H), the convolution's
`conv_kernel` taps a channel of inner + 2 G N, the output projection inner x d.
A `*` block: query and output projections d x (heads x head size), key and
value projections d x (key/value heads x head size).  An `E` block: the router
over all published experts, the two latent projections d x latent, the shared
expert's two matrices d x shared width, and the experts held here at their
expectation: a token chooses `num_experts_per_tok` of the published experts, of
which `n_routed_experts` are held, so it passes through k * held / published of
them on average (22 * 8 / 512 = 0.34), two matrices of latent x expert width
each.  The untied head is d x V.  The scan is counted as its recurrence
(`flops/ssd.py`: 4 N P a head and token forward, twice that backward), not as
the chunked form's matmuls.  Causal attention computes half of the S x S
scores: QK^T and PV cost 2 * head size each per score and head forward, three
times that with the backward pass, in the `*` blocks only."""

from __future__ import annotations

from benchmarks.flops import ssd


def routed_tokens_share(config: dict) -> float:
    """Held experts a token passes through, on average."""
    return (
        int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
        / int(config["published"]["n_routed_experts"])
    )


def block_weights(config: dict, block: str) -> float:
    d = int(config["hidden_size"])
    if block == "M":
        heads = int(config["mamba_num_heads"])
        inner = heads * int(config["mamba_head_dim"])
        conv = inner + 2 * int(config["n_groups"]) * int(config["ssm_state_size"])
        return d * (inner + conv + heads) + int(config["conv_kernel"]) * conv + inner * d
    if block == "*":
        hd = int(config["head_dim"])
        return 2 * d * hd * (int(config["num_attention_heads"]) + int(config["num_key_value_heads"]))
    latent = int(config["moe_latent_size"])
    experts = 2 * latent * int(config["moe_intermediate_size"]) * routed_tokens_share(config)
    shared = 2 * d * int(config["moe_shared_expert_intermediate_size"])
    return d * int(config["published"]["n_routed_experts"]) + 2 * d * latent + shared + experts


def matmul_weights(config: dict) -> float:
    blocks = sum(block_weights(config, b) for b in config["hybrid_override_pattern"])
    return blocks + int(config["hidden_size"]) * int(config["vocab_size"])


def per_example(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    pattern = config["hybrid_override_pattern"]
    scan = pattern.count("M") * ssd.flops(
        s, int(config["mamba_num_heads"]), int(config["mamba_head_dim"]), int(config["ssm_state_size"])
    )
    heads, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    attention = 3 * 2 * 2 * hd * heads * s * s / 2 * pattern.count("*")
    return 6.0 * matmul_weights(config) * s + scan + attention
