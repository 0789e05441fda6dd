"""Model FLOPs of one training example (one sequence) for configurations of
kind `mla_moe`: forward and backward, no recomputation, no embedding lookup.

Every weight matrix a token passes through costs 6 FLOPs per weight (2
forward, 4 backward).  Per block the latent attention's five matrices (query
down and up, key/value down and up, output); in a dense block the SwiGLU of
`intermediate_size`; in a routed block the router over all published experts,
the shared expert, and the experts held here at their expectation: a token
chooses `num_experts_per_tok` of the published experts, of which
`n_routed_experts` are held, so it passes through k * held / published of
them on average (4 * 16 / 64 = 1).  The prediction module is one more routed
block and the joining matrix (2 d x d), and each of the two heads is d x V.
Causal attention computes half of the S x S scores: QK^T costs 2 * qk size and
PV 2 * v size FLOPs per score and head forward, three times that with the
backward pass, in every block, the prediction module's too."""

from __future__ import annotations


def attention_weights(config: dict) -> int:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    q_rank, kv_rank = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    vd = int(config["v_head_dim"])
    return (
        d * q_rank + q_rank * h * (nope + rope) + d * (kv_rank + rope)
        + kv_rank * h * (nope + vd) + h * vd * d
    )


def routed_tokens_share(config: dict) -> float:
    """Held experts a token passes through, on average."""
    return (
        int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
        / int(config["published"]["n_routed_experts"])
    )


def routed_block_weights(config: dict) -> float:
    d, m = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    experts = 3 * d * m * (routed_tokens_share(config) + int(config["n_shared_experts"]))
    return attention_weights(config) + d * int(config["published"]["n_routed_experts"]) + experts


def matmul_weights(config: dict) -> float:
    d = int(config["hidden_size"])
    dense = int(config["first_k_dense_replace"])
    predict = int(config["num_nextn_predict_layers"])
    routed = int(config["num_hidden_layers"]) - dense + predict
    dense_block = attention_weights(config) + 3 * d * int(config["intermediate_size"])
    return (
        dense * dense_block + routed * routed_block_weights(config)
        + (1 + predict) * d * int(config["vocab_size"]) + predict * 2 * d * d
    )


def per_example(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    blocks = int(config["num_hidden_layers"]) + int(config["num_nextn_predict_layers"])
    qk = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    attention = 3 * s * s * int(config["num_attention_heads"]) * (
        qk + int(config["v_head_dim"])
    ) * blocks
    return 6.0 * matmul_weights(config) * s + attention
