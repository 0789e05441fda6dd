"""Model FLOPs of one training example (one sequence) for configurations of
kind `mamba_attn`: forward and backward, no recomputation, no embedding lookup.

Every weight a token passes through costs 6 FLOPs (2 forward, 4 backward).  A
Mamba layer's mixer: the input projection d x 2 I (I = `mamba_expand` d), the
convolution's `mamba_d_conv` taps a channel of I, the projection I x (R + 2 N)
to dt's rank, B and C, dt's projection R x I, the output projection I x d.  The
attention layer's: query and output projections d x (heads x head size), key
and value projections d x (key/value heads x head size).  Every layer's dense
SwiGLU: three matrices d x `intermediate_size`.  The tied table counts once, as
the head's d x V matmul (V the rows held here); the lookup is none.  The scan is
counted as its recurrence (`flops/selective_scan.py`: 4 N a channel and token
forward, twice that backward).  Causal attention computes half of the S x S
scores: QK^T and PV cost 2 * head size each per score and head forward, three
times that with the backward pass, in the attention layers only."""

from __future__ import annotations

from benchmarks.flops import selective_scan


def kinds(config: dict) -> list[str]:
    period, offset = int(config["attn_layer_period"]), int(config["attn_layer_offset"])
    return ["attention" if i % period == offset else "mamba" for i in range(int(config["num_hidden_layers"]))]


def mixer_weights(config: dict, kind: str) -> float:
    d = int(config["hidden_size"])
    if kind == "mamba":
        inner, n, r = int(config["mamba_expand"]) * d, int(config["mamba_d_state"]), int(config["mamba_dt_rank"])
        return d * 2 * inner + int(config["mamba_d_conv"]) * inner + inner * (r + 2 * n) + r * inner + inner * d
    hd = int(config["head_dim"])
    return 2 * d * hd * (int(config["num_attention_heads"]) + int(config["num_key_value_heads"]))


def matmul_weights(config: dict) -> float:
    d = int(config["hidden_size"])
    mlp = 3 * d * int(config["intermediate_size"])
    return sum(mixer_weights(config, k) + mlp for k in kinds(config)) + d * int(config["vocab_size"])


def per_example(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    layers = kinds(config)
    inner = int(config["mamba_expand"]) * int(config["hidden_size"])
    scan = layers.count("mamba") * selective_scan.flops(s, inner, int(config["mamba_d_state"]))
    heads, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    attention = 3 * 2 * 2 * hd * heads * s * s / 2 * layers.count("attention")
    return 6.0 * matmul_weights(config) * s + scan + attention
