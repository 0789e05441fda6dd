"""Model FLOPs of one training example (one sequence) for configurations
of kind `looped_decoder`: forward and backward, no recomputation, no embedding
lookup.

The stack runs `total_ut_steps` times a step on the same weights, and a head
and a gate follow every pass.  A weight costs 6 FLOPs a token (2 forward, 4
backward) every time a token passes through it, so a block's weights, the
head's and the gate's count once a pass: that is the model's work, not
recomputation.  One pass is what `flops/decoder.py` counts for a one-pass
decoder of these sizes (every weight once a token, the head among them, and
the causal half of the S x S scores a layer), and the gate's 6 d a token."""

from __future__ import annotations

from benchmarks.flops import decoder


def per_pass(config: dict, traffic: dict) -> float:
    gate = 6.0 * int(config["hidden_size"]) * int(traffic["seq_len"])
    return decoder.per_example(config, traffic) + gate


def per_example(config: dict, traffic: dict) -> float:
    return int(config["total_ut_steps"]) * per_pass(config, traffic)
