"""Trainer: model FLOPs per example (`flops/<kind>.py`: forward and
backward, no recomputation, no embedding lookup) x examples/s/chip over
the chip's bf16 peak, in percent."""

from benchmarks.recorder import throughput


def read(run: dict) -> float:
    flops = run["manifest"].module("flops", run["config"]["kind"])
    per_example = flops.per_example(run["config"], run["traffic"])
    return 100.0 * per_example * throughput(run) / run["peaks"]["bf16_flops_per_s"]
