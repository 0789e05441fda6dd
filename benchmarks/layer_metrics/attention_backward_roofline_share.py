"""Kernels: the flash attention's backward kernels' share of the backward
pass's roofline, in percent, from the device trace.

The kernels' events are named `_flash_backward...` on the `XLA Ops` line
(`_flash_backward_dkv` and `_flash_backward_dq`, one of each per layer's
backward pass; a fused design would show one).  The least time one backward
pass can take is the larger of the algorithm's FLOPs over the chip's bf16
peak and its bytes over the HBM peak (`flops/attention_backward.py`: five
block matmuls over the causal half, whatever the design recomputes); the
share is that, times the passes, over the summed device time of all those
events.  The passes are counted by the most frequent kernel name, so two
kernels a pass are one pass.  Where the program has no such kernel (the
backward in plain XLA, before PR 25) there is nothing to read."""

import re
from collections import Counter

from benchmarks import trace_reduce

KERNEL = r"^_flash_backward"


def read(run: dict) -> float | None:
    rows = run.get("trace_rows")
    if not rows or run["traffic"].get("input") != "tokens":
        return None
    device = trace_reduce.devices(rows)[0]
    seconds, calls = trace_reduce.kernel_seconds(rows, device, KERNEL)
    if not calls:
        return None
    kernels = Counter(
        re.sub(r"\.\d+$", "", name) for _, _, name in trace_reduce.op_intervals(
            rows, device, keep=lambda name: bool(re.search(KERNEL, name))
        )
    )
    passes = max(kernels.values())
    config, traffic = run["config"], run["traffic"]
    cost = run["manifest"].module("flops", "attention_backward")
    b = int(traffic["global_batch"]) // run["chips"]
    s, h = int(traffic["seq_len"]), int(config["num_attention_heads"])
    kv, hd = int(config["num_key_value_heads"]), int(config["head_dim"])
    compute = cost.flops(b, s, h, hd) / run["peaks"]["bf16_flops_per_s"]
    memory = cost.bytes_moved(b, s, h, kv, hd) / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["attention_backward_roofline"] = {
        "bound": "compute" if compute >= memory else "memory",
        "passes": passes, "kernel_calls": dict(kernels), "kernel_seconds": seconds,
        "least_seconds_per_pass": max(compute, memory),
    }
    return 100.0 * passes * max(compute, memory) / seconds
