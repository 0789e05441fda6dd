"""Kernels: the flash-attention forward kernel's share of its roofline, in
percent, from the device trace.

The kernel's events are named `_flash_forward` on the `XLA Ops` line (one
per call: every layer's forward pass and its recomputation under remat).
The least time a call can take is the larger of its FLOPs over the chip's
bf16 peak and its bytes over the HBM peak (`flops/attention.py`); the share
is that, times the calls, over the summed device time of the events.  Which
of the two bounds it goes on an earlier line."""

from benchmarks import trace_reduce

KERNEL = r"^_flash_forward"


def read(run: dict) -> float | None:
    rows = run.get("trace_rows")
    if not rows or run["traffic"].get("input") != "tokens":
        return None
    seconds, calls = trace_reduce.kernel_seconds(rows, trace_reduce.devices(rows)[0], KERNEL)
    if not calls:
        return None
    config, traffic = run["config"], run["traffic"]
    cost = run["manifest"].module("flops", "attention")
    b = int(traffic["global_batch"]) // run["chips"]
    s, h = int(traffic["seq_len"]), int(config["num_attention_heads"])
    kv, hd = int(config["num_key_value_heads"]), int(config["head_dim"])
    compute = cost.flops(b, s, h, hd) / run["peaks"]["bf16_flops_per_s"]
    memory = cost.bytes_moved(b, s, h, kv, hd) / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["attention_roofline"] = {
        "bound": "compute" if compute >= memory else "memory",
        "calls": calls, "kernel_seconds": seconds,
        "least_seconds_per_call": max(compute, memory),
    }
    return 100.0 * calls * max(compute, memory) / seconds
