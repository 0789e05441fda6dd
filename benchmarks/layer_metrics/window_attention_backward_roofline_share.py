"""Kernels: the windowed flash attention's backward kernels' share of the
backward pass's roofline, in percent, from the device trace.

The kernels' events are named `_window_flash_backward...` on the `XLA Ops` line
(`_window_flash_backward_dkv` and `_window_flash_backward_dq`, one of each per
window layer's backward pass; a fused design would show one).  The least time
one backward pass can take is the larger of the algorithm's FLOPs over the
chip's bf16 peak and its bytes over the HBM peak (`flops/window_attention.py`:
five block matmuls a score over the band, whatever the design recomputes); the
share is that, times the passes, over the summed device time of all those
events.  The passes are counted by the most frequent kernel name, so two
kernels a pass are one pass."""

import re
from collections import Counter

from benchmarks import trace_reduce

KERNEL = r"^_window_flash_backward"


def read(run: dict) -> float | None:
    rows = run.get("trace_rows")
    if not rows or run["traffic"].get("input") != "tokens":
        return None
    device = trace_reduce.devices(rows)[0]
    seconds, calls = trace_reduce.kernel_seconds(rows, device, KERNEL)
    cost = run["manifest"].module("flops", "window_attention")
    least = cost.least_seconds(run, backward=True) if calls else None
    if least is None:
        return None
    kernels = Counter(
        re.sub(r"\.\d+$", "", name) for _, _, name in trace_reduce.op_intervals(
            rows, device, keep=lambda name: bool(re.search(KERNEL, name))
        )
    )
    passes = max(kernels.values())
    run.setdefault("notes", {})["window_attention_backward_roofline"] = {
        "bound": least[1], "passes": passes, "kernel_calls": dict(kernels),
        "kernel_seconds": seconds, "least_seconds_per_pass": least[0],
    }
    return 100.0 * passes * least[0] / seconds
