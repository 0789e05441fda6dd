"""Kernels: the windowed flash-attention forward kernel's share of its
roofline, in percent, from the device trace.

The kernel's events are named `_window_flash_forward` on the `XLA Ops` line (one
per call: every window layer's forward pass and its recomputation under remat).
The least time a call can take is the larger of its FLOPs over the chip's bf16
peak and its bytes over the HBM peak (`flops/window_attention.py`: the scores
of the band, `S W - W (W - 1) / 2` a head, not of the tiles the kernel runs, so
another implementation is measured by the same reader); the share is that,
times the calls, over the summed device time of the events.  Heads, head size,
window and S come from the configuration (the window layers' own head count)."""

from benchmarks import trace_reduce

KERNEL = r"^_window_flash_forward"


def read(run: dict) -> float | None:
    rows = run.get("trace_rows")
    if not rows or run["traffic"].get("input") != "tokens":
        return None
    seconds, calls = trace_reduce.kernel_seconds(rows, trace_reduce.devices(rows)[0], KERNEL)
    cost = run["manifest"].module("flops", "window_attention")
    least = cost.least_seconds(run, backward=False) if calls else None
    if least is None:
        return None
    run.setdefault("notes", {})["window_attention_roofline"] = {
        "bound": least[1], "calls": calls, "kernel_seconds": seconds,
        "window_layers": cost.call_shape(run)["layers"], "least_seconds_per_call": least[0],
    }
    return 100.0 * calls * least[0] / seconds
