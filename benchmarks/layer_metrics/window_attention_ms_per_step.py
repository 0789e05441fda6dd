"""Kernels: device time of the flash attention's three kernels under a sliding
window (`_window_flash_forward`, `_window_flash_backward_dkv`,
`_window_flash_backward_dq`: every window layer's forward pass, its
recomputation and its backward pass), per executed program of the traced
window on device 0, in milliseconds.  The scopes this kind of model adds to
`moe_reduce.SCOPES`' table go to the notes beside it, and the window's static
account: of the (q block, kv block) pairs of one (batch, head) how many a call
runs at all is the kernels' to say; here, how many calls of each kernel ran."""

import re
from collections import Counter

from benchmarks import moe_reduce, trace_reduce

KERNEL = r"^_window_flash_"
SCOPES = {
    "attn_window": ("qkv", "gate", "rope", "core", "out"),
    "attn": ("qkv", "gate"),
}


def read(run: dict) -> float | None:
    rows = run.get("trace_rows")
    per_device = (run.get("trace") or {}).get("per_device")
    if not rows or not per_device or not per_device[0].get("programs"):
        return None
    device = trace_reduce.devices(rows)[0]
    seconds, calls = trace_reduce.kernel_seconds(rows, device, KERNEL)
    if not calls:
        return None
    programs = per_device[0]["programs"]
    kernels = Counter(
        re.sub(r"\.\d+$", "", name) for _, _, name in trace_reduce.op_intervals(
            rows, device, keep=lambda name: bool(re.search(KERNEL, name))
        )
    )
    table = {
        f"{parent}/{scope}": moe_reduce.scope_ms_per_step(run, (parent, scope))
        for parent, scopes in SCOPES.items() for scope in scopes
    }
    notes = run.setdefault("notes", {})
    notes["window_scope_ms_per_step"] = {k: v for k, v in table.items() if v is not None}
    notes["window_attention_kernels"] = {
        "calls_per_step": {k: v / programs for k, v in kernels.items()}, "programs": programs,
    }
    return 1e3 * seconds / programs
