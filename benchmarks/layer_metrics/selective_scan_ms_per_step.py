"""Kernels: device time of the selective scan (everything under the scope
`ssm/scan` of a Mamba-1 layer: `A = -exp(A_log)`, B and C spread over a lane
tile, the kernels `_selective_scan_forward` / `_selective_scan_backward` or the
plain form in their place, the lane sums of B's and C's gradients; forward,
recomputed and backward), per executed program of the traced window on device
0, in milliseconds.  The scopes this kind of model adds to `moe_reduce.SCOPES`'
table go to the notes beside it, and the two kernels' own times."""

from benchmarks import moe_reduce, trace_reduce

SCOPES = {
    "ssm": ("in_proj", "conv", "x_proj", "bcdt_norm", "dt_proj", "scan", "gate", "out_proj"),
    "attn": ("qkv",),
    "loss": ("ssm_norm",),
}
KERNELS = ("_selective_scan_forward", "_selective_scan_backward")


def read(run: dict) -> float | None:
    total = moe_reduce.scope_ms_per_step(run, ("ssm", "scan"))
    if total is None or "mamba_d_state" not in run["config"]:
        return None
    table = {
        f"{parent}/{scope}": moe_reduce.scope_ms_per_step(run, (parent, scope))
        for parent, scopes in SCOPES.items() for scope in scopes
    }
    notes = {k: v for k, v in table.items() if v is not None}
    rows = run["trace_rows"]
    for kernel in KERNELS:
        seconds, calls = trace_reduce.kernel_seconds(rows, trace_reduce.devices(rows)[0], "^" + kernel)
        if calls:
            notes[kernel] = {"calls": calls, "ms_per_call": 1e3 * seconds / calls}
    run.setdefault("notes", {})["selective_scan_scope_ms_per_step"] = notes
    return total
