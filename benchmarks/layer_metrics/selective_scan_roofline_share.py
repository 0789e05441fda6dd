"""Kernels: the selective scan's share of its roofline, in percent.

Denominator: device time under the scope `ssm/scan` per step (of every Mamba-1
layer, every pass the step makes).  Numerator: the least time those passes can
take, `flops/selective_scan.py`, which counts the recurrence and not the
program: per Mamba layer one backward pass and as many forward passes as the
trace holds: one more where a checkpoint around the scope recomputes its
operations (the layer's).  The larger of bytes over the chip's HBM peak and
FLOPs over its bf16 peak; the notes say which."""

from benchmarks import moe_reduce, scope_reduce


def read(run: dict) -> float | None:
    config, traffic = run["config"], run["traffic"]
    if "mamba_d_state" not in config:
        return None
    measured_ms = moe_reduce.scope_ms_per_step(run, ("ssm", "scan"))
    layers = run["manifest"].module("flops", "mamba_attn").kinds(config).count("mamba")
    if not measured_ms or not layers:
        return None
    under = [
        name.partition("ssm") for name in scope_reduce.op_names(run).values()
        if scope_reduce.has_scope(name, "ssm") and scope_reduce.has_scope(name, "scan")
    ]
    recomputed = any("rematted_computation" in part[0] for part in under)
    passes = dict(forward_passes=1 + recomputed, backward_passes=1)
    tokens = int(traffic["global_batch"]) // run["chips"] * int(traffic["seq_len"])
    channels = int(config["mamba_expand"]) * int(config["hidden_size"])
    state = int(config["mamba_d_state"])
    cost = run["manifest"].module("flops", "selective_scan")
    memory = layers * cost.bytes_moved(tokens, channels, state, **passes) / run["peaks"]["hbm_bytes_per_s"]
    compute = layers * cost.flops(tokens, channels, state, **passes) / run["peaks"]["bf16_flops_per_s"]
    run.setdefault("notes", {})["selective_scan_roofline"] = {
        "bound": "memory" if memory >= compute else "compute", **passes, "mamba_layers": layers,
        "least_ms_per_step": 1e3 * max(memory, compute), "measured_ms_per_step": measured_ms,
    }
    return 100.0 * 1e3 * max(memory, compute) / measured_ms
