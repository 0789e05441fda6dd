"""Kernels: the state-space scan's share of its roofline, in percent.

Denominator: device time under the scope `ssm/scan` per step (everything of
`ops/ssd.py` and dt's softplus, of every `M` block, every pass the step makes).
Numerator: the least time those passes can take, `flops/ssd.py`, which counts
the recurrence and not the program: per `M` block one backward pass and as many
forward passes as the trace holds: one more where a checkpoint around the scope
recomputes its operations (the block's) and one more where a checkpoint inside
it does (a tile's).
The larger of bytes over the chip's HBM peak and FLOPs over its bf16 peak; the
notes say which."""

from benchmarks import moe_reduce, scope_reduce


def read(run: dict) -> float | None:
    measured_ms = moe_reduce.scope_ms_per_step(run, ("ssm", "scan"))
    config, traffic = run["config"], run["traffic"]
    blocks = config.get("hybrid_override_pattern", "").count("M")
    if not measured_ms or not blocks:
        return None
    # The scope's operations recomputed by a checkpoint around the scope (the
    # block's), and by one inside it (a tile's): a forward pass more for each.
    under = [
        name.partition("ssm") for name in scope_reduce.op_names(run).values()
        if scope_reduce.has_scope(name, "ssm") and scope_reduce.has_scope(name, "scan")
    ]
    levels = sum(
        any("rematted_computation" in part[side] for part in under) for side in (0, 2)
    )
    passes = dict(forward_passes=1 + levels, backward_passes=1)
    tokens = int(traffic["global_batch"]) // run["chips"] * int(traffic["seq_len"])
    heads, head_dim = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    groups, state = int(config["n_groups"]), int(config["ssm_state_size"])
    cost = run["manifest"].module("flops", "ssd")
    memory = (
        blocks * cost.bytes_moved(tokens, heads, head_dim, groups, state, **passes)
        / run["peaks"]["hbm_bytes_per_s"]
    )
    compute = (
        blocks * cost.flops(tokens, heads, head_dim, state, **passes)
        / run["peaks"]["bf16_flops_per_s"]
    )
    run.setdefault("notes", {})["ssm_scan_roofline"] = {
        "bound": "memory" if memory >= compute else "compute", **passes, "ssm_blocks": blocks,
        "least_ms_per_step": 1e3 * max(memory, compute), "measured_ms_per_step": measured_ms,
    }
    return 100.0 * 1e3 * max(memory, compute) / measured_ms
