"""Kernels: the latent experts' grouped matmuls' share of their roofline, in
percent.

Denominator: device time under the scope `moe/experts` per step (the grouped
matmul kernels of every pass the step makes, and the relu^2 and the masks
between them).  Numerator: the least time those passes can take for the
assignments the program *counted* (`moe.assignments_held`, a step's sum over
the routed blocks), `flops/latent_experts.py`: two matmuls of `moe_latent_size`
x `moe_intermediate_size` a row; one backward pass, and as many forward passes
as the trace holds (a rematerialised block makes two), found from whether any
operation under the scope is rematerialised.  The larger of FLOPs over the
chip's bf16 peak and bytes over its HBM peak; the notes say which."""

from benchmarks import moe_reduce, scope_reduce


def read(run: dict) -> float | None:
    measured_ms = moe_reduce.scope_ms_per_step(run, ("moe", "experts"))
    counted = moe_reduce.routing(run)
    config = run["config"]
    if not measured_ms or not counted or "moe_latent_size" not in config:
        return None
    latent, m = int(config["moe_latent_size"]), int(config["moe_intermediate_size"])
    blocks = config["hybrid_override_pattern"].count("E")
    recomputed = any(
        "rematted_computation" in name and scope_reduce.has_scope(name, "experts")
        for name in scope_reduce.op_names(run).values()
    )
    passes = dict(forward_passes=2 if recomputed else 1, backward_passes=1)
    cost = run["manifest"].module("flops", "latent_experts")
    assignments = counted["moe.assignments_held"]
    compute = cost.flops(assignments, latent, m, **passes) / run["peaks"]["bf16_flops_per_s"]
    memory = cost.bytes_moved(
        assignments, blocks * int(config["n_routed_experts"]), latent, m, **passes
    ) / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["latent_experts_roofline"] = {
        "bound": "compute" if compute >= memory else "memory", **passes,
        "assignments_held_per_step": assignments, "least_ms_per_step": 1e3 * max(compute, memory),
        "measured_ms_per_step": measured_ms,
    }
    return 100.0 * 1e3 * max(compute, memory) / measured_ms
