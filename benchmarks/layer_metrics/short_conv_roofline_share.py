"""Kernels: the gated short convolution's core's share of its roofline, in
percent.

Denominator: device time under the scope `conv/core` per step (the two gates
and the taps of every `conv` layer, every pass the step makes).  Numerator: the
least time those passes can take, `flops/short_conv.py`: per `conv` layer one
backward pass and as many forward passes as the trace holds (a rematerialised
block makes two), found from whether any operation under the scope is
rematerialised.  The larger of bytes over the chip's HBM peak and FLOPs over its
bf16 peak (the first, by far); the notes say which."""

from benchmarks import moe_reduce, scope_reduce


def read(run: dict) -> float | None:
    measured_ms = moe_reduce.scope_ms_per_step(run, ("conv", "core"))
    config, traffic = run["config"], run["traffic"]
    if not measured_ms or "conv" not in config.get("layer_types", ()):
        return None
    recomputed = any(
        "rematted_computation" in name and scope_reduce.has_scope(name, "conv")
        and scope_reduce.has_scope(name, "core")
        for name in scope_reduce.op_names(run).values()
    )
    passes = dict(forward_passes=2 if recomputed else 1, backward_passes=1)
    layers = list(config["layer_types"]).count("conv")
    tokens = int(traffic["global_batch"]) // run["chips"] * int(traffic["seq_len"])
    d = int(config["hidden_size"])
    cost = run["manifest"].module("flops", "short_conv")
    memory = layers * cost.bytes_moved(tokens, d, **passes) / run["peaks"]["hbm_bytes_per_s"]
    compute = (
        layers * cost.flops(tokens, d, int(config["conv_L_cache"]), **passes)
        / run["peaks"]["bf16_flops_per_s"]
    )
    run.setdefault("notes", {})["short_conv_roofline"] = {
        "bound": "memory" if memory >= compute else "compute", **passes, "conv_layers": layers,
        "least_ms_per_step": 1e3 * max(memory, compute), "measured_ms_per_step": measured_ms,
    }
    return 100.0 * 1e3 * max(memory, compute) / measured_ms
