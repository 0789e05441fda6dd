"""Trainer: device time of one pass of a looped model's block stack, in
milliseconds: everything under the scope `loop_pass` (a pass's layer scan) and
under `attn_bwd` (the flash attention's backward kernels, should their
operations carry their own scope alone), forward, recomputed and backward, per
executed program of the traced window on device 0, over the passes a step runs
(the program's counter `loop.passes`, one observation a step).  It is the
number to hold beside a one-pass decoder's step on the same block.  None where
the program has no such scope or no such counter.  The loop's other counters
(each pass's loss, where the exit gate puts its mass) go to the notes as means
over the run's steps."""

from benchmarks import moe_reduce


def loop_counters() -> dict[str, float]:
    """The program's `loop.*` counters as means per observation."""
    from deeplearning_cfn_tpu.obs import tracing

    read = getattr(tracing, "counters", None)
    return {
        name: entry["total"] / entry["count"]
        for name, entry in (read() if read else {}).items()
        if name.startswith("loop.") and entry["count"]
    }


def read(run: dict) -> float | None:
    under_pass = moe_reduce.scope_ms_per_step(run, ("loop_pass",))
    counted = loop_counters()
    passes = counted.get("loop.passes")
    if under_pass is None or not passes:
        return None
    total = moe_reduce.scope_ms_per_step(run, (), ("loop_pass", "attn_bwd"))
    run.setdefault("notes", {})["loop_pass"] = {
        "passes_per_step": passes, "ms_per_step": total, "under_loop_pass_ms_per_step": under_pass,
        "counters": counted,
    }
    return total / passes
