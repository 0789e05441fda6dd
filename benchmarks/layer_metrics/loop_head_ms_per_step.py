"""Trainer: device time of a looped model's heads and of the objective that
mixes them (everything under the scope `loop_head`, one pass's final norm,
head, cross-entropy and exit gate, as many times a step as the stack runs,
and under `exit_mix`; forward, recomputed and backward), per executed program
of the traced window on device 0, in milliseconds.  The parts go to the notes
by scope."""

from benchmarks import moe_reduce

PARTS = ("final_norm", "head", "xent", "exit_gate")


def read(run: dict) -> float | None:
    total = moe_reduce.scope_ms_per_step(run, (), ("loop_head", "exit_mix"))
    if total is None:
        return None
    table = {
        f"loop_head/{part}": moe_reduce.scope_ms_per_step(run, ("loop_head", part))
        for part in PARTS
    }
    table["exit_mix"] = moe_reduce.scope_ms_per_step(run, ("exit_mix",))
    run.setdefault("notes", {})["loop_head_scope_ms_per_step"] = {
        k: v for k, v in table.items() if v is not None
    }
    return total
