"""Trainer: device time of the multi-token-prediction module (everything
under the scope `mtp`: the join, its block, its head and loss), per executed
program of the traced window on device 0, in milliseconds."""

from benchmarks import moe_reduce


def read(run: dict) -> float | None:
    return moe_reduce.scope_ms_per_step(run, ("mtp",))
