"""Experts: device time of the routed-experts layers (everything under the
scope `moe`: router, dispatch, the grouped matmuls, combine, the shared expert;
forward, recomputed and backward), per executed program of the traced window on
device 0, in milliseconds."""

from benchmarks import moe_reduce


def read(run: dict) -> float | None:
    moe_reduce.scope_table(run)  # the step by scope, to the notes
    return moe_reduce.scope_ms_per_step(run, ("moe",))
