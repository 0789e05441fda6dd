"""Experts: device time of what routing costs beside the matmuls (`moe/router`,
`moe/dispatch`: the sort and the gather into the buffer, `moe/combine`: the
weighted sum back per token), per executed program of the traced window on
device 0, in milliseconds."""

from benchmarks import moe_reduce


def read(run: dict) -> float | None:
    return moe_reduce.scope_ms_per_step(run, ("moe",), ("router", "dispatch", "combine"))
