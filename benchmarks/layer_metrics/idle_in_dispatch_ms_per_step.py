"""Trainer: device 0's idle time under `fit.dispatch` (the call of the jitted
step, until the program is enqueued and the device starts it), per executed
program of the traced window, in milliseconds."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    return host_spans.idle_ms_per_step(run, ("fit.dispatch",))
