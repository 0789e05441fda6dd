"""Trainer: device 0's idle time under `fit.sync` (the host waits for the
losses) and `fit.log` (logger, `should_save`, `stop_fn`), per executed
program of the traced window, in milliseconds."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    return host_spans.idle_ms_per_step(run, ("fit.sync", "fit.log"))
