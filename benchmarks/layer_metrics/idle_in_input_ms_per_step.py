"""Input pipeline: device 0's idle time under `fit.data_wait` (`next()` on
the batch source) and `fit.h2d` (`device_put_tree`), per executed program of
the traced window, in milliseconds."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    return host_spans.idle_ms_per_step(run, ("fit.data_wait", "fit.h2d"))
