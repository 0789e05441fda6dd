"""Trainer: median, in ms, of the intervals between consecutive step
completions in the window, on the host clock."""

from benchmarks.recorder import percentile, window_step_seconds


def read(run: dict) -> float | None:
    steps = window_step_seconds(run)
    return 1e3 * percentile(steps, 50) if steps else None
