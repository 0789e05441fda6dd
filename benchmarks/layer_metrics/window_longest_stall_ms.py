"""Trainer: the largest single max(0, interval - steps x m) among the timed
window's drains, in milliseconds (`window_drains.py`): the runs' scatter is
made by single events, so the maximum is the number that follows it."""

from benchmarks import window_drains


def read(run: dict) -> float | None:
    return window_drains.published(run, "longest_stall_ms")
