"""Trainer: the garbage collector's pauses (the program's `gc.callbacks`
hook, as each drain's row holds them) over the timed window's drains, per
step drained, in milliseconds (`window_drains.py`)."""

from benchmarks import window_drains


def read(run: dict) -> float | None:
    return window_drains.published(run, "gc_ms_per_step")
