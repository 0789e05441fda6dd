"""Trainer: the share of the timed window that its slow drains took beyond a
median drain: the sum over the window's drains of max(0, interval - steps x
m) over the sum of the intervals, in percent (`window_drains.py`; `m` is the
median of a drain's seconds a step).  The whole window, not the traced two
seconds; the table behind it is in every run's notes."""

from benchmarks import window_drains


def read(run: dict) -> float | None:
    return window_drains.published(run, "lost_share")
