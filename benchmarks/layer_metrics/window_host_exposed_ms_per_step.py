"""Trainer: the host's exposed segments (from a drain's return to the end of
the next dispatch, the device's queue empty) over the timed window's drains,
per step drained, in milliseconds (`window_drains.py`): the host-side twin
of the `idle_in_*` metrics, over the whole window."""

from benchmarks import window_drains


def read(run: dict) -> float | None:
    return window_drains.published(run, "host_exposed_ms_per_step")
