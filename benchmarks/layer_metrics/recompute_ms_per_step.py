"""Trainer: device time of the forward pass computed again inside the backward one (`rematted_computation`); nothing to read where nothing is rematerialised, per executed program of the traced window
on device 0, in milliseconds (`scope_reduce.py` has the rule)."""

from benchmarks import scope_reduce


def read(run: dict) -> float | None:
    return scope_reduce.class_ms_per_step(run, "recompute")
