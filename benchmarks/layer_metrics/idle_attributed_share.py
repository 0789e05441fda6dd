"""Trainer: the share of device 0's idle time in the traced window that lies
under a `fit.*` span of the training thread, in percent.  What is left over is
time the host spent under no name of the program's (`host_spans.py`)."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    out = host_spans.attributed(run)
    if out is None or not out["idle_ns"]:
        return None
    return 100.0 * (1.0 - out["unattributed_ns"] / out["idle_ns"])
