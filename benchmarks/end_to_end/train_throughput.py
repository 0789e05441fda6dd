"""Examples completed in the measured window / window seconds / chips.  An
example is one image, or one sequence of the cell's length.  Source: the
benchmark's own host-clock completion times."""

from benchmarks.recorder import throughput


def read(run: dict) -> float:
    return throughput(run)
