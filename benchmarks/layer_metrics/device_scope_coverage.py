"""Device: busy time of the operations that `scope_reduce.py` puts in a class
over the busy time of all of them, device 0, in percent.  It guards the
per-class times: what it leaves out they do not count."""

from benchmarks import scope_reduce


def read(run: dict) -> float | None:
    out = scope_reduce.reduced(run)
    if out is None or not out["busy_s"]:
        return None
    return 100.0 * out["classified_s"] / out["busy_s"]
