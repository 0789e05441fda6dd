"""Trainer: 95th percentile, in ms, of the intervals between consecutive
step completions in the window, on the host clock.  One reading is off by
some half a millisecond, which is why this is no end-to-end metric; the
median and the sample count go on an earlier line."""

from benchmarks.recorder import percentile, window_step_seconds


def read(run: dict) -> float | None:
    steps = window_step_seconds(run)
    if not steps:
        return None
    run.setdefault("notes", {})["train_step_ms"] = {
        "p50": 1e3 * percentile(steps, 50), "p95": 1e3 * percentile(steps, 95),
        "max": 1e3 * max(steps), "samples": len(steps),
    }
    return 1e3 * percentile(steps, 95)
