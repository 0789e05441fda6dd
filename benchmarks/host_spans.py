"""The program's spans beside the device's operations: which `fit.*` seam of
the training thread each idle gap of device 0 lies under.

Host rows are `[thread, name, start_ns, duration_ns]` in the capture's time
base (nanoseconds from the capture's start, as `trace_reduce`'s device rows
are).  They come from one of two places, the first that has them:

- the capture's `/host:CPU` plane, where `obs.tracing.span` puts a
  `TraceAnnotation` per span when the host tracer is on;
- the program's own list, `obs.tracing.recent_spans()`, stamped on the wall
  clock, less the capture's `profile_start_time` (the `Task Environment`
  plane).  `benchmarks/recorder.py` starts its capture with the host tracer
  off (the host plane held millions of PJRT events), so this is what a
  benchmark run reads.

A program without the spans (the parent of the PR that added them) gives no
rows, and every reader here returns None.

The device's clock and the host's are brought together by the runtime, not
exactly.  Two things cannot happen: a `fit.sync` ends before the device
finished the work it waited for, and the device starts a program before the
`fit.dispatch` that enqueued it began.  Over the gaps where the device
waited, these bracket the shift that host times need; `attribute` reports
the bracket and the raw lag of every sync's end behind the device's last
operation, and shifts the host rows by the bracket's middle only where the
unshifted clocks break one of the two.
"""

from __future__ import annotations

import bisect
from pathlib import Path

from benchmarks import trace_reduce

HOST_PLANE = "/host:CPU"
ENVIRONMENT_PLANE = "Task Environment"
PREFIXES = ("fit.", "prefetch.")
STEP = "fit.step"
# A gap this long is the device waiting for the host, not the few
# microseconds between two queued programs.
WAIT_NS = 100_000


def newest_capture(trace_dir: str | Path) -> Path:
    """The newest `.xplane.pb` under `trace_dir`."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_rows(trace_dir: str | Path) -> tuple[list[list], str] | None:
    """(host rows, where they came from), or None where neither the capture
    nor the program has any."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(newest_capture(trace_dir)))
    rows, started = [], None
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                rows += [
                    [line.name, e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith(PREFIXES)
                ]
        elif plane.name == ENVIRONMENT_PLANE:
            started = dict(plane.stats).get("profile_start_time")
    if any(r[1] == STEP for r in rows):
        return rows, "host_plane"
    from deeplearning_cfn_tpu.obs import tracing

    recent = getattr(tracing, "recent_spans", None)
    if recent is None or started is None:
        return None
    rows = [
        [thread, name, start - int(started), duration]
        for thread, name, start, duration in recent()
        if name.startswith(PREFIXES)
    ]
    return (rows, "program") if any(r[1] == STEP for r in rows) else None


def training_thread(rows: list[list]):
    """The thread whose line holds the `fit.step` spans."""
    threads = {r[0] for r in rows if r[1] == STEP}
    if len(threads) != 1:
        raise ValueError(f"fit.step on {len(threads)} threads")
    return threads.pop()


def device_gaps(device_rows: list[list], device: int = 0) -> tuple[list[tuple[int, int]], tuple[int, int]]:
    """(idle gaps, traced window) of one device, from `trace_reduce`'s own
    union of its operations."""
    busy = trace_reduce.union(trace_reduce.op_intervals(device_rows, device))
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:])]
    return gaps, (busy[0][0], busy[-1][1])


def overlap(gaps: list[tuple[int, int]], spans: list[tuple[int, int]]) -> int:
    """Nanoseconds of the (disjoint, sorted) gaps that the spans cover."""
    return trace_reduce.total(gaps) - trace_reduce.total(
        trace_reduce.subtract(gaps, trace_reduce.union(spans))
    )


def clock_bracket(gaps, seams: dict[str, list[tuple[int, int]]]) -> dict:
    """What the two impossibilities say about the shift host times need, over
    the gaps where the device waited for a sync's return and the dispatch
    after it."""
    waits = [g for g in gaps if g[1] - g[0] >= WAIT_NS]
    starts = [g[0] for g in waits]
    dispatches = sorted(seams.get("fit.dispatch", []))
    dispatch_starts = [d[0] for d in dispatches]
    lags, low, high = [], [], []
    for _, sync_end in sorted(seams.get("fit.sync", [])):
        # The wait this sync's return ended: the one whose start is nearest.
        i = bisect.bisect_left(starts, sync_end)
        near = [j for j in (i - 1, i) if 0 <= j < len(waits)]
        if not near:
            continue
        gap = waits[min(near, key=lambda j: abs(starts[j] - sync_end))]
        if abs(gap[0] - sync_end) > 20_000_000:
            continue  # no wait near this sync: it lies outside the window
        lags.append(sync_end - gap[0])
        low.append(gap[0] - sync_end)  # shift >= this: the sync ends after the device
        k = bisect.bisect_left(dispatch_starts, sync_end)
        if k < len(dispatches):
            high.append(gap[1] - dispatch_starts[k])  # shift <= this
    if not low or not high:
        return {"syncs": len(lags), "shift_ns": 0}
    lo, hi = max(low), min(high)
    shift = 0 if lo <= 0 <= hi else (lo + hi) // 2
    return {
        "syncs": len(lags),
        "sync_lag_ms": [min(lags) / 1e6, sorted(lags)[len(lags) // 2] / 1e6, max(lags) / 1e6],
        "bracket_ms": [lo / 1e6, hi / 1e6],
        "shift_ns": shift,
    }


def attribute(device_rows: list[list], host_rows: list[list], device: int = 0) -> dict:
    """Device 0's idle time in the traced window by the seam of the training
    thread that covers it: the leaf seams by name, `fit.step` for what lies
    under the step's span and no leaf, `unattributed` for the rest."""
    gaps, window = device_gaps(device_rows, device)
    thread = training_thread(host_rows)
    seams: dict[str, list[tuple[int, int]]] = {}
    for t, name, start, duration in host_rows:
        if t == thread and name.startswith("fit."):
            seams.setdefault(name, []).append((start, start + duration))
    clocks = clock_bracket(gaps, seams)
    shift = clocks["shift_ns"]
    if shift:
        seams = {n: [(a + shift, b + shift) for a, b in v] for n, v in seams.items()}
    idle = trace_reduce.total(gaps)
    by_span = {
        name: overlap(gaps, spans) for name, spans in sorted(seams.items()) if name != STEP
    }
    under_any = overlap(gaps, [i for spans in seams.values() for i in spans])
    by_span[STEP] = under_any - sum(by_span.values())
    return {
        "idle_ns": idle,
        "window_ns": window[1] - window[0],
        "by_span_ns": by_span,
        "unattributed_ns": idle - under_any,
        "clocks": clocks,
        "seam_counts": {n: len(v) for n, v in seams.items()},
    }


def attributed(run: dict) -> dict | None:
    """The run's attribution, once; its table goes to the notes.  A test (or
    a recording) puts host rows under `host_rows`."""
    if "host_spans" not in run:
        out = None
        rows = run.get("trace_rows")
        if rows and "host_rows" not in run and run.get("trace_dir"):
            loaded = load_rows(run["trace_dir"])
            if loaded is not None:
                run["host_rows"], run["host_rows_origin"] = loaded
        if rows and run.get("host_rows"):
            out = attribute(rows, run["host_rows"])
            out["programs"] = run["trace"]["per_device"][0]["programs"]
            out["origin"] = run.get("host_rows_origin", "given")
            run.setdefault("notes", {})["host_spans"] = {
                "origin": out["origin"],
                "idle_ms": out["idle_ns"] / 1e6,
                "by_span_ms": {n: v / 1e6 for n, v in out["by_span_ns"].items()},
                "unattributed_ms": out["unattributed_ns"] / 1e6,
                "clocks": out["clocks"],
                "programs": out["programs"],
            }
        run["host_spans"] = out
    return run["host_spans"]


def idle_ms_per_step(run: dict, names: tuple[str, ...]) -> float | None:
    """Idle milliseconds of device 0 under the named seams, per executed
    program of the traced window."""
    out = attributed(run)
    if out is None or not out["programs"]:
        return None
    return sum(out["by_span_ns"].get(n, 0) for n in names) / 1e6 / out["programs"]


def has_device_trace(run: dict) -> bool:
    """Whether the run took a capture that holds a device plane.  The
    readers of the program's aggregates and counters report beside one only:
    a run without (no `--trace 1`, or the tests' runs on the CPU) is not a
    measurement of the chip's host."""
    return bool((run.get("trace") or {}).get("per_device"))


def first_step_counters(run: dict) -> dict | None:
    """The compile counters `Trainer.fit` froze when the first step completed
    (`first_step.compile.*` of `obs.tracing.counters()`), without the prefix:
    `{name: {"count": n, "total": seconds}}`, to the notes too.  None where
    the program keeps none."""
    from deeplearning_cfn_tpu.obs import tracing

    read_counters = getattr(tracing, "counters", None)
    if read_counters is None or not has_device_trace(run):
        return None
    prefix = "first_step.compile."
    frozen = {
        name[len(prefix):]: value for name, value in read_counters().items()
        if name.startswith(prefix)
    }
    if not frozen:
        return None
    run.setdefault("notes", {})["first_step_compile"] = frozen
    return frozen
