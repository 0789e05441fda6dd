"""The timed window seen from inside the program: `Trainer.fit`'s drains.

`obs.tracing.recent_drains()` has one row per drain of the training loop:
when the wait for the device returned (`sync_end_s` on `perf_counter`, the
clock `run["times"]` and so the window are on; `sync_end_ns` on the wall
clock a capture is stamped with), the `interval_s` since the drain before,
the `steps` it drained, and the host's *exposed segment* after the return,
up to the end of the next dispatch, which the device waits out one for
one (`exposed_s`, with the collector's pauses in it).  A drain belongs to the window when its return lies between the
window's opening and closing; the window opens and closes on a step, the
rows on a drain, so the two cover times that differ by up to a drain at
each end.

Seconds a step of a drain = `interval_s` / `steps`; `m` is their median over
the window's drains, and a drain's excess is `interval_s - steps * m`.  The
segment that lies inside a drain's interval is the one the drain *before*
opened, so that is the one a slow drain is judged by.

Everything down to `table` is a pure function of rows; the clock is never
read.  A program without the rows (the parent of the PR that added them)
gives None everywhere.
"""

from __future__ import annotations

import bisect

from benchmarks import host_spans
from benchmarks.recorder import percentile

# The leaf seams an exposed segment is split by; what they leave is time
# under `fit.step` alone.  `host.gc` lies inside the seam that ran it.
SEAMS = ("fit.log", "fit.data_wait", "fit.h2d", "fit.dispatch")
GC_SPAN = "host.gc"
SLOWEST = 5
# A device gap and a drain's return are the same event when they lie this
# near on clocks that agree to a millisecond or two; drains are 0.4 s apart.
PAIR_NS = 50_000_000
# A drain within a millisecond of the median drain has nothing to explain.
QUIET_S = 1e-3


def in_window(drains: list[dict], opened_s: float, closed_s: float) -> list[dict]:
    """The drains that returned in the window and have a drain before them."""
    return [
        d for d in drains
        if d["interval_s"] is not None and opened_s <= d["sync_end_s"] <= closed_s
    ]


def median_step_s(drains: list[dict]) -> float:
    return percentile([d["interval_s"] / d["steps"] for d in drains], 50)


def excess_s(drain: dict, m: float) -> float:
    return drain["interval_s"] - drain["steps"] * m


def before(drains: list[dict]) -> dict[int, dict]:
    """id(row) -> the row of the same loop's drain before it."""
    by_end = {(d["thread"], d["step"]): d for d in drains}
    out = {}
    for d in drains:
        prior = by_end.get((d["thread"], d["step"] - d["steps"]))
        if prior is not None and prior["sync_end_s"] < d["sync_end_s"]:
            out[id(d)] = prior
    return out


def split_by_seam(spans: list[list], drain: dict) -> dict[str, float] | None:
    """Milliseconds of the drain's exposed segment by the seam of its thread
    that began in it (`spans`: `recent_spans()` rows sorted by start)."""
    if drain["exposed_s"] is None:
        return None
    start = drain["sync_end_ns"]
    end = start + int(drain["exposed_s"] * 1e9)
    lo = bisect.bisect_left(spans, start, key=lambda r: r[2])
    hi = bisect.bisect_left(spans, end, key=lambda r: r[2])
    out = dict.fromkeys(SEAMS + (GC_SPAN,), 0.0)
    for thread, name, _, duration in spans[lo:hi]:
        if thread == drain["thread"] and name in out:
            out[name] += duration / 1e6
    out[host_spans.STEP] = 1e3 * drain["exposed_s"] - sum(out[n] for n in SEAMS)
    return out


def verdict(
    drain: dict, m: float, prior: dict | None, exposed_median_s: float | None,
    grew: str | None, watcher_interval_s: float | None,
) -> dict:
    """Where a drain's excess went, from the numbers alone.  The collector
    first (its pause also lengthens the seam it ran in), then the host's
    segment inside the interval, then the benchmark's watcher, which stamped
    the same steps' completions on the same clock from another thread."""
    over = excess_s(drain, m)
    if over < QUIET_S:
        return {"verdict": "none"}
    if (drain["gc_s"] or 0.0) >= over / 2:
        return {"verdict": "gc"}
    if prior is not None and prior["exposed_s"] is not None and exposed_median_s is not None:
        if prior["exposed_s"] - exposed_median_s >= over / 2:
            return {"verdict": "host_exposed", "seam": grew}
    if watcher_interval_s is None:
        return {"verdict": "unknown"}
    if watcher_interval_s - drain["steps"] * m < over / 2:
        # the steps completed on time; the thread that waited for them came back late
        return {"verdict": "training_thread_late"}
    return {"verdict": "device_or_machine"}


def latencies(waits: list[tuple[int, int]], drains: list[dict], started_ns: int) -> list[dict]:
    """For each wait of the device (a gap on its own clock, from the capture's
    start) the drain whose return it followed, and the gap less that drain's
    exposed segment (the host's clock alone): what passes between the
    device's last operation and the thread's wake-up, plus from the
    dispatch's end to the device's first operation, with no clock offset in it."""
    out = []
    closed = [d for d in drains if d["exposed_s"] is not None]
    for a, b in waits:
        near = min(closed, key=lambda d: abs(d["sync_end_ns"] - started_ns - a), default=None)
        if near is None or abs(near["sync_end_ns"] - started_ns - a) > PAIR_NS:
            continue
        out.append({
            "step": near["step"], "device_gap_ms": (b - a) / 1e6,
            "exposed_ms": 1e3 * near["exposed_s"],
            "wake_up_plus_launch_ms": (b - a) / 1e6 - 1e3 * near["exposed_s"],
        })
    return out


def summarise(
    drains: list[dict], opened_s: float, closed_s: float, *,
    spans: list[list] = (), steps: list[int] = (), times: list[float] = (),
) -> dict | None:
    """The window's table, or None where fewer than three drains lie in it or
    the oldest row kept is younger than its opening (the list turned over)."""
    if not drains or drains[0]["sync_end_s"] > opened_s:
        return None
    inside = in_window(drains, opened_s, closed_s)
    if len(inside) < 3:
        return None
    m = median_step_s(inside)
    priors = before(drains)
    spans = sorted(spans, key=lambda r: r[2])
    intervals = sum(d["interval_s"] for d in inside)
    drained = sum(d["steps"] for d in inside)
    over = [max(0.0, excess_s(d, m)) for d in inside]
    closed = [d for d in inside if d["exposed_s"] is not None]
    exposed_median = percentile([d["exposed_s"] for d in closed], 50) if closed else None
    splits = [split_by_seam(spans, d) for d in closed]
    seam_medians = {
        name: percentile([s[name] for s in splits], 50) for name in SEAMS + (host_spans.STEP,)
    } if splits else {}
    at = dict(zip(steps, times))
    slowest = []
    for d in sorted(inside, key=lambda d: -excess_s(d, m))[:SLOWEST]:
        prior = priors.get(id(d))
        split = split_by_seam(spans, prior) if prior is not None else None
        grew = max(seam_medians, key=lambda n: split[n] - seam_medians[n]) if split and seam_medians else None
        watched = None
        if d["step"] in at and d["step"] - d["steps"] in at:
            watched = at[d["step"]] - at[d["step"] - d["steps"]]
        slowest.append({
            **d, "excess_ms": 1e3 * excess_s(d, m),
            "exposed_before": None if prior is None else {
                "exposed_s": prior["exposed_s"], "exposed_gc_s": prior["exposed_gc_s"],
                "by_seam_ms": split,
            },
            "watcher_interval_s": watched,
            **verdict(d, m, prior, exposed_median, grew, watched),
        })
    return {
        "drains": len(inside), "steps": drained,
        "interval_sum_s": intervals, "window_s": closed_s - opened_s,
        "median_step_ms": 1e3 * m,
        # the window's first, middle and last drains apart: a step still
        # settling when the window opens reads as lost time in the numbers below
        "median_step_ms_by_third": [
            1e3 * median_step_s(inside[len(inside) * k // 3:len(inside) * (k + 1) // 3]) for k in range(3)
        ],
        "median_exposed_ms": None if exposed_median is None else 1e3 * exposed_median,
        "lost_share": 100.0 * sum(over) / intervals,
        # the arithmetic twin of the throughput: slow drains less fast ones
        "signed_share": 100.0 * sum(excess_s(d, m) for d in inside) / intervals,
        "longest_stall_ms": 1e3 * max(over),
        "host_exposed_ms_per_step": (
            1e3 * sum(d["exposed_s"] for d in closed) / sum(d["steps"] for d in closed)
            if closed else None
        ),
        "gc_ms_per_step": 1e3 * sum(d["gc_s"] for d in inside) / drained,
        "slowest": slowest,
    }


def capture_start_ns(trace_dir: str) -> int | None:
    """The wall-clock start the capture stamped itself with."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(host_spans.newest_capture(trace_dir)))
    for plane in data.planes:
        if plane.name == host_spans.ENVIRONMENT_PLANE:
            return dict(plane.stats).get("profile_start_time")
    return None


def table(run: dict) -> dict | None:
    """The run's table, once; it goes to the notes in every run that closed a
    window, traced or not."""
    if "window_drains" not in run:
        from deeplearning_cfn_tpu.obs import tracing

        out = None
        recent = getattr(tracing, "recent_drains", None)
        if recent is not None and run.get("window"):
            drains = recent()
            opened, closed = run["window"]
            out = summarise(
                drains, run["times"][opened], run["times"][closed],
                spans=tracing.recent_spans(), steps=run["steps"], times=run["times"],
            )
            if out is not None:
                out["attempted"] = closed - opened
                if run.get("trace_rows") and run.get("trace_dir"):
                    started = capture_start_ns(run["trace_dir"])
                    gaps, _ = host_spans.device_gaps(run["trace_rows"])
                    waits = [g for g in gaps if g[1] - g[0] >= host_spans.WAIT_NS]
                    if started is not None:
                        out["traced"] = latencies(waits, drains, int(started))
                run.setdefault("notes", {})["window_drains"] = out
        run["window_drains"] = out
    return run["window_drains"]


def published(run: dict, key: str) -> float | None:
    """One of the table's numbers as a metric: on the chip alone (the table
    itself is in every run's notes)."""
    out = table(run)
    if out is None or run["device"]["platform"] != "tpu":
        return None
    return out[key]
