"""Spans and counters: the program's one tracing primitive.

``with span("checkpoint", step=3): ...`` times a block on the host and

- folds the duration into a process-wide per-name aggregate (count /
  total / max / last), which ``dlcfn status --format prom`` exports;
- keeps the span in a bounded list of recent spans with its start on the
  wall clock (``time.time_ns``, the clock a ``jax.profiler`` capture
  stamps its start with), so a reader can lay it beside a device trace
  taken in the same process (``recent_spans``);
- enters a ``jax.profiler.TraceAnnotation`` of the same name and
  attributes for the block, when ``jax`` is already imported: in a
  capture whose host tracer is on, the span sits on the calling thread's
  line of the ``/host:CPU`` plane, in the time base of the device's
  operations.  With no capture running that is one atomic check.  ``obs``
  itself never imports jax (the broker and the CLI stay light);
- records a ``span`` event on the flight recorder, unless ``journal`` is
  false: per-step seams fold and annotate only, or five lines a step would
  turn the recorder's ring over in under a minute.

A span measures *host* time.  Around an asynchronously dispatched jitted
call it is the time to enqueue, not the device's; where the host waits
for the device (``fit.sync``) is where device time surfaces
(docs/OBSERVABILITY.md).

``counter(name, value)`` folds a value that is not a block's duration
(a compile's seconds as JAX reports them, a cache hit) into a per-name
count and total; ``freeze_counters`` copies a family of them aside.

``Drains`` keeps one row per drain of a loop that feeds a device
(``recent_drains``): when the host's wait for the device returned, on the
wall clock and on ``perf_counter``, how long since the drain before, with
the collector's pauses, the thread's involuntary context switches and its
major faults over that time, and how long the thread took from the return
to the next dispatch's end, while the device's queue was empty.  It
records; whoever owns the loop judges (``train/trainer.py``'s
``_FitSeams`` journals a ``stall``).  The collector's pauses come from one
``gc.callbacks`` hook (``install_gc_hook``): the counter ``gc.pause_s``,
and a span ``host.gc`` among the recent ones for a pause of a millisecond
or more.

Aggregates, counters, recent spans and drains are process-wide and
outlive the trainer that fed them.  The budget is the train-step hot
path: two clock reads, one dict update under a lock, one append, and the
annotation; a drain adds two more clock reads and one ``getrusage``.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Iterator

from deeplearning_cfn_tpu.obs.recorder import FlightRecorder, get_recorder

#: How many spans ``recent_spans`` looks back over: minutes of a training
#: loop's seams, a few hundred kilobytes.
RECENT_SPANS = 16384

#: How many drains ``recent_drains`` looks back over: an hour of a loop
#: that drains twice a second, a megabyte or two.
RECENT_DRAINS = 8192
#: A collector's pause from this long on is kept as a ``host.gc`` span: it
#: is visible beside a drain's few milliseconds, and young collections
#: (tens of microseconds, hundreds a minute) would crowd the seams out.
GC_SPAN_S = 1e-3

# Read through the module so that a test can put a virtual clock in.
_perf_counter = time.perf_counter
_time_ns = time.time_ns

try:  # the calling thread's own account; Linux has it, not every platform
    import resource

    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):
    _RUSAGE_THREAD = None


@dataclass
class SpanStats:
    """Running aggregate for one span name."""

    count: int = 0
    errors: int = 0
    total_s: float = 0.0
    max_s: float = 0.0
    last_s: float = 0.0

    def fold(self, seconds: float, ok: bool) -> None:
        self.count += 1
        if not ok:
            self.errors += 1
        self.total_s += seconds
        self.max_s = max(self.max_s, seconds)
        self.last_s = seconds

    def as_dict(self) -> dict[str, Any]:
        return {
            "count": self.count,
            "errors": self.errors,
            "total_s": round(self.total_s, 6),
            "max_s": round(self.max_s, 6),
            "last_s": round(self.last_s, 6),
        }


_aggregates: dict[str, SpanStats] = {}
_counters: dict[str, list[float]] = {}  # name -> [count, total]
_recent: deque[tuple[int, str, int, int]] = deque(maxlen=RECENT_SPANS)
_drains: deque[dict[str, Any]] = deque(maxlen=RECENT_DRAINS)
_lock = threading.Lock()
# The collector's hook runs between any two bytecodes of any thread, also
# inside a block that holds ``_lock``: it takes no lock and writes nothing
# but these two and (one atomic append) ``_recent``.
_gc_totals = [0, 0.0]  # collections, their seconds
_gc_started: list[Any] = [None, 0]  # perf_counter and time_ns of the one running


def _annotation(name: str, step_num: int | None, attrs: dict[str, Any]):
    """The profiler's annotation for a span, or None where jax is not
    loaded.  Attribute values the profiler cannot encode go as text."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    attrs = {
        k: v if isinstance(v, (str, int, float)) else str(v) for k, v in attrs.items()
    }
    if step_num is not None:
        return profiler.StepTraceAnnotation(name, step_num=int(step_num), **attrs)
    return profiler.TraceAnnotation(name, **attrs)


@contextmanager
def span(
    name: str,
    recorder: FlightRecorder | None = None,
    *,
    journal: bool | str = True,
    step_num: int | None = None,
    **attrs: Any,
) -> Iterator[None]:
    """Time a block: aggregate, recent spans, profiler annotation and,
    unless ``journal`` is false, one ``span`` event.  ``journal`` may name
    the event's span differently from the annotation (``fit.step`` is
    journalled as ``train_step``, the name the journal's readers know).
    ``step_num`` makes the annotation a step annotation, which profile
    viewers group device work by."""
    annotation = _annotation(name, step_num, attrs)
    if annotation is not None:
        annotation.__enter__()
    start_ns = _time_ns()
    t0 = _perf_counter()
    ok = True
    try:
        yield
    except BaseException:
        ok = False
        raise
    finally:
        seconds = _perf_counter() - t0
        if annotation is not None:
            annotation.__exit__(None, None, None)
        with _lock:
            stats = _aggregates.get(name)
            if stats is None:
                stats = _aggregates[name] = SpanStats()
            stats.fold(seconds, ok)
            _recent.append((threading.get_ident(), name, start_ns, int(seconds * 1e9)))
        if journal:
            (recorder or get_recorder()).record(
                "span",
                span=name if journal is True else journal,
                seconds=round(seconds, 6),
                ok=ok,
                **attrs,
            )


def span_aggregates() -> dict[str, dict[str, Any]]:
    """Snapshot of every span name's running aggregate."""
    with _lock:
        return {name: stats.as_dict() for name, stats in _aggregates.items()}


def recent_spans() -> list[list]:
    """The last ``RECENT_SPANS`` spans of the process as rows
    ``[thread, name, start_ns, duration_ns]``, in the order they ended;
    ``start_ns`` is on the wall clock."""
    with _lock:
        rows = list(_recent)  # one call: the collector's hook may append meanwhile
    return [list(row) for row in rows]


def counter(name: str, value: float = 1.0, count: int = 1) -> None:
    """Fold ``count`` observations that sum to ``value`` into the counter
    ``name`` (negative to take back what a later observation includes)."""
    with _lock:
        entry = _counters.get(name)
        if entry is None:
            entry = _counters[name] = [0, 0.0]
        entry[0] += count
        entry[1] += value


def counters() -> dict[str, dict[str, float]]:
    """Snapshot ``{name: {"count": n, "total": x}}`` of every counter."""
    with _lock:
        out = {
            name: {"count": int(count), "total": total}
            for name, (count, total) in _counters.items()
        }
    collections, seconds = _gc_totals
    if collections:
        out["gc.pause_s"] = {"count": collections, "total": seconds}
    return out


def freeze_counters(prefix: str, under: str) -> None:
    """Copy every counter whose name starts with ``prefix`` to
    ``under + name`` as it stands now, replacing an earlier copy: what had
    been counted when some point was reached (``first_step.``), apart
    from whatever the process counts afterwards."""
    with _lock:
        for name in [n for n in _counters if n.startswith(under)]:
            del _counters[name]
        for name, entry in list(_counters.items()):
            if name.startswith(prefix):
                _counters[under + name] = list(entry)


def reset_aggregates() -> None:
    with _lock:
        _aggregates.clear()
        _counters.clear()
        _recent.clear()
        _drains.clear()
        _gc_totals[:] = [0, 0.0]


def _on_gc(phase: str, info: dict[str, Any]) -> None:
    """``gc.callbacks``: time one collection on the thread that runs it."""
    if phase == "start":
        _gc_started[:] = [_perf_counter(), _time_ns()]
        return
    t0, start_ns = _gc_started
    if t0 is None:  # installed while this collection ran
        return
    _gc_started[0] = None
    seconds = _perf_counter() - t0
    _gc_totals[0] += 1
    _gc_totals[1] += seconds
    if seconds >= GC_SPAN_S:
        _recent.append((threading.get_ident(), "host.gc", start_ns, int(seconds * 1e9)))


def install_gc_hook() -> None:
    """Have the collector report its pauses here; once, however often called."""
    with _lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def _thread_usage() -> tuple[int, int] | None:
    """The calling thread's involuntary context switches and major page
    faults so far, or None where the platform keeps no account by thread."""
    if _RUSAGE_THREAD is None:
        return None
    usage = resource.getrusage(_RUSAGE_THREAD)
    return usage.ru_nivcsw, usage.ru_majflt


def spans_between(thread: int, start_ns: int, end_ns: int) -> dict[str, float]:
    """Seconds of ``thread``'s recent spans that began in [start_ns, end_ns),
    by name."""
    with _lock:
        rows = list(_recent)
    out: dict[str, float] = {}
    for t, name, start, duration in rows:
        if t == thread and start_ns <= start < end_ns:
            out[name] = out.get(name, 0.0) + duration / 1e9
    return out


class Drains:
    """One loop's drains: ``returned`` where a wait for the device that
    emptied the loop's pending results ends, ``dispatched`` at the end of the
    dispatch after it (while ``open`` is set).  One row a drain in
    ``recent_drains()``:

    - ``thread``, ``step`` (the global step the drain completed), ``steps``
      (how many it drained);
    - ``sync_end_ns`` / ``sync_end_s``: the wait's return on ``time.time_ns``,
      a device trace's clock, and on ``perf_counter``, the clock intervals
      are timed with: the row is the bridge between the two;
    - ``interval_s``: since the drain before returned, with the collector's
      pauses (``gc_s``), the thread's involuntary context switches
      (``nivcsw``) and major faults (``majflt``) over it; None on a loop's
      first drain, the two counts also where the platform has none;
    - ``exposed_s``: from the return to the end of the next dispatch, the
      host's segment that the device waits out one for one, with the
      collector's pauses inside it (``exposed_gc_s``); None until that
      dispatch ends, so on a loop's last drain.  The segment lies in the
      *next* drain's interval.
    """

    def __init__(self) -> None:
        install_gc_hook()
        self.open: dict[str, Any] | None = None  # the row whose segment is running
        self.last: dict[str, Any] | None = None  # the newest row
        # the collector's seconds and the thread's usage at that row's return
        self._gc_s, self._usage = 0.0, None

    def returned(self, step: int, steps: int) -> dict[str, Any] | None:
        """The row of the drain that just ended, or None if it drained nothing."""
        if steps <= 0:
            return None
        now, now_ns = _perf_counter(), _time_ns()
        gc_s, usage, before = _gc_totals[1], _thread_usage(), self.last
        row: dict[str, Any] = {
            "thread": threading.get_ident(), "step": step, "steps": steps,
            "sync_end_ns": now_ns, "sync_end_s": now,
            "interval_s": None, "gc_s": None, "nivcsw": None, "majflt": None,
            "exposed_s": None, "exposed_gc_s": None,
        }
        if before is not None:
            row["interval_s"] = now - before["sync_end_s"]
            # at least 0: ``reset_aggregates`` zeroes the totals under a live loop
            row["gc_s"] = max(0.0, gc_s - self._gc_s)
            if usage is not None and self._usage is not None:
                row["nivcsw"] = usage[0] - self._usage[0]
                row["majflt"] = usage[1] - self._usage[1]
        self._gc_s, self._usage = gc_s, usage
        self.last = self.open = row
        with _lock:
            _drains.append(row)
        return row

    def dispatched(self) -> None:
        row, self.open = self.open, None
        if row is None:
            return
        row["exposed_s"] = _perf_counter() - row["sync_end_s"]
        row["exposed_gc_s"] = max(0.0, _gc_totals[1] - self._gc_s)


def recent_drains() -> list[dict[str, Any]]:
    """The last ``RECENT_DRAINS`` drains of the process's loops, a copy of
    each row (``Drains``), in the order they returned."""
    with _lock:
        return [dict(row) for row in _drains]
