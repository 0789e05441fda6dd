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

Aggregates, counters and recent spans are process-wide and outlive the
trainer that fed them.  The budget is the train-step hot path: two clock
reads, one dict update under a lock, one append, and the annotation.
"""

from __future__ import annotations

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

# Read through the module so that a test can put a virtual clock in.
_perf_counter = time.perf_counter
_time_ns = time.time_ns


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
_lock = threading.Lock()


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
        return [list(row) for row in _recent]


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
        return {
            name: {"count": int(count), "total": total}
            for name, (count, total) in _counters.items()
        }


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
