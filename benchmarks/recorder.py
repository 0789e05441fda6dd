"""What the benchmark itself observes of a `Trainer.fit` call: one host-clock
completion time per step, the compile requests of the process, and the
arithmetic from those to windows and percentiles.

`StepRecorder` is handed to `fit` as its `logger` (the `step(step, loss)`
method `fit` calls on every step) and its `should_stop` as `stop_fn`.  A
watcher thread waits for each step's loss in order and stamps the clock
when it is there, so every step gets a completion time and the training
thread is never made to wait: it dispatches exactly as far ahead as `fit`
lets it with the product's own logger.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from typing import Callable, Sequence


class CompileCounter:
    """Counts this process's compile requests through JAX's own monitoring
    events: persistent-cache requests, hits and misses (the idea is
    chip_smoke.py's `CacheCounter`), and backend compiles, which also fire
    with the persistent cache off."""

    def __init__(self) -> None:
        import jax

        self.requests = self.hits = self.misses = self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def total(self) -> int:
        """Programs asked of the compiler or of its cache so far."""
        return self.requests + self.backend_compiles

    def snapshot(self) -> dict:
        return {
            "requests": self.requests, "hits": self.hits,
            "misses": self.misses, "backend_compiles": self.backend_compiles,
        }


def find_window(times: Sequence[float], ready_at: float | None, warm_seconds: float, seconds: float):
    """Indices (open, close) into `times`, the completion times of
    consecutive steps, or None while the window has not closed.

    `ready_at` is when set-up's last program had compiled: the first step
    and the probe's readings of the steps after it.  Warm-up runs for
    `warm_seconds` more.  The window opens on the first completion after
    that and closes on the first completion at or after `seconds` later, so
    it is a whole number of steps and at least `seconds` long.
    """
    if ready_at is None:
        return None
    opened = next((i for i, t in enumerate(times) if t >= ready_at + warm_seconds), None)
    if opened is None:
        return None
    closed = next(
        (i for i in range(opened + 1, len(times)) if times[i] >= times[opened] + seconds),
        None,
    )
    return None if closed is None else (opened, closed)


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) with linear interpolation between order
    statistics, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    position = (len(ordered) - 1) * q / 100.0
    low = math.floor(position)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (position - low)


def step_seconds(times: Sequence[float]) -> list[float]:
    """Seconds between consecutive completions."""
    return [b - a for a, b in zip(times, times[1:])]


class StepRecorder:
    def __init__(
        self,
        *,
        warm_seconds: float,
        seconds: float,
        compile_total: Callable[[], int] = lambda: 0,
        trace_dir: str | None = None,
        trace_seconds: float = 2.0,
        trace_min_steps: int = 3,
        clock: Callable[[], float] = time.perf_counter,
        wait: Callable | None = None,
    ):
        self.warm_seconds, self.seconds = warm_seconds, seconds
        self.trace_dir, self.trace_seconds = trace_dir, trace_seconds
        self.trace_min_steps = trace_min_steps
        self._clock, self._compile_total = clock, compile_total
        if wait is None:
            import jax

            wait = jax.block_until_ready
        self._wait = wait
        self.steps: list[int] = []
        self.times: list[float] = []
        self.compiles: list[int] = []
        self.error: BaseException | None = None
        self.ready_at: float | None = None
        # (clock, last step dispatched) when the trace started, at the first
        # sync point after that, and when it stopped.  Starting the profiler
        # stalls the training thread for a second or two, so what lies
        # before `trace_settled` is not steady state and is not reduced.
        self.trace_started: tuple[float, int] | None = None
        self.trace_settled: tuple[float, int] | None = None
        self.trace_stopped: tuple[float, int] | None = None
        self._last_step = 0
        self._queue: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    # --- fit's side ---------------------------------------------------------
    def step(self, step: int, loss) -> None:
        self._last_step = step
        self._queue.put((step, loss))

    def mark_ready(self) -> None:
        """Set-up has compiled its last program; warm-up counts from here."""
        self.ready_at = self._clock()

    def should_stop(self, _metrics=None) -> bool:
        """`fit` asks at its own sync points (every `log_every` steps, all
        dispatched steps done).  True once the window has closed and, in a
        traced run, the trace after it has been taken."""
        if self.error is not None:
            raise self.error
        if self.window() is None:
            return False
        if self.trace_dir is None:
            return True
        import jax

        if self.trace_started is None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            # The host's events are millions (one per tile the input
            # transfer transposes); the reductions read device planes only.
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.trace_started = (self._clock(), self._last_step)
            return False
        if self.trace_settled is None:
            self.trace_settled = (self._clock(), self._last_step)
            return False
        if (
            self._clock() - self.trace_settled[0] >= self.trace_seconds
            and self._last_step - self.trace_settled[1] >= self.trace_min_steps
        ):
            self.trace_stopped = (self._clock(), self._last_step)
            jax.profiler.stop_trace()
            return True
        return False

    # --- the watcher ----------------------------------------------------------
    def _watch(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            step, loss = item
            try:
                self._wait(loss)
            except BaseException as e:  # reported by should_stop and close
                self.error = e
                return
            self.compiles.append(self._compile_total())
            self.steps.append(step)
            self.times.append(self._clock())

    def window(self):
        return find_window(self.times, self.ready_at, self.warm_seconds, self.seconds)

    def close(self) -> None:
        """Stop the watcher once it has stamped everything handed to it."""
        self._queue.put(None)
        self._thread.join(timeout=60.0)
        if self._thread.is_alive():
            raise RuntimeError("the step recorder's watcher did not finish")
        if self.error is not None:
            raise self.error


def window_times(run: dict) -> list[float]:
    """The completion times from the window's opening to its closing."""
    opened, closed = run["window"]
    return run["times"][opened : closed + 1]


def window_step_seconds(run: dict) -> list[float]:
    """The interval before every step that completed in the window."""
    return step_seconds(window_times(run))


def throughput(run: dict) -> float:
    """Examples completed in the window, per second and chip."""
    times = window_times(run)
    return (len(times) - 1) * run["examples_per_step"] / (times[-1] - times[0]) / run["chips"]
