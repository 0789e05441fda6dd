"""StepProfiler: phase accounting, rolling quantiles, program attribution.

Every timing test drives the profiler with an injected virtual clock —
no wall-clock dependence, exact phase arithmetic."""

from __future__ import annotations

import threading

from deeplearning_cfn_tpu.obs.profiler import (
    NULL_PROFILER,
    PHASES,
    RollingQuantiles,
    StepProfiler,
    program_attribution,
    program_cost,
)


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


class FakeRecorder:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        event = {"kind": kind}
        event.update(fields)
        self.events.append(event)
        return event


def test_rolling_quantiles_empty_and_single():
    q = RollingQuantiles()
    assert q.quantiles() == {}
    q.add(5.0)
    assert q.quantiles() == {"p50": 5.0, "p95": 5.0, "p99": 5.0}


def test_rolling_quantiles_known_distribution():
    q = RollingQuantiles(window=1000)
    for v in range(1, 101):  # 1..100
        q.add(float(v))
    out = q.quantiles()
    # Nearest-rank on index round(q * (n-1)): n=100 -> indexes 50/94/98.
    assert out["p50"] == 51.0
    assert out["p95"] == 95.0
    assert out["p99"] == 99.0
    assert out["p50"] <= out["p95"] <= out["p99"]


def test_rolling_quantiles_window_bounds_memory():
    q = RollingQuantiles(window=8)
    for v in range(100):
        q.add(float(v))
    assert len(q) == 8
    # Only the last 8 samples (92..99) survive; p50 is index round(3.5)=4.
    assert q.quantiles()["p50"] == 96.0


def test_phase_accounting_exact():
    clock = VirtualClock()
    prof = StepProfiler(name="t", clock=clock)
    prof.start()
    for _ in range(4):
        clock.advance(0.001)  # untimed loop work -> host residual
        with prof.phase("h2d"):
            clock.advance(0.002)
        with prof.phase("dispatch"):
            clock.advance(0.003)
        with prof.sync_boundary(1):
            clock.advance(0.010)
        prof.step_done()
    snap = prof.snapshot()
    assert snap["steps"] == 4
    assert abs(snap["h2d_ms"] - 2.0) < 1e-9
    assert abs(snap["dispatch_ms"] - 3.0) < 1e-9
    assert abs(snap["compute_ms"] - 10.0) < 1e-9
    assert abs(snap["host_ms"] - 1.0) < 1e-9
    assert abs(snap["step_ms"]["p50"] - 16.0) < 1e-9
    # The acceptance-criteria flat keys are all present.
    for phase in PHASES:
        assert f"{phase}_ms" in snap


def test_sync_boundary_amortizes_over_steps():
    clock = VirtualClock()
    prof = StepProfiler(name="t", clock=clock)
    prof.start()
    for _ in range(5):
        with prof.phase("dispatch"):
            clock.advance(0.001)
        prof.step_done()
    # One drain observing 5 steps' device time at once.
    with prof.sync_boundary(5):
        clock.advance(0.050)
    snap = prof.snapshot()
    compute = snap["phases"]["compute"]
    assert compute["count"] == 5
    assert abs(compute["total_ms"] - 50.0) < 1e-9
    assert abs(compute["p50_ms"] - 10.0) < 1e-9  # per-step, not per-drain


def test_non_critical_fold_excluded_from_host_residual():
    clock = VirtualClock()
    prof = StepProfiler(name="t", clock=clock)
    prof.start()
    clock.advance(0.004)
    # Producer-side overlapped transfer: phase stats yes, residual no.
    prof.fold("h2d", 0.100, critical=False)
    prof.step_done()
    snap = prof.snapshot()
    assert abs(snap["h2d_ms"] - 100.0) < 1e-9
    assert abs(snap["host_ms"] - 4.0) < 1e-9  # NOT 4 - 100 clamped weirdness
    assert abs(snap["step_ms"]["p50"] - 4.0) < 1e-9


def test_wrap_source_times_data_wait():
    clock = VirtualClock()
    prof = StepProfiler(name="t", clock=clock)

    def slow_source():
        for i in range(3):
            clock.advance(0.007)  # inside next(): counted as data_wait
            yield i

    items = list(prof.wrap_source(slow_source()))
    assert items == [0, 1, 2]
    wait = prof.snapshot()["phases"]["data_wait"]
    assert wait["count"] == 3
    assert abs(wait["total_ms"] - 21.0) < 1e-9


def test_per_step_events_journal_breakdown():
    clock = VirtualClock()
    rec = FakeRecorder()
    prof = StepProfiler(name="t", clock=clock, recorder=rec, per_step_events=True)
    prof.start()
    for i in range(2):
        with prof.phase("dispatch"):
            clock.advance(0.002)
        clock.advance(0.001)
        prof.step_done(step=i)
    kinds = [e["kind"] for e in rec.events]
    assert kinds == ["step_time", "step_time"]
    ev = rec.events[0]
    assert ev["profiler"] == "t"
    assert ev["step"] == 0
    assert abs(ev["total_ms"] - 3.0) < 1e-9
    assert abs(ev["dispatch_ms"] - 2.0) < 1e-9
    assert abs(ev["host_ms"] - 1.0) < 1e-9


def test_multi_step_done_divides_per_step():
    clock = VirtualClock()
    prof = StepProfiler(name="t", clock=clock)
    prof.start()
    with prof.phase("dispatch"):
        clock.advance(0.004)
    prof.step_done(steps=4)  # one k=4 program call
    snap = prof.snapshot()
    assert snap["steps"] == 4
    assert abs(snap["dispatch_ms"] - 1.0) < 1e-9
    assert abs(snap["step_ms"]["p50"] - 1.0) < 1e-9


def test_disabled_profiler_is_inert():
    src = iter(())
    assert NULL_PROFILER.wrap_source(src) is src
    # Reusable null context, no state change.
    with NULL_PROFILER.phase("dispatch"):
        pass
    with NULL_PROFILER.sync_boundary(4):
        pass
    NULL_PROFILER.step_done()
    snap = NULL_PROFILER.snapshot()
    assert snap["steps"] == 0
    rec = FakeRecorder()
    NULL_PROFILER.journal(recorder=rec)
    assert rec.events == []  # disabled profilers never journal


def test_journal_records_one_step_profile_event():
    clock = VirtualClock()
    rec = FakeRecorder()
    prof = StepProfiler(name="bench", clock=clock, recorder=rec)
    prof.start()
    with prof.phase("dispatch"):
        clock.advance(0.002)
    prof.step_done()
    snap = prof.journal()
    assert [e["kind"] for e in rec.events] == ["step_profile"]
    assert rec.events[0]["name"] == "bench"
    assert rec.events[0]["dispatch_ms"] == snap["dispatch_ms"]


def test_concurrent_folds_from_producer_thread():
    clock = VirtualClock()
    prof = StepProfiler(name="t", clock=clock)
    prof.start()

    def producer():
        for _ in range(100):
            prof.fold("h2d", 0.001, critical=False)

    threads = [threading.Thread(target=producer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert prof.snapshot()["phases"]["h2d"]["count"] == 400


class _FakeCompiled:
    def __init__(self, cost):
        self._cost = cost

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost


def test_program_cost_reads_the_cost_model_or_reports_none():
    cost = {"flops": 100.0, "bytes accessed": 50.0}
    assert program_cost(_FakeCompiled(cost)) == {
        "flops": 100.0,
        "bytes_accessed": 50.0,
    }
    assert program_cost(_FakeCompiled(None))["flops"] is None
    assert program_cost(_FakeCompiled(RuntimeError("no cost model")))[
        "flops"
    ] is None


def test_program_attribution_mfu_math():
    out = program_attribution(
        flops=4e9,
        bytes_accessed=2e8,
        seconds_per_call=0.04,
        steps_per_call=4,
        peak_flops=1e12,
    )
    assert out["steps_per_call"] == 4
    assert out["flops_per_step"] == 1e9
    assert out["bytes_per_step"] == 5e7
    # 4e9 flops in 0.04 s = 1e11 flop/s over 1e12 peak = 0.1 MFU.
    assert abs(out["mfu"] - 0.1) < 1e-9
    assert abs(out["bytes_per_sec"] - 5e9) < 1e-3


def test_program_attribution_handles_missing_cost():
    out = program_attribution(
        flops=None, bytes_accessed=None, seconds_per_call=0.01, peak_flops=1e12
    )
    assert "mfu" not in out and "flops_per_step" not in out


def test_labels_ride_snapshot_only_when_set():
    prof = StepProfiler(name="labeled", clock=VirtualClock())
    assert "labels" not in prof.snapshot()  # unset -> absent, not {}
    prof.set_label("mode", "multi_step_k4")
    prof.set_label("k", 4)
    snap = prof.snapshot()
    assert snap["labels"] == {"mode": "multi_step_k4", "k": 4}
    # Re-setting overwrites; snapshot holds a copy, not the live dict.
    prof.set_label("mode", "single_step")
    assert snap["labels"]["mode"] == "multi_step_k4"
    assert prof.snapshot()["labels"]["mode"] == "single_step"
    # The disabled profiler swallows labels like every other call.
    NULL_PROFILER.set_label("mode", "x")
    assert "labels" not in NULL_PROFILER.snapshot()
