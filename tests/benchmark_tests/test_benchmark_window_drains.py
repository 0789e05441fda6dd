"""`benchmarks/window_drains.py` on made-up rows: the four numbers by hand's
arithmetic, each verdict from rows built to give it, the cases that read
nothing; the manifest's four entries; and the toy run's notes."""

import json
import time

import pytest

from benchmarks import run as bench_run
from benchmarks import window_drains
from benchmarks.manifest import Manifest

THREAD = 7
STEP_S = 0.5  # a step on the device
EXPOSED_S = 0.004


def drain(k, *, steps=2, interval=None, at=None, exposed=EXPOSED_S, gc=0.0, exposed_gc=0.0):
    """The k-th drain of a loop that drains every second step."""
    interval = steps * STEP_S + EXPOSED_S if interval is None else interval
    return {
        "thread": THREAD, "step": steps * k, "steps": steps,
        "sync_end_s": at, "sync_end_ns": None,
        "interval_s": interval if k > 1 else None, "gc_s": gc if k > 1 else None,
        "nivcsw": 0, "majflt": 0,
        "exposed_s": exposed, "exposed_gc_s": exposed_gc,
    }


def loop(n, slow=None):
    """n drains from t = 100 on; `slow` maps a drain's number to fields of
    its own.  Returns stamped on both clocks, the wall clock 1e9 s ahead."""
    rows, t = [], 100.0
    for k in range(1, n + 1):
        row = drain(k, **(slow or {}).get(k, {}))
        t += row["interval_s"] if row["interval_s"] is not None else 1.0
        row["sync_end_s"], row["sync_end_ns"] = t, int((t + 1e9) * 1e9)
        rows.append(row)
    return rows


def seams(rows, grown=None):
    """`recent_spans()` rows for every exposed segment: `fit.log` 1 ms,
    `fit.data_wait` 0.5, `fit.h2d` 0.5, `fit.dispatch` 1.5, the rest under
    `fit.step`; `grown` names the seam that takes a longer segment's surplus."""
    out = []
    for row in rows:
        if row["exposed_s"] is None:
            continue
        t = row["sync_end_ns"]
        parts = {"fit.log": 1.0, "fit.data_wait": 0.5, "fit.h2d": 0.5, "fit.dispatch": 1.5}
        if grown and row["exposed_s"] > EXPOSED_S:
            parts[grown] += 1e3 * (row["exposed_s"] - EXPOSED_S)
        for name, ms in parts.items():
            out.append([THREAD, name, t, int(ms * 1e6)])
            t += int(ms * 1e6)
    return out


def test_a_quiet_window_reads_zero_and_the_median_exposed_segment():
    rows = loop(40)
    got = window_drains.summarise(rows, rows[4]["sync_end_s"], rows[34]["sync_end_s"], spans=seams(rows))
    assert got["drains"] == 31 and got["steps"] == 62
    assert got["lost_share"] == pytest.approx(0.0, abs=1e-9)
    assert got["signed_share"] == pytest.approx(0.0, abs=1e-9)
    assert got["longest_stall_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["median_step_ms"] == pytest.approx(502.0)
    assert got["median_step_ms_by_third"] == pytest.approx([502.0] * 3)
    assert got["median_exposed_ms"] == pytest.approx(4.0)
    assert got["host_exposed_ms_per_step"] == pytest.approx(2.0)
    assert got["gc_ms_per_step"] == 0.0
    # the intervals add up to the window less its first drain's own
    assert got["interval_sum_s"] == pytest.approx(got["window_s"] + 1.004)
    assert len(got["slowest"]) == 5 and {s["verdict"] for s in got["slowest"]} == {"none"}
    assert got["slowest"][0]["exposed_before"]["by_seam_ms"] == pytest.approx(
        {"fit.log": 1.0, "fit.data_wait": 0.5, "fit.h2d": 0.5, "fit.dispatch": 1.5, "host.gc": 0.0, "fit.step": 0.5}
    )


def test_a_window_that_opens_on_a_step_still_settling_shows_it_by_thirds():
    # the first ten drains 60, 54, ... 6 ms a drain over the settled one
    rows = loop(31, slow={k: {"interval": 1.004 + 0.006 * (12 - k)} for k in range(2, 12)})
    got = window_drains.summarise(rows, rows[0]["sync_end_s"], rows[30]["sync_end_s"])
    assert got["drains"] == 30
    assert got["median_step_ms_by_third"] == pytest.approx([502.0 + 16.5, 502.0, 502.0])
    # and as lost time, which no stall made
    assert got["longest_stall_ms"] == pytest.approx(60.0)
    assert got["lost_share"] == pytest.approx(100 * 0.330 / (30 * 1.004 + 0.330))


def test_one_interval_of_two_and_a_half_seconds_more_reads_its_share_and_its_length():
    rows = loop(32, slow={12: {"interval": 1.004 + 2.5}})
    got = window_drains.summarise(rows, rows[1]["sync_end_s"], rows[31]["sync_end_s"])
    # 31 drains, one of them 2.5 s over the median drain of 1.004 s
    assert got["drains"] == 31
    assert got["longest_stall_ms"] == pytest.approx(2500.0)
    assert got["lost_share"] == pytest.approx(100 * 2.5 / (31 * 1.004 + 2.5))
    assert got["lost_share"] == pytest.approx(got["signed_share"])
    assert got["slowest"][0]["step"] == 24 and got["slowest"][0]["excess_ms"] == pytest.approx(2500.0)
    assert got["slowest"][1]["excess_ms"] == pytest.approx(0.0, abs=1e-6)


VERDICTS = {
    # the segment before the slow drain grew by the drain's excess, in a hook
    "host_exposed": (
        {11: {"exposed": 0.504}, 12: {"interval": 1.504}}, "fit.log", None,
        {"verdict": "host_exposed", "seam": "fit.log"},
    ),
    # the same, waiting for the batch source
    "host_exposed_by_the_source": (
        {11: {"exposed": 0.504}, 12: {"interval": 1.504}}, "fit.data_wait", None,
        {"verdict": "host_exposed", "seam": "fit.data_wait"},
    ),
    # a collection inside that segment: the collector is asked first
    "gc": (
        {11: {"exposed": 0.504, "exposed_gc": 0.49}, 12: {"interval": 1.504, "gc": 0.49}}, "fit.log", None,
        {"verdict": "gc"},
    ),
    # the watcher saw the two steps complete a second apart; the thread's wait returned late
    "training_thread_late": ({12: {"interval": 1.504}}, None, 1.004, {"verdict": "training_thread_late"}),
    # the watcher saw them late too
    "device_or_machine": ({12: {"interval": 1.504}}, None, 1.504, {"verdict": "device_or_machine"}),
}


@pytest.mark.parametrize("name", list(VERDICTS))
def test_each_verdict_from_rows_built_to_give_it(name):
    slow, grown, watched, want = VERDICTS[name]
    rows = loop(32, slow=slow)
    steps, times = [], []
    if watched is not None:
        # the watcher's stamps of every step: on time, but the slow drain's as given
        for row in rows:
            steps += [row["step"] - 1, row["step"]]
            times += [row["sync_end_s"] - STEP_S, row["sync_end_s"]]
        late = rows[11]
        times[steps.index(late["step"])] = times[steps.index(late["step"] - 2)] + watched
    got = window_drains.summarise(
        rows, rows[1]["sync_end_s"], rows[31]["sync_end_s"],
        spans=seams(rows, grown), steps=steps, times=times,
    )
    worst = got["slowest"][0]
    assert worst["step"] == 24 and worst["excess_ms"] == pytest.approx(500.0)
    assert {k: worst[k] for k in want} == want
    assert got["longest_stall_ms"] == pytest.approx(500.0)
    if grown:
        assert worst["exposed_before"]["by_seam_ms"][grown] > 499.0
    json.dumps(got, allow_nan=False)


def test_without_the_watchers_stamps_a_slow_drain_is_not_judged_between_thread_and_device():
    rows = loop(32, slow={12: {"interval": 1.504}})
    got = window_drains.summarise(rows, rows[1]["sync_end_s"], rows[31]["sync_end_s"])
    assert got["slowest"][0]["verdict"] == "unknown"


@pytest.mark.parametrize("case", ["no_recent_drains", "two_drains_in_the_window", "the_list_turned_over"])
def test_a_reader_returns_none_never_a_number(case, monkeypatch):
    from deeplearning_cfn_tpu.obs import tracing

    rows = loop(40)
    window = (rows[4], rows[34])
    if case == "two_drains_in_the_window":
        window = (rows[4], rows[5])
    elif case == "the_list_turned_over":
        rows = rows[6:]
    if case == "no_recent_drains":
        monkeypatch.delattr(tracing, "recent_drains")  # the parent commit's program
    else:
        monkeypatch.setattr(tracing, "recent_drains", lambda: rows)
    run = {
        "window": (0, 1), "times": [window[0]["sync_end_s"], window[1]["sync_end_s"]],
        "steps": [1, 2], "device": {"platform": "tpu"},
    }
    assert window_drains.table(run) is None
    assert "notes" not in run
    for name in ("lost_share", "longest_stall_ms", "host_exposed_ms_per_step", "gc_ms_per_step"):
        assert window_drains.published(run, name) is None


def test_the_numbers_are_published_on_the_chip_alone_and_the_table_everywhere(monkeypatch):
    from deeplearning_cfn_tpu.obs import tracing

    rows = loop(40)
    monkeypatch.setattr(tracing, "recent_drains", lambda: rows)
    for platform, publishes in (("tpu", True), ("cpu", False)):
        run = {
            "window": (0, 1), "times": [rows[4]["sync_end_s"], rows[34]["sync_end_s"]],
            "steps": [1, 2], "device": {"platform": platform},
        }
        value = window_drains.published(run, "host_exposed_ms_per_step")
        assert (value == pytest.approx(2.0)) if publishes else value is None
        assert run["notes"]["window_drains"]["drains"] == 31
        assert run["notes"]["window_drains"]["attempted"] == 1


def test_the_devices_gap_less_the_exposed_segment_needs_no_common_clock():
    rows = loop(8)
    started = rows[0]["sync_end_ns"] - 5_000_000_000
    # the device's clock runs 1.2 ms behind the host's; each gap is the
    # segment plus 0.3 ms of wake-up before it and 0.2 ms of launch after
    waits = [
        (r["sync_end_ns"] - started - 1_200_000 - 300_000, r["sync_end_ns"] - started - 1_200_000 + 4_000_000 + 200_000)
        for r in rows[2:5]
    ]
    got = window_drains.latencies(waits, rows, started)
    assert [g["step"] for g in got] == [6, 8, 10]
    assert all(g["wake_up_plus_launch_ms"] == pytest.approx(0.5) for g in got)
    assert all(g["device_gap_ms"] == pytest.approx(4.5) and g["exposed_ms"] == pytest.approx(4.0) for g in got)
    # a gap with no drain near it is left out
    assert window_drains.latencies([(10**12, 10**12 + 10**6)], rows, started) == []


@pytest.mark.parametrize("name, unit, source", [
    ("window_lost_share", "%", "program_span"),
    ("window_longest_stall_ms", "ms", "program_span"),
    ("window_host_exposed_ms_per_step", "ms", "program_span"),
    ("window_gc_ms_per_step", "ms", "program_counter"),
])
def test_the_manifest_has_the_windows_metric_by_name(name, unit, source):
    manifest = Manifest()
    (entry,) = [m for m in manifest.data["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "trainer", "moves": "train_throughput",
    }
    # no `workloads`: every cell that reports the throughput reports it
    for cell in manifest.data["workloads"]:
        assert name in {m["name"] for m in manifest.per_layer_for(cell["name"])}
    assert callable(manifest.module("layer_metrics", name).read)


def test_a_toy_runs_notes_hold_the_windows_table_traced_or_not(toy_manifest, cpu_device, tmp_path, monkeypatch):
    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    line, notes = bench_run.run_cell(
        toy_manifest, "decoder-toy.train-toy-tokens", seed=2**31 + 11, seconds=0.5, trace=0,
        device=cpu_device, peaks=bench_run.load_peaks()["TPU v5 lite"], t_process=time.perf_counter(),
    )
    table = notes[-1]["notes"]["window_drains"]
    assert table["drains"] >= 3 and table["attempted"] == line["attempted"]
    # the rows add up to the window, to within a drain at each end
    longest = max(s["interval_s"] for s in table["slowest"])
    assert abs(table["interval_sum_s"] - table["window_s"]) <= 2 * longest
    assert abs(table["steps"] - table["attempted"]) <= 2 * max(s["steps"] for s in table["slowest"])
    assert table["median_step_ms"] > 0 and table["host_exposed_ms_per_step"] > 0
    assert {"verdict", "excess_ms", "exposed_before", "watcher_interval_s"} <= set(table["slowest"][0])
    # a CPU's window is no measurement of the chip's host: no metric of it
    assert not {k for k in notes[-1]["not_in_the_result"]["layer_metrics"] if k.startswith("window_")}
    json.dumps(notes, allow_nan=False)
