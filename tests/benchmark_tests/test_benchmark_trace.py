"""The reduction from trace to numbers: interval arithmetic on made-up rows,
and the whole reduction on a recording from the chip (PR 23, TPU v5 lite),
trimmed to its last two step programs and kept under data/."""

import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
P0 = "/device:TPU:0"


def expand(path: Path) -> list[list]:
    """Rows from the compact form the recordings are kept in."""
    c = json.loads(path.read_text())
    return [
        [c["planes"][p], c["lines"][l], c["names"][n], start, dur]
        for p, l, n, start, dur in c["rows"]
    ]


def op(name, start, dur, plane=P0, line=tr.OP_LINE):
    return [plane, line, name, start, dur]


@pytest.mark.parametrize(
    "intervals, want",
    [
        ([], []),
        ([(0, 10), (20, 30)], [(0, 10), (20, 30)]),
        ([(0, 10), (5, 30), (30, 40)], [(0, 40)]),
        ([(20, 30), (0, 10), (2, 3)], [(0, 10), (20, 30)]),
    ],
)
def test_union(intervals, want):
    assert tr.union(intervals) == want


@pytest.mark.parametrize(
    "intervals, holes, want",
    [
        ([(0, 100)], [], [(0, 100)]),
        ([(0, 100)], [(10, 20), (50, 120)], [(0, 10), (20, 50)]),
        ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
        ([(0, 10)], [(0, 10)], []),
    ],
)
def test_subtract(intervals, holes, want):
    assert tr.subtract(intervals, holes) == want


def test_short_name_cuts_the_hlo_text_to_the_operation():
    text = "%fusion.12 = bf16[128,56,56,256]{3,0,2,1:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput"
    assert tr.short_name(text) == "fusion.12"
    assert tr.short_name("all-reduce.3") == "all-reduce.3"


def test_reduce_device_by_hand():
    rows = [
        op("jit_step_fn(1)", 0, 100, line=tr.MODULE_LINE),
        op("%fusion.1 = f32[8] fusion(%x)", 0, 40),
        op("%all-reduce.1 = f32[8] all-reduce(%y)", 30, 30),  # 10 hidden, 20 exposed
        op("%fusion.2 = f32[8] fusion(%z)", 70, 30),  # a gap of 10 before it
        op("%fusion.1 = f32[8] fusion(%x)", 1000, 50, plane="/device:TPU:1"),
        op("host thing", 0, 10**6, plane="/host:CPU"),
    ]
    assert tr.devices([r for r in rows if r[0].startswith("/device")]) == [0, 1]
    got = tr.reduce_device(rows, 0)
    assert got["window_s"] == pytest.approx(100e-9) and got["busy_s"] == pytest.approx(90e-9)
    assert got["programs"] == 1 and got["ops"] == 3
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(40e-9)]
    assert got["idle_gaps"] == [["before fusion.2", pytest.approx(10e-9)]]
    assert got["collective_s"] == pytest.approx(30e-9)
    assert got["collective_exposed_s"] == pytest.approx(20e-9)
    seconds, count = tr.kernel_seconds(rows, 0, r"^fusion\.")
    assert seconds == pytest.approx(70e-9) and count == 2


def test_steady_rows_leaves_out_what_ran_before_the_profiler_settled():
    rows = []
    for i in range(5):
        rows.append(op("jit_step_fn(1)", i * 100, 90, line=tr.MODULE_LINE))
        rows.append(op("%fusion.1 = f32[8] fusion(%x)", i * 100, 90))
    kept = tr.steady_rows(rows, 2)
    assert min(r[3] for r in kept) == 200 and len(kept) == 6
    assert tr.steady_rows(rows, 0) is rows
    assert tr.steady_rows(rows, 5) == []


RECORDINGS = sorted(DATA.glob("trace_*.json"))


@pytest.mark.parametrize("path", RECORDINGS, ids=lambda p: p.stem)
def test_reduction_of_a_recording_from_the_chip(path):
    rows = expand(path)
    reduced = tr.reduce(rows)
    assert reduced["per_device"], "the recording has a device plane"
    for device in reduced["per_device"]:
        # two step programs, back to back: the device is busy nearly all the time
        assert device["programs"] == 2
        assert 0 < device["busy_s"] <= device["window_s"]
        assert device["busy_s"] / device["window_s"] > 0.9
        assert len(device["device_ops"]) == 10 and len(device["idle_gaps"]) <= 10
        assert device["device_ops"][0][1] >= device["device_ops"][-1][1] > 0
        assert sum(s for _, s in device["device_ops"]) <= device["busy_s"]
        assert 0 <= device["collective_exposed_s"] <= device["collective_s"] <= device["busy_s"]
    assert reduced["busy_s"] == pytest.approx(
        sum(d["busy_s"] for d in reduced["per_device"]) / len(reduced["per_device"]))


def test_there_is_a_recording():
    assert RECORDINGS, "tests/benchmark_tests/data/trace_*.json is missing"
