"""A run end to end at toy size on the CPU mesh, beneath the device gate:
`cli.main(["run", ...])` -> provision -> contract -> launch plan ->
`benchmarks.job` -> `Trainer.fit`, on a configuration, a traffic mix, a
cell and a per-layer metric added from this directory by files and
appended entries alone.  And the gate's refusal."""

import json
import math

import pytest

from benchmarks import run as bench_run

PEAKS = bench_run.load_peaks()["TPU v5 lite"]


@pytest.fixture()
def toy_run(toy_manifest, cpu_device, tmp_path, monkeypatch):
    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))

    def run(workload, trace):
        import time

        return bench_run.run_cell(
            toy_manifest, workload, seed=2**31 + 7, seconds=0.5, trace=trace,
            device=cpu_device, peaks=PEAKS, t_process=time.perf_counter(),
        )

    return run


def test_untraced_run_reports_the_cells_end_to_end_metrics(toy_run, cpu_device):
    line, notes = toy_run("resnet-toy.train-toy-images", 0)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_throughput", "setup_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and math.isfinite(metric["value"]) and metric["value"] > 0
    assert line["metrics"]["train_throughput"]["unit"] == "examples/s/chip"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == cpu_device["platform"]
    json.dumps(line, allow_nan=False)
    # every number compared is printed beside its limit, in every run
    check = notes[0]["check"]
    assert [r["name"] for r in check] == [
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"]
    assert all(r["ok"] and r["value"] <= r["limit"] for r in check)
    assert all(notes[1]["conditions"].values())
    assert notes[1]["compile"]["in_window"] == 0
    # the per-layer metrics that need no trace are read too, on an earlier line
    other = notes[-1]["not_in_the_result"]["layer_metrics"]
    assert {"process_start_s", "warm_up_s", "train_step_ms_p95", "mfu"} <= set(other)
    assert "device_idle_share" not in other


def test_traced_run_reports_per_layer_metrics_and_leaves_out_what_it_cannot_read(toy_run):
    line, _ = toy_run("decoder-toy.train-toy-tokens", 1)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "breakdown"]
    assert line["correct"] is True
    # The CPU has no device plane: the trace's readers find nothing and are
    # left out; the host's and the counters' are there, and so is the metric
    # this directory added.
    assert set(line["metrics"]) == {
        "process_start_s", "provision_s", "first_step_s", "compile_cache_misses", "warm_up_s",
        "input_wait_ms_per_step", "input_mb_per_step", "step_ms_p50", "mfu", "toy.steps_traced",
    }
    assert line["metrics"]["toy.steps_traced"]["value"] >= 3
    assert line["metrics"]["input_mb_per_step"]["value"] == pytest.approx(8 * 32 * 4 * 2 / 1e6)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_set_up_is_the_sum_of_its_four_per_layer_parts_and_the_tail_is_of_single_steps(toy_run):
    line, notes = toy_run("resnet-toy.train-toy-images", 1)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # the cells that a metric's `workloads` name report it, and no others
    assert "train_step_ms_p95" in got and "attention_roofline_share" not in got
    assert set(notes[-1]["not_in_the_result"]["end_to_end"]) == {"train_throughput", "setup_s"}
    tail = notes[-1]["notes"]["train_step_ms"]
    assert tail["samples"] == line["attempted"]
    assert tail["p50"] <= tail["p95"] == got["train_step_ms_p95"] <= tail["max"]
    assert got["step_ms_p50"] == tail["p50"]
    untraced, _ = toy_run("resnet-toy.train-toy-images", 0)
    parts = ("process_start_s", "provision_s", "first_step_s", "warm_up_s")
    assert all(got[p] >= 0 for p in parts)
    # the same four parts, in another run: the sum is a set-up time of the same order
    assert 0.2 < sum(got[p] for p in parts) / untraced["metrics"]["setup_s"]["value"] < 5


def test_the_gate_refuses_a_cpu_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as refusal:
        bench_run.main(["--workload", "resnet50.train-b128", "--seed", "1", "--seconds", "1"])
    assert "refusing to run" in str(refusal.value) and "'platform': 'cpu'" in str(refusal.value)
    assert refusal.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_the_gate_refuses_a_wrong_chip_count(monkeypatch):
    import jax

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert bench_run.require_device(1) == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(SystemExit, match="asks for 4 chip"):
        bench_run.require_device(4)
    Chip.device_kind = "TPU v9"
    with pytest.raises(SystemExit, match="peaks.json"):
        bench_run.require_device(1)
