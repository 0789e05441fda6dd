"""A whole traced run of the `looped_decoder` kind (PR 43) through `cli.main`
and `Trainer.fit` at toy size on the CPU, the cell and its metrics appended
from this directory as a later PR would."""

import json
import math
import time
from pathlib import Path

from benchmarks import recorder
from benchmarks import run as bench_run
from deeplearning_cfn_tpu.obs import tracing

REPO = Path(__file__).resolve().parents[2]
CELL = "ouro-2.6b.train-s8192x1"
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
WARM_STEPS, WINDOW_STEPS = 2, 4


def window_in_steps(times, ready_at, warm_seconds, seconds):
    """`recorder.find_window` by count: open `WARM_STEPS` completions after
    set-up's last program compiled, close `WINDOW_STEPS` later.  Under six
    workers a toy step's time is the machine's load; its count is not."""
    if ready_at is None:
        return None
    first = next((i for i, t in enumerate(times) if t >= ready_at), None)
    if first is None or len(times) <= first + WARM_STEPS + WINDOW_STEPS:
        return None
    return first + WARM_STEPS, first + WARM_STEPS + WINDOW_STEPS


def test_a_traced_run_of_the_kind_through_the_cli_and_fit(tmp_path, monkeypatch, cpu_device):
    """`cli.main` -> job -> `Trainer.fit` on the toy configuration in bfloat16
    (two sandwich-normed blocks four times a step, 32 tokens a sequence):
    correct, the loop's counters folded once a step, and the readers that need
    a device plane left out."""
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "looped-decoder-toy.train-toy-tokens"
    data["configs"].append({
        "name": "looped-decoder-toy", "source": "test fixture", "reduced": [], "why": "toy",
        "file": "tests/benchmark_tests/configs/looped-decoder-toy.json"})
    data["workloads"].append({"name": cell, "config": "looped-decoder-toy",
                              "traffic": "train-toy-tokens", "chips": 1, "why": "toy"})
    for metric in data["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    manifest = tmp_path / "BENCHMARK.toy.json"
    manifest.write_text(json.dumps(data))
    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    monkeypatch.setattr(recorder, "find_window", window_in_steps)
    tracing.reset_aggregates()  # the run's notes read the process's counters
    try:
        line, notes = bench_run.run_cell(
            manifest, cell, seed=2**31 + 43, seconds=0.3, trace=1, device=cpu_device, peaks=PEAKS,
            t_process=time.perf_counter(),
        )
        counted = tracing.counters()
    finally:
        tracing.reset_aggregates()  # and so does the next run in this worker
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == WINDOW_STEPS
    assert [r["name"] for r in notes[0]["check"]] == [
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"]
    got = line["metrics"]
    assert {"mfu", "step_ms_p50"} <= set(got)
    assert not {"loop_head_ms_per_step", "loop_pass_ms_per_pass", "attention_roofline_share",
                "attention_backward_roofline_share", "recompute_ms_per_step"} & set(got)  # no device plane
    assert math.isfinite(got["mfu"]["value"]) and got["mfu"]["value"] > 0
    # one observation a step of every counter; four passes every step; p sums to one
    steps = counted["loop.passes"]["count"]
    assert steps >= WARM_STEPS + WINDOW_STEPS and counted["loop.passes"]["total"] == 4 * steps
    names = {"loop.passes", "loop.exit_entropy"} | {
        f"loop.{kind}.{t}" for kind in ("loss", "exit_mass") for t in (1, 2, 3, 4)}
    assert names <= set(counted) and all(counted[n]["count"] == steps for n in names)
    mass = sum(counted[f"loop.exit_mass.{t}"]["total"] for t in (1, 2, 3, 4)) / steps
    assert abs(mass - 1.0) < 1e-5
    assert 0.0 < counted["loop.exit_entropy"]["total"] / steps < math.log(4.0)
