"""A whole traced run of the `mamba_attn` kind (PR 47) through `cli.main` and
`Trainer.fit` at toy size on the CPU, the cell and its metrics appended from
this directory as a later PR would.  On one of the tests' eight virtual devices:
a toy step's collectives over eight of them can wait on one another for ever
under six loaded workers (the looped decoder's twin of this test, ROADMAP D12),
and nothing this test reads exists only across devices."""

import json
import math
import time
from pathlib import Path

import jax

from benchmarks import recorder
from benchmarks import run as bench_run
from deeplearning_cfn_tpu.obs import tracing

REPO = Path(__file__).resolve().parents[2]
CELL = "jamba2-3b.train-s8192x1"
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
    (two Mamba-1 layers, the attention layer, two Mamba-1 layers; 32 tokens a
    sequence): correct, dt's counters folded once a step, and the readers that
    need a device plane left out."""
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "mamba-attn-toy.train-toy-tokens"
    data["configs"].append({
        "name": "mamba-attn-toy", "source": "test fixture", "reduced": [], "why": "toy",
        "file": "tests/benchmark_tests/configs/mamba-attn-toy.json"})
    data["workloads"].append({"name": cell, "config": "mamba-attn-toy",
                              "traffic": "train-toy-tokens", "chips": 1, "why": "toy"})
    for metric in data["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    manifest = tmp_path / "BENCHMARK.toy.json"
    manifest.write_text(json.dumps(data))
    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    monkeypatch.setattr(recorder, "find_window", window_in_steps)
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    tracing.reset_aggregates()  # the run's notes read the process's counters
    try:
        line, notes = bench_run.run_cell(
            manifest, cell, seed=2**31 + 47, seconds=0.3, trace=1,
            device=dict(cpu_device, count=1), peaks=PEAKS, t_process=time.perf_counter(),
        )
        counted = tracing.counters()
    finally:
        tracing.reset_aggregates()  # and so does the next run in this worker
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == WINDOW_STEPS
    assert [r["name"] for r in notes[0]["check"]] == [
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"]
    got = line["metrics"]
    assert {"mfu", "step_ms_p50"} <= set(got)
    assert not {"selective_scan_ms_per_step", "selective_scan_roofline_share", "ssm_mixer_ms_per_step",
                "attention_roofline_share", "recompute_ms_per_step"} & set(got)  # no device plane
    assert math.isfinite(got["mfu"]["value"]) and got["mfu"]["value"] > 0
    steps = counted["ssm.dt_mean"]["count"]
    assert steps >= WARM_STEPS + WINDOW_STEPS and counted["ssm.dt_max"]["count"] == steps
    assert 1e-3 < counted["ssm.dt_mean"]["total"] / steps < counted["ssm.dt_max"]["total"] / steps < 10.0
