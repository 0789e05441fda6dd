"""A whole traced run of the `ssm_attn_moe` kind (PR 41) through `cli.main`
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
CELL = "nemotron-3-super-120b-a12b.train-s8192x1"
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
    (two pairs of experts and Mamba-2, an attention block; 32 tokens a sequence,
    four chunks of 8): correct, nothing dropped, the routing counted over the
    two routed blocks and compared, and the readers that need a device plane
    left out."""
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "ssm-attn-moe-toy.train-toy-tokens"
    data["configs"].append({
        "name": "ssm-attn-moe-toy", "source": "test fixture", "reduced": [], "why": "toy",
        "file": "tests/benchmark_tests/configs/ssm-attn-moe-toy.json"})
    data["workloads"].append({"name": cell, "config": "ssm-attn-moe-toy",
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
            manifest, cell, seed=2**31 + 41, seconds=0.3, trace=1, device=cpu_device, peaks=PEAKS,
            t_process=time.perf_counter(),
        )
    finally:
        tracing.reset_aggregates()  # and so does the next run in this worker
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == WINDOW_STEPS
    assert [r["name"] for r in notes[0]["check"]] == [
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"]
    got = line["metrics"]
    assert {"mfu", "step_ms_p50", "moe_load_max_over_mean"} <= set(got)
    assert not {"moe_ms_per_step", "moe_experts_roofline_share", "ssm_mixer_ms_per_step",
                "ssm_scan_roofline_share", "latent_experts_roofline_share",
                "attention_roofline_share"} & set(got)  # no device plane
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    routing = notes[-1]["notes"]["moe_routing"]
    assert routing["moe.dropped"] == 0.0
    assert routing["moe.assignments"] == 2 * 8 * 32 * 3  # two routed blocks, top 3
    differing = routing["differing_from_reference"]
    assert differing["assignments"] == 2 * 8 * 32 * 3 and 0 <= differing["share"] < 0.1
    assert math.isfinite(got["mfu"]["value"]) and got["mfu"]["value"] > 0
