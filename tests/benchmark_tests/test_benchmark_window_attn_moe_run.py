"""A whole traced run of the `window_attn_moe` kind (PR 33) through `cli.main`
and `Trainer.fit` at toy size on the CPU, the cell and its metrics appended
from this directory as a later PR would."""

import json
import math
import time
from pathlib import Path

from benchmarks import run as bench_run
from deeplearning_cfn_tpu.obs import tracing

REPO = Path(__file__).resolve().parents[2]
CELL = "laguna-xs.2.train-s8192"
PEAKS = bench_run.load_peaks()["TPU v5 lite"]


def test_a_traced_run_of_the_kind_through_the_cli_and_fit(tmp_path, monkeypatch, cpu_device):
    """`cli.main` -> job -> `Trainer.fit` on the toy configuration in bfloat16
    (a dense full layer, three window layers, a routed full layer; 32 tokens a
    sequence, five windows of 6): correct, nothing dropped, the routing counted
    and compared, and the readers that need a device plane left out."""
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "window-attn-moe-toy.train-toy-tokens"
    data["configs"].append({
        "name": "window-attn-moe-toy", "source": "test fixture", "reduced": [], "why": "toy",
        "file": "tests/benchmark_tests/configs/window-attn-moe-toy.json"})
    data["workloads"].append({"name": cell, "config": "window-attn-moe-toy",
                              "traffic": "train-toy-tokens", "chips": 1, "why": "toy"})
    for metric in data["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    manifest = tmp_path / "BENCHMARK.toy.json"
    manifest.write_text(json.dumps(data))
    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    tracing.reset_aggregates()  # the run's notes read the process's counters
    try:
        line, notes = bench_run.run_cell(
            manifest, cell, seed=2**31 + 33, seconds=0.3, trace=1, device=cpu_device, peaks=PEAKS,
            t_process=time.perf_counter(),
        )
    finally:
        tracing.reset_aggregates()  # and so does the next run in this worker
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert [r["name"] for r in notes[0]["check"]] == [
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"]
    got = line["metrics"]
    assert {"mfu", "step_ms_p50", "moe_load_max_over_mean"} <= set(got)
    assert not {"moe_ms_per_step", "moe_experts_roofline_share", "window_attention_ms_per_step",
                "window_attention_roofline_share", "window_attention_backward_roofline_share",
                "attention_roofline_share"} & set(got)  # no device plane
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    routing = notes[-1]["notes"]["moe_routing"]
    assert routing["moe.dropped"] == 0.0
    assert routing["moe.assignments"] == 4 * 8 * 32 * 2  # four routed blocks, top 2
    differing = routing["differing_from_reference"]
    assert differing["assignments"] == 4 * 8 * 32 * 2 and 0 <= differing["share"] < 0.1
    assert math.isfinite(got["mfu"]["value"]) and got["mfu"]["value"] > 0
