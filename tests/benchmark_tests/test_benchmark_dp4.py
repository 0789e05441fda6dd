"""`resnet50.train-dp4`: the traffic ISSUE 24 gave it, and limits that lie
between the two readings they were set from."""

import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
LIMITS = json.loads((REPO / "benchmarks/limits/resnet50.train-dp4.json").read_text())
NUMBERS = ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap")


def test_traffic_is_512_images_a_step_over_four_chips():
    traffic = json.loads((REPO / "benchmarks/traffic/train-dp4.json").read_text())
    assert traffic == {
        "kind": "train", "input": "images", "global_batch": 512, "pool_batches": 4,
        "log_every": 10, "warm_seconds": 2.0, "check_steps": 3, "trace_seconds": 2.0,
    }
    cell = next(
        w for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]
        if w["name"] == "resnet50.train-dp4"
    )
    assert cell["chips"] == 4 and cell["config"] == "resnet50" and cell["traffic"] == "train-dp4"


@pytest.mark.parametrize("name", NUMBERS)
def test_every_sound_seed_passes(name):
    reading = LIMITS["readings"][name]
    assert reading["seeds"] >= 12
    assert reading["sound_max"] < LIMITS[name]


@pytest.mark.parametrize("name", ["grad_sketch_gap", "head_sketch_gap"])
def test_the_projections_limits_lie_between_the_readings_with_room(name):
    reading = LIMITS["readings"][name]
    assert 1.5 * reading["sound_max"] <= LIMITS[name] <= min(reading["control_cpu"]) / 1.5


def test_the_control_fails_on_every_control_seed():
    controls = len(LIMITS["readings"]["control_cpu_seeds"])
    assert controls >= 3
    for i in range(controls):
        failed = [n for n in NUMBERS if LIMITS["readings"][n]["control_cpu"][i] > LIMITS[n]]
        assert "head_sketch_gap" in failed and failed
    assert "CPU" in LIMITS["readings"]["origin"] and "four chips" in LIMITS["readings"]["origin"]
