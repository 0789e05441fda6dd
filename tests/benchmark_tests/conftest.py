"""Fixtures shared by the benchmark's tests: the committed manifest, and a
copy of it with one configuration, one traffic mix, one cell and one
per-layer metric of each kind appended from this directory's own files,
the way a later PR adds them."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TOY_CELLS = {
    "resnet-toy.train-toy-images": ("resnet-toy", "train-toy-images"),
    "decoder-toy.train-toy-tokens": ("decoder-toy", "train-toy-tokens"),
}


def toy_manifest_data() -> dict:
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    for cell, (config, traffic) in TOY_CELLS.items():
        data["configs"].append({
            "name": config, "source": "test fixture", "reduced": [], "why": "toy",
            "file": f"tests/benchmark_tests/configs/{config}.json",
        })
        data["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": 1, "why": "toy",
        })
    for metric in data["per_layer"]:
        if metric["name"] == "train_step_ms_p95":
            metric["workloads"].append("resnet-toy.train-toy-images")
    data["per_layer"].append({
        "name": "toy.steps_traced", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer", "moves": "train_throughput",
        "workloads": list(TOY_CELLS),
    })
    return data


@pytest.fixture()
def toy_manifest(tmp_path):
    path = tmp_path / "BENCHMARK.toy.json"
    path.write_text(json.dumps(toy_manifest_data()))
    return path


@pytest.fixture()
def cpu_device():
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
