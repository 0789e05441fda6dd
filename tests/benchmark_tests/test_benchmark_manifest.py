"""BENCHMARK.json against its contract: names, units, cross-references, the
files every entry names, the four-chip share and the chip-time budget."""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$|_rank$|head_|expan|experts_per)")

METRICS = DATA["end_to_end"] + DATA["per_layer"]
CELLS = {w["name"]: w for w in DATA["workloads"]}
NAMES = (
    [m["name"] for m in METRICS]
    + list(CELLS)
    + [c["name"] for c in DATA["configs"]]
    + [w["traffic"] for w in DATA["workloads"]]
    + [k for c in DATA["configs"] for k in c["reduced"]]
)


def reported(cell: str) -> set[str]:
    return {m["name"] for m in DATA["end_to_end"] if cell in m.get("workloads", [cell])}


def test_top_level_keys_are_exactly_the_contracts():
    assert set(DATA) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("name", NAMES)
def test_name_uses_only_the_accepted_characters(name):
    assert NAME.match(name), name


def test_no_name_appears_twice():
    for group in ([m["name"] for m in METRICS], list(CELLS), [c["name"] for c in DATA["configs"]]):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in DATA["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if metric in DATA["end_to_end"] else {"layer", "moves"}
    assert set(metric) <= allowed and {"name", "unit", "better", "source"} <= set(metric)
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if metric in DATA["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("metric", DATA["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_each_of_its_cells_reports(metric):
    assert metric["moves"] in {m["name"] for m in DATA["end_to_end"]}
    cells = metric.get("workloads") or [c for c in CELLS if metric["moves"] in reported(c)]
    assert cells
    for cell in cells:
        assert metric["moves"] in reported(cell)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader_of_its_own(metric):
    folder = "end_to_end" if metric in DATA["end_to_end"] else "layer_metrics"
    text = (REPO / "benchmarks" / folder / f"{metric['name']}.py").read_text()
    assert "def read(run" in text


@pytest.mark.parametrize("cell", list(CELLS.values()), ids=lambda w: w["name"])
def test_cell_resolves_to_its_files_and_reports_enough(cell):
    from benchmarks.manifest import Manifest

    manifest = Manifest()
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    config = manifest.config(cell["config"])
    traffic = manifest.json("traffic", cell["traffic"])
    limits = manifest.json("limits", cell["name"])
    assert traffic["kind"] == "train" and traffic["global_batch"] % cell["chips"] == 0
    assert set(limits) == {
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap",
        "readings",
    }
    for folder in ("builders", "reference", "flops"):
        assert manifest.find(folder, f"{config['kind']}.py").is_file()
    got = reported(cell["name"])
    assert "setup_s" in got and len(got) >= 2
    assert manifest.per_layer_for(cell["name"])


@pytest.mark.parametrize("config", DATA["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in DATA["paths"])
    body = json.loads((REPO / config["file"]).read_text())
    assert body["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert not WIDTH.search(key), f"{key} is a width and may not be reduced"
    assert any(w["config"] == config["name"] for w in DATA["workloads"])
    assert 1 <= len(config["source"]) <= 200


def test_files_are_one_per_configuration():
    files = [c["file"] for c in DATA["configs"]]
    assert len(files) == len(set(files))


def test_mistral_widths_are_the_published_ones():
    body = json.loads((REPO / "benchmarks/configs/mistral-7b-v0.3.json").read_text())
    published = {
        "hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
        "num_key_value_heads": 8, "vocab_size": 32768, "rms_norm_eps": 1e-5,
        "rope_theta": 1e6, "max_position_embeddings": 32768, "tie_word_embeddings": False,
        "sliding_window": None, "torch_dtype": "bfloat16", "hidden_act": "silu",
    }
    assert {k: body[k] for k in published} == published
    assert body["published"]["num_hidden_layers"] == 32


def test_at_most_a_quarter_of_the_cells_and_always_one_may_take_four_chips():
    four = [w for w in DATA["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(DATA["workloads"]) // 4)


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = next(m for m in DATA["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_a_full_check_of_24_cells_fits_the_chip_time():
    seconds = DATA["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_command_and_paths():
    assert DATA["command"] == ["python3", "-m", "benchmarks.run"]
    assert DATA["paths"] == ["benchmarks", "tests/benchmark_tests"]
    for word in DATA["command"]:
        assert not word.startswith("/") and ".." not in word


def test_peaks_are_keyed_by_device_kind_with_their_source():
    peaks = json.loads((REPO / "benchmarks/peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "cpu" not in peaks
