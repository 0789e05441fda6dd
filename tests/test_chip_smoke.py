"""chip_smoke.py: each phase at toy size on the CPU mesh, and the refusal.

The phases are plain functions of their sizes, so the same code the chip
runs at full width runs here small: the template → cluster → ResNet-50
trainer path through ``dlcfn run``, a tiny Llama through
``examples.llama_train``, the Pallas kernels in interpret mode (asked for by
name), and the serving engine against its references.  What only a chip
can show — an MFU, a Mosaic call in the lowered step, allocator stats — is
switched off by name here and is what ``python chip_smoke.py`` itself is
for; under ``JAX_PLATFORMS=cpu`` that command must refuse.
"""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def test_refuses_a_cpu_without_training_or_printing_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "refusing to run" in proc.stderr and "'platform': 'cpu'" in proc.stderr
    # Refused before any phase: nothing was provisioned or compiled.
    assert "trainer ..." not in proc.stderr


def test_trainer_phase_runs_dlcfn_run_on_the_committed_template(tmp_path, monkeypatch):
    root = tmp_path / "root"
    # The phase points DLCFN_ROOT at its root for the rest of the process;
    # monkeypatch puts back what was there before.
    monkeypatch.setenv("DLCFN_ROOT", str(root))
    report = chip_smoke.trainer_phase(
        root, batch_per_chip=1, image_size=32, steps=2, expect_mfu=False
    )
    n = len(jax.devices())
    assert report["contract"] == {"workers": n, "chips_per_worker": 1, "total_chips": n}
    assert report["global_batch"] == n and report["steps"] == 2
    assert sorted(int(k) for k in report["state_bytes_by_device"]) == list(range(n))
    assert report["template_to_first_step_s"] >= report["first_step_s"]
    assert (root / "contract.json").exists()


def test_training_checks_catch_a_run_that_did_not_train():
    good = {
        "losses": [1.0, 2.0], "step": 2, "params_changed": True, "first_step_s": 1.5,
        "history": [{"step": 2, "loss": 2.0, "examples_per_sec": 10.0, "mfu": 0.3}],
        "state_bytes_by_device": {d.id: 8 for d in jax.devices()},
        "batch_bytes_by_device": {d.id: 4 for d in jax.devices()},
    }
    chip_smoke.check_training_run(good, steps=2, expect_mfu=True)
    for broken in (
        {"losses": [1.0, float("nan")]},
        {"step": 1},
        {"params_changed": False},
        {"first_step_s": None},
        {"history": [{"step": 2, "loss": 2.0, "examples_per_sec": 10.0}]},  # mfu: null
        {"batch_bytes_by_device": {0: 4}},  # everything on device 0
    ):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.check_training_run({**good, **broken}, steps=2, expect_mfu=True)


def test_llama_phase_and_the_lowering_it_inspects():
    report = chip_smoke.llama_phase(
        size="tiny", seq_len=32, batch_per_chip=1, steps=2,
        expect_mosaic=False, expect_mfu=False,
    )
    n = len(jax.devices())
    fsdp, tp = chip_smoke.llama_layout(n)
    assert (report["mesh"]["fsdp"], report["mesh"]["tp"]) == (fsdp, tp) == (n // 2, 2)
    assert report["attention"] == "xla"  # no Mosaic off a TPU, by attention_kind
    text = chip_smoke.lowered_llama_step("tiny", 32, n, fsdp, tp)
    assert "stablehlo" in text and "tpu_custom_call" not in text
    assert chip_smoke.llama_layout(1) == (1, 1) and chip_smoke.llama_layout(4) == (2, 2)


def test_kernel_phase_in_interpret_mode_asked_for_by_name():
    report = chip_smoke.kernel_phase(
        attention_cases=((2, 50, 4, 2, 16, "bfloat16"),),  # ragged, GQA
        dense_cases=((48, 96, 200, "gelu", "float32"),),
        interpret=True,
    )
    assert report["interpret"] is True
    assert len(report["attention"]) == 1 and len(report["dense"]) == 1
    # On the CPU mesh the compiled kernels are an error, not a silent fallback.
    with pytest.raises(ValueError, match="interpret mode"):
        chip_smoke.kernel_phase(
            attention_cases=((1, 64, 4, 2, 16, "float32"),), dense_cases=()
        )


def test_serving_phase_against_its_references():
    from deeplearning_cfn_tpu.models import llama

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, seq_len=32), dtype=jnp.float32
    )
    report = chip_smoke.serving_phase(
        cfg=cfg, num_slots=2, block_size=4, blocks_per_slot=8, prefill_len=16,
        requests=((3, 4), (9, 2), (16, 4)), tolerance=1e-4,
    )
    assert report["prefills"] == 3 and report["recycled_blocks"] > 0
    assert report["first_tokens_matching_generate"] == "3/3"
    assert list(report["params_bytes_by_device"]) == [jax.devices()[0].id]
    with pytest.raises(chip_smoke.SmokeFailure, match="below the reference"):
        # No run can meet a negative tolerance: the gate is live.
        chip_smoke.serving_phase(
            cfg=cfg, num_slots=2, block_size=4, blocks_per_slot=8, prefill_len=16,
            requests=((3, 2),), tolerance=-1.0,
        )
