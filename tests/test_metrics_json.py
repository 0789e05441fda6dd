"""NaN-safe metrics JSON (ISSUE 1 satellite: scripts/chip_measure.py:101).

On CPU/GPU test backends ``peak_hbm_bytes_per_chip()`` is None; the old
``peak or float("nan")`` fallback made chip_measure emit ``"mbu": NaN`` —
a bare token that is NOT JSON, so every strict consumer of the bench
stream choked.  The fix routes every emitter through
:func:`train.metrics.utilization` / :func:`train.metrics.json_safe` with
``allow_nan=False``; these tests pin the helpers and strictly parse the
exact record shapes the emitters produce.
"""

import json
import math

import pytest

from deeplearning_cfn_tpu.train.metrics import (
    JsonlMetricsSink,
    json_safe,
    utilization,
)


def strict_loads(s: str):
    """json.loads that rejects the NaN/Infinity extensions outright."""

    def reject(token):
        raise ValueError(f"non-JSON token {token!r} in metrics output")

    return json.loads(s, parse_constant=reject)


def test_strict_loads_rejects_bare_nan():
    """The regression harness itself must catch the old failure shape."""
    with pytest.raises(ValueError, match="NaN"):
        strict_loads('{"mbu": NaN}')


# --- utilization: the MFU/MBU ratio ----------------------------------------

def test_utilization_none_propagation():
    assert utilization(None, 900e9) is None  # no measurement
    assert utilization(1.0e9, None) is None  # unknown device peak
    assert utilization(1.0e9, 0) is None     # degenerate denominator
    assert utilization(None, None) is None


def test_utilization_computes_and_rounds():
    assert utilization(45.0, 100.0) == 0.45
    assert utilization(1.0, 3.0) == round(1 / 3, 4)
    assert utilization(1.0, 3.0, ndigits=2) == 0.33


def test_utilization_maps_nonfinite_to_none():
    assert utilization(float("nan"), 1.0) is None
    assert utilization(float("inf"), 1.0) is None
    assert utilization(1.0, float("inf")) is None or utilization(
        1.0, float("inf")
    ) == 0.0  # inf denominator underflows to 0.0: a finite, valid ratio


# --- json_safe: the recursive sanitizer ------------------------------------

def test_json_safe_maps_nonfinite_to_null_recursively():
    record = {
        "loss": float("nan"),
        "mfu": float("inf"),
        "nested": {"v": [-float("inf"), 1.5, float("nan")]},
        "ok": 3,
        "name": "throughput",
    }
    safe = json_safe(record)
    assert safe["loss"] is None
    assert safe["mfu"] is None
    assert safe["nested"]["v"] == [None, 1.5, None]
    assert safe["ok"] == 3 and safe["name"] == "throughput"
    # And the sanitized record serializes strictly.
    strict_loads(json.dumps(safe, allow_nan=False))


def test_json_safe_preserves_finite_floats_exactly():
    assert json_safe(0.4471) == 0.4471
    assert json_safe([1, 2.5]) == [1, 2.5]


# --- the chip_measure record shapes ----------------------------------------

def test_decode_record_with_unknown_peak_emits_null_mbu():
    """The exact decode-mode emitter expression from scripts/chip_measure.py
    with peak_hbm_bytes_per_chip() -> None (any non-TPU backend): "mbu"
    must round-trip as null, and the line must parse strictly."""
    param_bytes, step_s, peak_bw = 2 * 435e6, 0.004, None  # CPU: peak unknown
    line = json.dumps(json_safe({
        "mode": "decode",
        "param_bytes": param_bytes,
        "ms_per_step": round(1000 * step_s, 2),
        "mbu": utilization(param_bytes / step_s, peak_bw),
    }), allow_nan=False)
    record = strict_loads(line)
    assert record["mbu"] is None
    assert record["ms_per_step"] == 4.0


def test_decode_record_with_known_peak_computes_mbu():
    param_bytes, step_s, peak_bw = 2 * 435e6, 0.004, 819e9  # v5e figure
    mbu = utilization(param_bytes / step_s, peak_bw)
    record = strict_loads(json.dumps({"mbu": mbu}, allow_nan=False))
    assert record["mbu"] == pytest.approx(param_bytes / step_s / peak_bw, abs=1e-4)


def test_throughput_record_with_unknown_peak_emits_null_mfu():
    mfu = utilization(1.23e12, None)
    line = json.dumps(json_safe({"mode": "throughput", "mfu": mfu}),
                      allow_nan=False)
    assert strict_loads(line)["mfu"] is None


# --- the training metrics sink ---------------------------------------------

def test_jsonl_sink_writes_nan_loss_as_null(tmp_path):
    """A NaN loss mid-run must land in the stream as null — not crash the
    trainer (allow_nan=False alone raises) and not emit a bare NaN token."""
    sink = JsonlMetricsSink(tmp_path / "w0.jsonl")
    sink.write({"event": "train_step", "step": 10, "loss": float("nan"),
                "examples_per_sec": 512.0})
    sink.close()
    lines = (tmp_path / "w0.jsonl").read_text().splitlines()
    assert len(lines) == 1
    record = strict_loads(lines[0])
    assert record["loss"] is None
    assert record["examples_per_sec"] == 512.0
    assert math.isfinite(record["ts"])


def test_throughput_window_closes_when_the_step_has_finished():
    """Dispatch is asynchronous: the loss handed to the logger is a device
    scalar that is not ready yet.  The window must close after waiting for
    it — closed at enqueue time, this step's device time lands in the next
    window and the rate reads too high."""
    import time

    from deeplearning_cfn_tpu.train.metrics import ThroughputLogger

    class PendingLoss:
        def __float__(self):
            time.sleep(0.05)  # the device finishing the step
            return 1.0

    logger = ThroughputLogger(global_batch_size=10, log_every=1)
    logger.step(1, PendingLoss())
    assert logger.history[0]["examples_per_sec"] <= 10 / 0.05
