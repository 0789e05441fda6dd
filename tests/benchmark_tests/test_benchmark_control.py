"""The control of `correct`, at a size a test run can hold: the reference
computed in fp8 (e4m3 operands, e5m2 gradients, `benchmarks/precision.py`),
put in the program's place, has to fail one of the cell's limits on every
seed, and the sound program none.  On the chip `python -m benchmarks.control`
reads the same two sets of numbers at the cells' own sizes; PERF.md section 4
has them.  The toy limits stand between this file's own readings (CPU, seeds
11-16; `limits/*.json` beside this file has them)."""

import pytest

from benchmarks.control import CellReader
from benchmarks.manifest import Manifest


@pytest.mark.parametrize("workload", ["resnet-toy.train-toy-images", "decoder-toy.train-toy-tokens"])
def test_the_fp8_control_fails_the_limits_the_sound_program_passes(toy_manifest, workload):
    manifest = Manifest(toy_manifest)
    limits = manifest.json("limits", workload)
    reader = CellReader(manifest, workload)
    for seed in (11, 2**31 + 12):
        row = reader.read(seed)
        sound = {r["name"]: r["value"] for r in row["sound"]}
        control = {r["name"]: r["value"] for r in row["control"]}
        assert all(sound[name] <= limits[name] for name in sound), (seed, sound)
        # It is the projections that fp8 fails, by its rounding noise ...
        for name in ("grad_sketch_gap", "head_sketch_gap"):
            assert control[name] > limits[name], (seed, control)
            assert control[name] > 2.5 * sound[name]
        # ... and not by losing its gradients: their norms and the step they
        # give are those of a sound run, which is why no norm can tell fp8.
        assert control["grad_norm_gap"] <= limits["grad_norm_gap"]
        assert control["update_norm_gap"] <= limits["update_norm_gap"]
        lowered = reader.last["control"]
        assert min(lowered["grad_norm"].values()) > 0 and min(lowered["update_norm"].values()) > 0
