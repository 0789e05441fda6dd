"""`correct` has to come out false when it should: here with the timed path
broken underneath a whole run (test_benchmark_control.py has the
lower-precision control), and the comparison's arithmetic by hand."""

import time

import pytest

from benchmarks import check
from benchmarks import run as bench_run


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
    toy_manifest, cpu_device, tmp_path, monkeypatch
):
    from deeplearning_cfn_tpu.train.trainer import Trainer

    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    sound = Trainer._raw_step_fn

    def broken(self):
        step = sound(self)

        def keeps_its_state(state, x, y):
            new, metrics = step(state, x, y)
            return state.replace(step=new.step), metrics

        return keeps_its_state

    monkeypatch.setattr(Trainer, "_raw_step_fn", broken)
    line, notes = bench_run.run_cell(
        toy_manifest, "resnet-toy.train-toy-images", seed=5, seconds=0.3, trace=0,
        device=cpu_device, peaks=bench_run.load_peaks()["TPU v5 lite"],
        t_process=time.perf_counter(),
    )
    assert line["correct"] is False
    assert notes[1]["conditions"]["parameters_moved"] is False
    rows = {r["name"]: r for r in notes[0]["check"]}
    assert rows["update_norm_gap"]["ok"] is False and rows["update_norm_gap"]["value"] == pytest.approx(1.0)
    # the optimizer's state did not move either, so no first gradient is read from it
    assert rows["grad_norm_gap"]["ok"] is False and rows["grad_sketch_gap"]["ok"] is False
    assert rows["head_sketch_gap"]["ok"] is False


def test_worst_leaf_gap_measures_against_the_median_leaf_where_a_norm_is_all_but_zero():
    reference = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, leaf = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 3e-9}, reference)
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, leaf = check.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5}, reference)
    assert leaf == "c" and gap == pytest.approx(0.5)
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, reference)
    assert check.worst_leaf_gap({"a": float("nan"), "b": 2.0, "c": 0.0}, reference)[0] == float("inf")


def test_sketch_gap_is_the_root_mean_square_over_leaves_against_the_floored_norm():
    norms = {"a": 1.0, "b": 2.0, "c": 1e-9}
    reference = {"a": [0.5, 0.1], "b": [-1.0, 1.0], "c": [1e-9, 0.0]}
    gap, leaf = check.sketch_gap({"a": [0.8, 0.5], "b": [-1.0, 1.0], "c": [1e-9, 0.0]}, reference, norms)
    assert leaf == "a" and gap == pytest.approx(((0.3**2 + 0.4**2) / 2 / 3) ** 0.5)
    # a leaf whose gradient is all but zero is measured against the median leaf's norm
    gap, leaf = check.sketch_gap({"a": [0.5, 0.1], "b": [-1.0, 1.0], "c": [0.4, 0.0]}, reference, norms)
    assert leaf == "c" and gap == pytest.approx((0.4**2 / 2 / 3) ** 0.5)
    # the opposite sign at full size: the norm would not see it, the projections do
    flipped = {k: [-x for x in v] for k, v in reference.items()}
    assert check.sketch_gap(flipped, reference, norms)[0] == pytest.approx(
        ((1.0**2 + 0.2**2) / 2 / 3 + (2.0**2 + 2.0**2) / 2 / 4 / 3) ** 0.5)
    # over some leaves only: the floor is still the median over all of them
    gap, leaf = check.sketch_gap(flipped, reference, norms, leaves=["b", "c"])
    assert leaf == "b" and gap == pytest.approx(((2.0**2 + 2.0**2) / 2 / 4 / 2) ** 0.5)
    with pytest.raises(ValueError):
        check.sketch_gap({"a": [1.0, 1.0]}, reference, norms)
    with pytest.raises(ValueError):
        check.sketch_gap({**reference, "a": [1.0]}, reference, norms)
    broken = {"a": [float("nan"), 0.0], "b": [0.0, 0.0], "c": [0.0, 0.0]}
    assert check.sketch_gap(broken, reference, norms) == (float("inf"), "a")


def test_compare_gives_each_number_its_own_limit():
    program = {"loss": [2.0, 3.0, 4.0], "grad_norm": {"w": 1.0}, "grad_sketch": {"w": [0.7, 0.5]},
               "update_norm": {"w": 0.0}}
    reference = {"loss": [2.0, 3.3], "grad_norm": {"w": 1.0}, "grad_sketch": {"w": [0.5, 0.3]},
                 "head_leaves": ["w"], "update_norm": {"w": 1.0}}
    limits = {"loss_gap": 0.2, "grad_norm_gap": 0.0, "grad_sketch_gap": 0.1,
              "head_sketch_gap": 0.3, "update_norm_gap": 0.5}
    rows = check.compare(program, reference, limits)
    assert [(r["name"], r["ok"]) for r in rows] == [
        ("loss_gap", True), ("grad_norm_gap", True), ("grad_sketch_gap", False),
        ("head_sketch_gap", True), ("update_norm_gap", False)]
    assert rows[0]["value"] == pytest.approx(0.3 / 3.3) and rows[4]["value"] == 1.0
    assert rows[2]["value"] == rows[3]["value"] == pytest.approx(0.2)
