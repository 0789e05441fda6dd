"""The readers this PR added: idle time by the program's spans
(`host_spans.py`), device time by the step's named scopes (`scope_reduce.py`)
and the per-layer metrics on top of them: on made-up rows and run records,
and on recordings from the chip (PR 24, TPU v5 lite) that hold the program's
host rows and the operations' op_names beside device 0's last programs."""

import json
from pathlib import Path

import pytest

from benchmarks import host_spans, scope_reduce
from benchmarks import trace_reduce as tr
from benchmarks.manifest import Manifest

DATA = Path(__file__).resolve().parent / "data"
P0 = "/device:TPU:0"
MS = 1_000_000
NEW_METRICS = (
    "idle_attributed_share", "idle_in_sync_ms_per_step", "idle_in_dispatch_ms_per_step",
    "idle_in_input_ms_per_step", "host_dispatch_ms_per_step", "forward_ms_per_step",
    "backward_ms_per_step", "recompute_ms_per_step", "optimizer_ms_per_step",
    "attention_backward_ms_per_step", "device_scope_coverage", "setup_trace_lower_s",
    "setup_compile_load_s", "collective_exposed_ms_per_step",
)


def reader(name):
    return Manifest().module("layer_metrics", name)


def op(name, start, dur, line=tr.OP_LINE):
    return [P0, line, name, start, dur]


# --- the classification rule -------------------------------------------------


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(train_step)/optimizer/mul", "optimizer"),
        ("jit(train_step)/optimizer/transpose(x)/mul", "optimizer"),
        ("jit(train_step)/loss/jvp(mlp)/dot_general", "forward"),
        ("jit(train_step)/loss/jvp()/while/body/closed_call/attn/core/jit(_flash_forward)/_flash_forward/pallas_call", "forward"),
        ("jit(train_step)/loss/transpose(jvp(mlp))/dot_general", "backward"),
        ("jit(train_step)/loss/transpose(jvp())/while/body/closed_call/checkpoint/attn/core/attn_bwd/dot_general", "backward"),
        # A rematerialised forward pass lies under transpose(jvp()) too.
        ("jit(train_step)/loss/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general", "recompute"),
        ("jit(train_step)/loss/while/body/transpose(jvp(ResNet))/stage1_block1/conv1/conv_general_dilated", "backward"),
        ("jit(train_step)/input/convert_element_type", "input"),
        ("jit(train_step)/attn/rope/mul", "unscoped"),
        ("jit(train_step)/jit(_loss)/add", "unscoped"),
        ("", "unscoped"),
    ],
)
def test_classification_rule(op_name, want):
    assert scope_reduce.classify(op_name) == want


def test_a_scope_is_a_component_or_sits_in_a_transformations_wrapper():
    assert scope_reduce.has_scope("jit(f)/loss/transpose(jvp(attn))/mul", "attn")
    assert scope_reduce.has_scope("jit(f)/loss/attn/core/attn_bwd/mul", "attn_bwd")
    assert not scope_reduce.has_scope("jit(f)/loss/attn_norm/mul", "attn")
    assert not scope_reduce.has_scope("jit(f)/jit(_loss)/mul", "loss")


def test_scope_reduce_sums_device_time_by_class_and_leaves_containers_out():
    names = {
        "fusion.1": "jit(s)/loss/jvp(mlp)/dot_general",
        "fusion.2": "jit(s)/loss/transpose(jvp(mlp))/dot_general",
        "fusion.3": "jit(s)/loss/transpose(jvp())/checkpoint/rematted_computation/mlp/dot",
        "fusion.4": "jit(s)/optimizer/mul",
        "fusion.5": "jit(s)/loss/transpose(jvp())/attn/core/attn_bwd/dot_general",
        "while.1": "jit(s)/loss/jvp()/while",
    }
    rows = [
        op("jit_s(1)", 0, 100, line=tr.MODULE_LINE),
        op("%while.1 = () while()", 0, 60),
        op("%fusion.1 = f32[] fusion()", 0, 30),
        op("%fusion.2 = f32[] fusion()", 30, 20),
        op("%fusion.5 = f32[] fusion()", 50, 10),
        op("%fusion.3 = f32[] fusion()", 60, 15),
        op("%fusion.4 = f32[] fusion()", 75, 5),
        op("%copy.9 = f32[] copy()", 80, 10),
    ]
    out = scope_reduce.reduce(rows, names)
    assert out["seconds"] == {
        "forward": 30e-9, "backward": 30e-9, "recompute": 15e-9, "optimizer": 5e-9,
        "input": 0.0, "unscoped": 10e-9,
    }
    assert out["busy_s"] == 90e-9 and out["classified_s"] == 80e-9
    assert out["attention_backward_s"] == 10e-9
    # a program that names nothing gives nothing
    assert scope_reduce.reduce(rows, {}) is None
    assert scope_reduce.reduce(rows, {"fusion.1": "jit(s)/jvp(mlp)/dot_general"}) is None


# --- idle time by host span --------------------------------------------------


def made_up_run():
    """Three programs of 10 ms: 15 us between the first two, 3 ms (a drain)
    before the third."""
    rows = []
    for start in (0, 10 * MS + 15_000, 23 * MS + 15_000):
        rows += [op("jit_train_step(1)", start, 10 * MS, line=tr.MODULE_LINE),
                 op("%fusion.1 = f32[] fusion()", start, 6 * MS),
                 op("%fusion.2 = f32[] fusion()", start + 6 * MS, 4 * MS)]
    t = 7
    host = [
        [t, "fit.step", 100, 2 * MS], [t, "fit.dispatch", 500, 400_000],
        [t, "fit.step", 3 * MS, 18_500_000], [t, "fit.dispatch", 3_100_000, 400_000],
        [t, "fit.sync", 4 * MS, 16_215_000],       # ends 0.2 ms after the device does
        [t, "fit.log", 20_300_000, 1 * MS],
        [t, "fit.data_wait", 21_600_000, 200_000],
        [t, "fit.step", 21_900_000, 3 * MS], [t, "fit.h2d", 22 * MS, 100_000],
        [t, "fit.dispatch", 22_200_000, 900_000],
        [9, "prefetch.h2d", 0, 5 * MS],
    ]
    names = {"fusion.1": "jit(train_step)/loss/jvp(m)/dot", "fusion.2": "jit(train_step)/optimizer/mul"}
    return {
        "trace_rows": rows, "trace": tr.reduce(rows), "host_rows": host, "op_names": names,
        "traffic": {"input": "images"}, "chips": 1,
    }


def test_idle_time_is_put_down_to_the_innermost_seam_that_covers_it():
    run = made_up_run()
    out = host_spans.attributed(run)
    assert out["idle_ns"] == 3 * MS + 15_000 and out["programs"] == 3
    assert out["by_span_ns"] == {
        "fit.data_wait": 200_000, "fit.dispatch": 815_000, "fit.h2d": 100_000,
        "fit.log": 1 * MS, "fit.sync": 215_000, "fit.step": 485_000,
    }
    assert out["unattributed_ns"] == 200_000
    assert sum(out["by_span_ns"].values()) + out["unattributed_ns"] == out["idle_ns"]
    clocks = out["clocks"]
    assert clocks["syncs"] == 1 and clocks["shift_ns"] == 0
    assert clocks["sync_lag_ms"] == [0.2, 0.2, 0.2]
    assert clocks["bracket_ms"] == [-0.2, 0.815]
    assert run["notes"]["host_spans"]["by_span_ms"]["fit.log"] == 1.0


def test_a_clock_that_breaks_causality_is_shifted_to_the_brackets_middle():
    run = made_up_run()
    # the host's clock 2 ms ahead of the device's: the sync would end 2.2 ms
    # after the device did, and the device would start before its dispatch
    run["host_rows"] = [[t, n, s + 2 * MS, d] for t, n, s, d in run["host_rows"]]
    out = host_spans.attributed(run)
    assert out["clocks"]["bracket_ms"] == [-2.2, -1.185]
    assert out["clocks"]["shift_ns"] == -1_692_500
    # what was 0.2 and 0.815 ms of slack either way is now split evenly
    assert out["by_span_ns"]["fit.log"] == 1 * MS
    assert abs(out["by_span_ns"]["fit.sync"] - out["by_span_ns"]["fit.dispatch"]) <= 300_000


def test_the_new_readers_on_a_made_up_run():
    run = made_up_run()
    got = {name: reader(name).read(run) for name in NEW_METRICS}
    assert got["idle_attributed_share"] == pytest.approx(100 * (1 - 200_000 / 3_015_000))
    assert got["idle_in_sync_ms_per_step"] == pytest.approx(1.215 / 3)
    assert got["idle_in_dispatch_ms_per_step"] == pytest.approx(0.815 / 3)
    assert got["idle_in_input_ms_per_step"] == pytest.approx(0.3 / 3)
    assert got["forward_ms_per_step"] == pytest.approx(6.0)
    assert got["optimizer_ms_per_step"] == pytest.approx(4.0)
    assert got["device_scope_coverage"] == pytest.approx(100.0)
    assert got["collective_exposed_ms_per_step"] == 0.0
    # nothing transposed, rematerialised or under attn_bwd here
    assert got["backward_ms_per_step"] is None and got["recompute_ms_per_step"] is None
    assert got["attention_backward_ms_per_step"] is None
    assert run["notes"]["scope_reduce"]["ms_per_step"]["forward"] == pytest.approx(6.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_run_without_a_trace_or_without_the_programs_names_reads_nothing(name):
    """What the parent of this PR gives: device rows, no spans, no scopes,
    no counters.  Every reader but the collectives' returns None and none
    raises."""
    rows = made_up_run()["trace_rows"]
    bare = {"trace_rows": rows, "trace": tr.reduce(rows), "host_rows": [], "op_names": {},
            "traffic": {"input": "images"}, "chips": 1}
    untraced = {"traffic": {"input": "images"}, "chips": 1}
    assert reader(name).read(untraced) is None
    value = reader(name).read(bare)
    if name == "collective_exposed_ms_per_step":
        assert value == 0.0
    elif name in ("host_dispatch_ms_per_step", "setup_trace_lower_s", "setup_compile_load_s"):
        # these read the process's own aggregates and counters: whatever
        # other tests left there, a number or nothing
        assert value is None or value >= 0
    else:
        assert value is None


def test_the_counters_readers_read_what_fit_froze(monkeypatch):
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()
    for name, value in (("compile.trace_s", 1.5), ("compile.trace_s", 0.5), ("compile.lower_s", 0.25),
                        ("compile.backend_s", 3.0), ("compile.cache_hit", 1.0)):
        tracing.counter(name, value)
    tracing.freeze_counters("compile.", "first_step.")
    tracing.counter("compile.backend_s", 100.0)  # the reference's compile, afterwards
    for seconds in (9.0, 0.001, 0.003):
        monkeypatch.setattr(tracing, "_perf_counter", iter([0.0, seconds]).__next__)
        with tracing.span("fit.dispatch", journal=False):
            pass
    run = made_up_run()
    assert reader("setup_trace_lower_s").read(run) == 2.25
    assert reader("setup_compile_load_s").read(run) == 3.0
    assert run["notes"]["first_step_compile"]["trace_s"] == {"count": 2, "total": 2.0}
    # the mean leaves the longest call out: the first, which compiled
    assert reader("host_dispatch_ms_per_step").read(run) == pytest.approx(2.0)
    tracing.reset_aggregates()
    assert reader("setup_trace_lower_s").read(run) is None


# --- on recordings from the chip ---------------------------------------------


def recording(name):
    c = json.loads((DATA / name).read_text())
    rows = [[c["planes"][p], c["lines"][l], c["names"][n], s, d] for p, l, n, s, d in c["rows"]]
    return {
        "trace_rows": rows, "trace": tr.reduce(rows), "host_rows": c["host_rows"],
        "op_names": c["op_names"], "traffic": {"input": "images"}, "chips": 1,
    }, c


RECORDINGS = sorted(p.name for p in DATA.glob("spans_*.json"))


@pytest.mark.parametrize("name", RECORDINGS)
def test_recordings_hold_host_rows_and_the_name_stat(name):
    run, c = recording(name)
    assert c["device"] == "TPU v5 lite" and c["origin_of_host_rows"] in ("program", "host_plane")
    assert {r[1] for r in run["host_rows"]} >= {
        "fit.step", "fit.data_wait", "fit.h2d", "fit.dispatch", "fit.sync", "fit.log", "prefetch.h2d",
    }
    assert len(run["op_names"]) > 100
    assert any("/optimizer/" in v for v in run["op_names"].values())


@pytest.mark.parametrize("name", RECORDINGS)
def test_idle_time_on_a_recording_lies_under_the_fit_seams(name):
    run, _ = recording(name)
    out = host_spans.attributed(run)
    assert out["idle_ns"] > 0
    assert sum(out["by_span_ns"].values()) + out["unattributed_ns"] == out["idle_ns"]
    assert reader("idle_attributed_share").read(run) >= 95.0
    lags = out["clocks"]["sync_lag_ms"]
    assert out["clocks"]["syncs"] >= 1 and -3.0 < lags[0] <= lags[2] < 3.0
    # the device waits for the host where fit drains, logs, pulls a batch
    # and dispatches: those four hold nearly all of it
    named = sum(
        reader(n).read(run)
        for n in ("idle_in_sync_ms_per_step", "idle_in_dispatch_ms_per_step", "idle_in_input_ms_per_step")
    )
    assert named * out["programs"] * 1e6 >= 0.9 * out["idle_ns"]


@pytest.mark.parametrize("name", RECORDINGS)
def test_device_time_on_a_recording_is_classified_but_for_the_compilers_copies(name):
    run, _ = recording(name)
    out = scope_reduce.reduced(run)
    decoder = "mistral" in name
    # ResNet-50's step spends 6% of its busy time in copies between memory
    # spaces that the compiler put in and that carry no op_name.
    assert reader("device_scope_coverage").read(run) >= (99.0 if decoder else 93.0)
    if not decoder:
        assert list(out["unscoped_kinds_s"])[0] == "copy-done"
    parts = ("forward", "backward", "recompute", "optimizer", "input")
    assert sum(out["seconds"][p] for p in parts) == pytest.approx(
        out["busy_s"], rel=0.01 if decoder else 0.07
    )
    assert sum(out["seconds"].values()) == pytest.approx(out["busy_s"], rel=1e-3)
    assert out["seconds"]["backward"] > out["seconds"]["forward"] > out["seconds"]["optimizer"] > 0
    if decoder:
        assert out["seconds"]["recompute"] > 0 and out["attention_backward_s"] > 0
    else:
        assert out["seconds"]["recompute"] == 0
