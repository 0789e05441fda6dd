"""The dynamic compile-audit sentinel (analysis/compile_audit.py).

Three layers: the CompileWatcher counts real XLA compiles from the
``jax_log_compiles`` stream; ``measure_donation`` observes buffer
deletion directly; ``run_compile_audit`` drives the real Trainer and
must come back clean — steady-state zero-retrace is an acceptance
criterion, so a deliberately-retracing toy step must trip it and the
production step loop must not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.analysis.compile_audit import (
    AUDITED_FILE,
    CompileWatcher,
    PathAudit,
    measure_donation,
    run_compile_audit,
    violations_for,
)
from deeplearning_cfn_tpu.analysis.sharding import (
    AUDIT_RULE_DONATION,
    AUDIT_RULE_RETRACE,
)


# --- CompileWatcher ----------------------------------------------------------


def test_watcher_counts_one_compile_per_program():
    def double(x):
        return x * 2

    fn = jax.jit(double)
    with CompileWatcher() as w:
        fn(jnp.ones(4))
        fn(jnp.ones(4))  # cache hit — must not count
    assert w.compiles.get("double") == 1
    assert w.traces.get("double") == 1
    assert w.retrace_count == 0
    assert w.backend_compiles >= 1


def test_watcher_catches_deliberate_retrace():
    """The seeded bug the sentinel exists for: a step whose cache key
    churns (here: shape) recompiles after the warmup mark."""

    def leaky_step(x):
        return x.sum()

    fn = jax.jit(leaky_step)
    # Inputs made up front: jnp.ones itself dispatches one tiny program
    # per new shape, which would muddy the per-function ledger.
    a4, b4, a5, a6 = jnp.ones(4), jnp.ones(4), jnp.ones(5), jnp.ones(6)
    with CompileWatcher() as w:
        fn(a4)  # warmup compile
        w.mark_steady()
        fn(b4)  # steady: cache hit
        fn(a5)  # shape churn -> silent recompile
        fn(a6)
    # Keyed lookup, not dict equality: lowering sum() dispatches its own
    # internal helper per shape, which is noise here.
    assert w.new_compiles_since_mark()["leaky_step"] == 2
    assert w.new_traces_since_mark()["leaky_step"] == 2
    assert w.retrace_count >= 2
    assert fn._cache_size() == 3


def test_watcher_restores_logging_state():
    import logging

    flag_before = bool(jax.config.jax_log_compiles)
    logger = logging.getLogger("jax._src.dispatch")
    propagate_before = logger.propagate
    with CompileWatcher() as w:
        assert bool(jax.config.jax_log_compiles) is True
        assert w in logger.handlers
    assert bool(jax.config.jax_log_compiles) is flag_before
    assert w not in logger.handlers
    assert logger.propagate is propagate_before


def test_snapshot_shape_is_json_ready():
    import json

    with CompileWatcher() as w:
        jax.jit(lambda x: x + 1)(jnp.ones(2))
    snap = w.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["compile_count"] == sum(snap["compiles"].values())
    assert set(snap) == {
        "traces",
        "compiles",
        "compile_count",
        "retrace_count",
        "backend_compiles",
    }


# --- donation ----------------------------------------------------------------


def test_measure_donation_sees_donated_buffers():
    state = {"w": jnp.ones(256), "b": jnp.ones(4)}
    step = jax.jit(
        lambda s, x: {"w": s["w"] + x.sum(), "b": s["b"]}, donate_argnums=(0,)
    )
    out, report = measure_donation(step, state, jnp.ones(8))
    assert report.effective
    assert report.donated_leaves == 2
    assert report.donated_bytes == 256 * 4 + 4 * 4
    assert out["w"].shape == (256,)


def test_measure_donation_sees_dropped_donation():
    """The DLC411 condition: donate_argnums removed, nothing deleted."""
    state = {"w": jnp.ones(256)}
    step = jax.jit(lambda s, x: {"w": s["w"] + x.sum()})
    _out, report = measure_donation(step, state, jnp.ones(8))
    assert not report.effective
    assert report.donated_bytes == 0
    assert report.retained_leaves == 1


# --- findings + baseline ratchet --------------------------------------------


def test_violations_for_maps_audits_to_dlc41x():
    from deeplearning_cfn_tpu.analysis.compile_audit import DonationReport

    dirty = PathAudit(
        name="single_step",
        steady_steps=4,
        new_compiles={"step_fn": 3},
        donation=DonationReport(0, 1024, 0, 2),
    )
    clean = PathAudit(name="multi_step", steady_steps=4)
    found = violations_for([dirty, clean])
    assert [v.rule for v in found] == [AUDIT_RULE_RETRACE, AUDIT_RULE_DONATION]
    assert all(v.path == str(AUDITED_FILE) for v in found)
    assert "step_fn" in found[0].message
    assert not dirty.clean and clean.clean


def test_dlc41x_findings_ride_the_lint_baseline():
    """Count-free messages: a retrace firing 3x vs 4x across runs is the
    same finding, so the (rule, path, message) key matches either way."""
    from deeplearning_cfn_tpu.analysis.runner import apply_baseline, baseline_key

    three = PathAudit(name="single_step", steady_steps=4, new_compiles={"f": 3})
    four = PathAudit(name="single_step", steady_steps=4, new_compiles={"f": 4})
    (v3,), (v4,) = violations_for([three]), violations_for([four])
    assert baseline_key(v3) == baseline_key(v4)
    fresh, stale = apply_baseline([v4], {baseline_key(v3)})
    assert fresh == [] and stale == []


# --- the real trainer --------------------------------------------------------


@pytest.fixture(scope="module")
def real_audit(tmp_path_factory):
    """One audited run shared by the assertions below (the compile bill
    is the expensive part, not the checks)."""
    from deeplearning_cfn_tpu.obs import recorder

    journal = tmp_path_factory.mktemp("audit") / "flight.jsonl"
    recorder.configure(path=journal)
    try:
        report = run_compile_audit(steady_steps=2, warmup_steps=1, k=2)
    finally:
        recorder.configure()
    return report, journal


def test_real_trainer_reaches_steady_state(real_audit):
    report, _ = real_audit
    assert report.violations == []
    for path in report.paths:
        assert path.clean, path.to_dict()
        assert path.new_compiles == {}
        # One wrapper, one cache entry: the build-once-call-many idiom.
        assert path.cache_size == 1
        assert path.donation is not None and path.donation.effective


def test_real_trainer_compile_counts_are_consistent(real_audit):
    report, _ = real_audit
    watcher = report.watcher
    assert watcher["retrace_count"] == 0
    assert watcher["compiles"].get("train_step") == 1
    assert watcher["compiles"].get("k_steps") == 1
    # The nameless jax.monitoring stream is the independent cross-check.
    assert watcher["backend_compiles"] == watcher["compile_count"]


def test_audit_journals_to_the_flight_recorder(real_audit):
    from deeplearning_cfn_tpu.obs.recorder import read_journal

    report, journal = real_audit
    events = [e for e in read_journal(journal, kind="compile_audit")]
    assert len(events) == 1
    event = events[0]
    assert event["clean"] is True
    assert event["retrace_count"] == 0
    assert set(event["paths"]) == {"single_step", "multi_step"}
    assert report.to_dict()["clean"] is True
