"""The dynamic comms-audit sentinel (analysis/comms_audit.py).

Three layers: the HLO readout (``hlo_collectives`` must parse sync and
async collective instructions with exact byte counts), the DLC511
golden program (a deliberately missing ``with_sharding_constraint`` on
an 8-virtual-device fsdp step makes XLA materialize the batch
replicated — the sentinel must name that gather, and the constrained
variant must come back parameter-gathers-only), and ``run_comms_audit``
driving the real Trainer: every program yields a non-empty budget that
matches scripts/comms_budget.json exactly, and every finding on the
repo's own hot path is already captured in the ratcheted baseline.

Plus the DLC512 overlap instrument: ``schedule_overlap`` must read
compute slack per collective issue point out of scheduled HLO text
(async ``-start``/``-done`` pairs included), and ``violations_for``
must fire when a ``*_overlap`` program fails to strictly beat its
monolithic baseline or when a program's score falls below the
committed budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning_cfn_tpu.analysis.collectives import (
    AUDIT_RULE_BUDGET,
    AUDIT_RULE_IDS,
    AUDIT_RULE_OVERLAP,
    AUDIT_RULE_UNPREDICTED,
)
from deeplearning_cfn_tpu.analysis.comms_audit import (
    AUDITED_FILE,
    CommsWatcher,
    ProgramComms,
    StrategyPrediction,
    hlo_collectives,
    hlo_computation_ops,
    load_budget,
    run_comms_audit,
    schedule_overlap,
    violations_for,
    write_budget,
)

#: every program the real audit lowers (the fsdp trio plus the dp
#: comms-overlap pair and its scanned multi-step variant)
AUDITED_PROGRAMS = {
    "train_step",
    "multi_step",
    "train_step_dp",
    "train_step_dp_overlap",
    "multi_step_dp_overlap",
    "serve_decode",
}

# --- the HLO readout ---------------------------------------------------------


def test_hlo_collectives_reads_sync_and_async_ops():
    """Async ``-start`` ops count once (their ``-done`` halves carry the
    same bytes) and tuple result shapes keep the u32 control member."""
    hlo = """\
  %ag = f32[16,64]{1,0} all-gather(f32[2,64]{1,0} %p0), replica_groups={}
  %ars = (f32[16,8]{1,0}, u32[]) all-reduce-start(f32[16,8]{1,0} %x), to_apply=%sum
  %ard = f32[16,8]{1,0} all-reduce-done((f32[16,8]{1,0}, u32[]) %ars)
  %rs = bf16[4,4]{1,0} reduce-scatter(bf16[32,4]{1,0} %y), dimensions={0}
"""
    ops = hlo_collectives(hlo)
    assert [(o.op, o.result_shapes) for o in ops] == [
        ("all-gather", ((16, 64),)),
        ("all-reduce", ((16, 8), ())),
        ("reduce-scatter", ((4, 4),)),
    ]
    # f32[16,64] = 4096 B; f32[16,8] + u32[] = 512 + 4; bf16[4,4] = 32.
    assert [o.nbytes for o in ops] == [4096, 516, 32]


def test_hlo_collectives_ignores_non_collective_ops():
    hlo = "  %d = f32[16,64]{1,0} dot(f32[16,8]{1,0} %a, f32[8,64]{1,0} %b)\n"
    assert hlo_collectives(hlo) == []


# --- the schedule-overlap readout --------------------------------------------

_SCHEDULED_HLO = """\
ENTRY %main (p0: f32[16,64]) -> f32[16,64] {
  %p0 = f32[16,64]{1,0} parameter(0)
  %ar = f32[16,64]{1,0} all-reduce(f32[16,64]{1,0} %p0), to_apply=%sum
  %m1 = f32[16,64]{1,0} multiply(f32[16,64]{1,0} %ar, f32[16,64]{1,0} %p0)
  %m2 = f32[16,64]{1,0} add(f32[16,64]{1,0} %m1, f32[16,64]{1,0} %p0)
  ROOT %ag = f32[16,64]{1,0} all-gather(f32[16,64]{1,0} %m2), replica_groups={}
}
"""


def test_schedule_overlap_counts_slack_between_issue_points():
    """First all-reduce has 2 ops of slack before the next collective;
    the final all-gather ends the computation with 0 — serialized."""
    overlap = schedule_overlap(_SCHEDULED_HLO)
    assert overlap == {
        "overlap_score": 1.0,
        "serialized_collectives": 1,
        "scheduled_collectives": 2,
    }


def test_schedule_overlap_async_done_is_a_boundary_not_an_issue_point():
    """The ops between -start and -done ARE the start's slack; the -done
    half must not count as a second collective issue."""
    hlo = """\
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %ars = (f32[8]{0}, u32[]) all-reduce-start(f32[8]{0} %p0), to_apply=%sum
  %m1 = f32[8]{0} multiply(f32[8]{0} %p0, f32[8]{0} %p0)
  %m2 = f32[8]{0} add(f32[8]{0} %m1, f32[8]{0} %p0)
  %m3 = f32[8]{0} subtract(f32[8]{0} %m2, f32[8]{0} %p0)
  ROOT %ard = f32[8]{0} all-reduce-done((f32[8]{0}, u32[]) %ars)
}
"""
    overlap = schedule_overlap(hlo)
    assert overlap == {
        "overlap_score": 3.0,
        "serialized_collectives": 0,
        "scheduled_collectives": 1,
    }


def test_schedule_overlap_zero_for_collective_free_programs():
    hlo = """\
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %p0, f32[8]{0} %p0)
}
"""
    assert schedule_overlap(hlo)["overlap_score"] == 0.0
    assert schedule_overlap("")["overlap_score"] == 0.0


def test_hlo_computation_ops_splits_per_computation_in_order():
    """Headers at column zero open a computation; a bare ``}`` closes
    it; instruction order within each body is preserved (HLO prints the
    schedule)."""
    hlo = """\
%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(f32[8]{0} %p0), to_apply=%sum
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %ar, f32[8]{0} %p0)
}
"""
    comps = hlo_computation_ops(hlo)
    assert list(comps.values()) == [
        ["parameter", "parameter", "add"],
        ["parameter", "all-reduce", "multiply"],
    ]


def test_strategy_prediction_covers_exactly_the_state_leaves():
    state = {"w": np.zeros((64, 256), np.float32), "b": np.zeros((256,))}
    pred = StrategyPrediction.from_state(state)
    assert pred.predicts((64, 256))
    assert pred.predicts((256,))
    assert not pred.predicts((16, 64))


# --- the DLC511 golden program -----------------------------------------------


@pytest.fixture(scope="module")
def golden():
    """A miniature fsdp step pair: batch sharded over the mesh, first
    kernel sharded over its columns.  Without a constraint on the hidden
    activation, GSPMD resolves the propagation conflict by all-gathering
    the BATCH (f32[16,64]) — data parallelism silently collapsed.  The
    constrained variant earns only the predicted parameter gather."""
    if jax.device_count() < 8:
        pytest.skip("golden program needs the 8-device virtual mesh")
    mesh = Mesh(np.array(jax.devices()[:8]), ("fsdp",))

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    x = jax.device_put(np.ones((16, 64), np.float32), sh("fsdp", None))
    w1 = jax.device_put(np.ones((64, 256), np.float32), sh(None, "fsdp"))
    w2 = jax.device_put(np.ones((256, 8), np.float32), sh(None, None))

    def loss_missing_constraint(x, w1, w2):
        h = jnp.tanh(x @ w1)
        return jnp.sum((h @ w2) ** 2)

    def loss_constrained(x, w1, w2):
        h = jnp.tanh(x @ w1)
        h = jax.lax.with_sharding_constraint(h, sh("fsdp", None))
        return jnp.sum((h @ w2) ** 2)

    bad = jax.jit(loss_missing_constraint).lower(x, w1, w2).compile()
    good = jax.jit(loss_constrained).lower(x, w1, w2).compile()
    prediction = StrategyPrediction(
        leaf_shapes=frozenset({(64, 256), (256, 8)})
    )
    return bad, good, prediction


def test_dlc511_catches_the_planted_batch_gather(golden):
    bad, _, prediction = golden
    program = CommsWatcher().watch("train_step", bad, prediction=prediction)
    assert (16, 64) in program.unpredicted_gathers
    violations = violations_for([program], budget=None, device_count=8)
    assert [v.rule for v in violations] == [AUDIT_RULE_UNPREDICTED]
    assert "16x64" in violations[0].message
    assert "train_step" in violations[0].message
    # Findings anchor on the audited step's file by default.
    assert violations[0].path == str(AUDITED_FILE)


def test_constrained_variant_gathers_only_what_fsdp_predicts(golden):
    _, good, prediction = golden
    program = CommsWatcher().watch("train_step", good, prediction=prediction)
    assert program.unpredicted_gathers == ()
    assert violations_for([program], budget=None, device_count=8) == []
    # The parameter gather fsdp earns is still there — the sentinel
    # excuses it, it does not pretend the program is collective-free.
    assert program.by_op.get("all-gather", 0) >= 1


# --- the DLC510 budget ratchet -----------------------------------------------


def _program(name="train_step", count=8, nbytes=11544, peak=1000, overlap=0.0):
    return ProgramComms(
        name=name,
        collective_count=count,
        collective_bytes=nbytes,
        peak_hbm_bytes=peak,
        by_op={},
        bytes_by_op={},
        flops=None,
        bytes_accessed=None,
        overlap_score=overlap,
    )


def _budget(count=8, nbytes=11544, device_count=8, name="train_step",
            overlap=0.0):
    return {
        "device_count": device_count,
        "programs": {
            name: {
                "collective_count": count,
                "collective_bytes": nbytes,
                "peak_hbm_bytes": 1000,
                "overlap_score": overlap,
            }
        },
    }


def test_dlc510_fires_when_op_count_regresses():
    violations = violations_for([_program(count=9)], _budget(), device_count=8)
    assert [v.rule for v in violations] == [AUDIT_RULE_BUDGET]
    assert "op count" in violations[0].message


def test_dlc510_fires_when_bytes_regress():
    violations = violations_for(
        [_program(nbytes=11545)], _budget(), device_count=8
    )
    assert [v.rule for v in violations] == [AUDIT_RULE_BUDGET]
    assert "bytes" in violations[0].message


def test_dlc510_quiet_at_exactly_the_committed_budget():
    assert violations_for([_program()], _budget(), device_count=8) == []


def test_dlc510_skips_on_device_count_mismatch():
    """A budget measured on 8 devices says nothing about a 4-device
    run — comparison must skip, not false-positive."""
    regressed = _program(count=99)
    assert (
        violations_for([regressed], _budget(device_count=4), device_count=8)
        == []
    )


def test_dlc510_skips_programs_the_budget_never_committed():
    violations = violations_for(
        [_program(name="new_path", count=99)], _budget(), device_count=8
    )
    assert violations == []


# --- the DLC512 overlap ratchet ----------------------------------------------


def test_dlc512_fires_when_the_overlap_program_fails_to_beat_its_base():
    """A `<name>_overlap` program exists to BEAT `<name>`; a tie means
    the bucket schedule bought nothing.  Needs no committed budget."""
    pair = [
        _program(name="train_step_dp", overlap=3.0),
        _program(name="train_step_dp_overlap", overlap=3.0),
    ]
    violations = violations_for(pair, budget=None, device_count=8)
    assert [v.rule for v in violations] == [AUDIT_RULE_OVERLAP]
    assert "strictly exceed" in violations[0].message
    assert "train_step_dp_overlap" in violations[0].message


def test_dlc512_quiet_when_the_overlap_program_strictly_wins():
    pair = [
        _program(name="train_step_dp", overlap=3.0),
        _program(name="train_step_dp_overlap", overlap=3.75),
    ]
    assert violations_for(pair, budget=None, device_count=8) == []


def test_dlc512_pair_check_skips_overlap_programs_without_a_base():
    """multi_step_dp_overlap has no multi_step_dp sibling in the audit —
    the pair invariant must skip it, not crash or false-positive."""
    solo = [_program(name="multi_step_dp_overlap", overlap=0.0)]
    assert violations_for(solo, budget=None, device_count=8) == []


def test_dlc512_fires_when_the_score_falls_below_the_committed_budget():
    violations = violations_for(
        [_program(overlap=5.0)], _budget(overlap=6.0), device_count=8
    )
    assert [v.rule for v in violations] == [AUDIT_RULE_OVERLAP]
    assert "fell below the committed budget" in violations[0].message


def test_dlc512_quiet_at_or_above_the_committed_score():
    assert (
        violations_for([_program(overlap=6.0)], _budget(overlap=6.0),
                       device_count=8)
        == []
    )
    assert (
        violations_for([_program(overlap=7.0)], _budget(overlap=6.0),
                       device_count=8)
        == []
    )


def test_dlc512_skips_budgets_that_predate_the_overlap_field():
    """An old committed budget with no overlap_score key must not
    compare against the measured score (None is not a ratchet)."""
    budget = _budget()
    del budget["programs"]["train_step"]["overlap_score"]
    assert (
        violations_for([_program(overlap=0.0)], budget, device_count=8) == []
    )


def test_budget_roundtrips_through_disk(tmp_path):
    path = tmp_path / "comms_budget.json"
    program = _program()
    payload = write_budget([program], path, device_count=8)
    loaded = load_budget(path)
    assert loaded == payload
    assert loaded["programs"]["train_step"] == program.budget
    assert load_budget(tmp_path / "missing.json") is None


# --- the real trainer --------------------------------------------------------


@pytest.fixture(scope="module")
def real_comms_audit(tmp_path_factory):
    """One audited run shared by the assertions below (the compile bill
    is the expensive part, not the checks)."""
    from deeplearning_cfn_tpu.obs import recorder

    journal = tmp_path_factory.mktemp("comms") / "flight.jsonl"
    recorder.configure(path=journal)
    try:
        report = run_comms_audit(k=2, journal=True, budget_path=None)
    finally:
        recorder.configure()
    return report, journal


def test_real_audit_budgets_every_program(real_comms_audit):
    report, _ = real_comms_audit
    budgets = {p.name: p.budget for p in report.programs}
    assert set(budgets) == AUDITED_PROGRAMS
    for name, budget in budgets.items():
        assert budget["peak_hbm_bytes"] > 0, name
        for value in budget.values():
            assert value >= 0
    # The fsdp train step must actually communicate on an 8-way mesh,
    # and DLC512 must tell the truth about the bucketed dp program: it
    # fires exactly when the bucketed schedule fails to strictly beat the
    # monolithic one on schedule slack.  (On HLO lowered for the CPU by
    # the installed compiler it fails to — one fused all-reduce either
    # way — and the finding is carried in scripts/lint_baseline.json;
    # whether the engine earns its keep is decided on chips, ROADMAP S8.)
    if report.device_count == 8:
        assert budgets["train_step"]["collective_count"] > 0
        assert budgets["train_step"]["collective_bytes"] > 0
        beats = (
            budgets["train_step_dp_overlap"]["overlap_score"]
            > budgets["train_step_dp"]["overlap_score"]
        )
        fired = any(
            v.rule == "DLC512" and "train_step_dp_overlap path" in v.message
            for v in report.violations
        )
        assert beats != fired


def test_real_audit_matches_the_committed_budget(real_comms_audit):
    """The exact-match ratchet: same source, same HLO, same numbers.
    A drift here means the committed budget was not regenerated after a
    change to the trainer or audit model."""
    report, _ = real_comms_audit
    committed = load_budget()
    if committed is None or int(committed["device_count"]) != report.device_count:
        pytest.skip("no committed budget for this device count")
    measured = {p.name: p.budget for p in report.programs}
    assert measured == committed["programs"]


def test_real_audit_findings_are_all_captured_in_the_baseline(real_comms_audit):
    """The repo's own hot path carries known DLC511 findings (the tiny
    audit model's batch gathers) — ratcheted into the committed
    baseline, so the sentinel must report nothing FRESH."""
    from deeplearning_cfn_tpu.analysis.runner import apply_audit_baseline

    report, _ = real_comms_audit
    assert all(v.rule in AUDIT_RULE_IDS for v in report.violations)
    fresh, _stale = apply_audit_baseline(
        report.violations, None, AUDIT_RULE_IDS
    )
    assert fresh == [], [v.to_dict() for v in fresh]


def test_real_audit_journals_to_the_flight_recorder(real_comms_audit):
    from deeplearning_cfn_tpu.obs.recorder import read_journal

    report, journal = real_comms_audit
    events = list(read_journal(journal, kind="comms_audit"))
    assert len(events) == 1
    event = events[0]
    assert set(event["programs"]) == AUDITED_PROGRAMS
    assert event["device_count"] == report.device_count
    for program in event["programs"].values():
        assert {
            "collective_count",
            "collective_bytes",
            "peak_hbm_bytes",
            "overlap_score",
        } <= set(program)
