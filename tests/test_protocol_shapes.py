"""The broker message-*shape* checker (DLC300-302) and lifecycle-kind
checker (DLC303), plus the suppression-baseline ratchet.

Same proof obligation as test_contract_check.py, one level deeper: the
real repo's three protocol layers agree byte-for-byte on request arity,
payload framing, reply tokens, and multi-field frame shapes — and each
class of single-layer drift (spec comment loses an argument, broker
renames a reply token, a frame loses a field, a lifecycle kind is
published but never dispatched) fails lint on a mutated fixture copy.
"""

import dataclasses
from pathlib import Path

from deeplearning_cfn_tpu.analysis import protocol as ps
from deeplearning_cfn_tpu.analysis import runner
from deeplearning_cfn_tpu.analysis.core import Violation


def test_real_repo_shapes_agree():
    assert ps.check_protocol() == []


def test_real_repo_lifecycle_kinds_agree():
    assert ps.check_lifecycle() == []


def test_shape_extraction_is_not_vacuous():
    """Each extractor independently recovers real shapes — the guarantee
    that an empty-extraction bug can't make agreement vacuous."""
    canon = ps.canonical_shapes()
    assert canon["PING"] == {(0, False)}
    assert canon["SEND"] == {(2, True)}  # SEND <queue> <nbytes> + payload
    assert canon["RECV"] == {(3, False)}
    # HEARTBEAT's two spec lines: record (1 arg) and table dump (0 args).
    assert canon["HEARTBEAT"] == {(0, False), (1, False)}
    # TELEM's two spec lines: record (2 args + payload) and dump (0 args).
    assert canon["TELEM"] == {(2, True), (0, False)}
    # The replication verbs (warm-standby control plane, PR 10).
    assert canon["SENDID"] == {(3, True)}  # SENDID <queue> <rid> <nbytes>
    assert canon["ROLE"] == {(0, False)}
    assert canon["PROMOTE"] == {(1, False)}
    assert canon["SYNC"] == {(3, True)}  # SYNC <epoch> <seq> <nbytes>
    # The keyspace-sharding verb (sharded broker control plane).
    assert canon["SHARD"] == {(0, False)}

    cpp = ps.cpp_request_shapes()
    assert cpp["RECV"] == (3, False)
    assert cpp["SET"][1] is True  # kv write reads a payload
    assert cpp["SYNC"] == (3, True)  # journal frame rides the payload
    assert cpp["PROMOTE"] == (1, False)
    assert cpp["SHARD"] == (0, False)

    client_tokens, client_frames = ps.client_reply_contract()
    assert "PONG" in client_tokens["PING"]
    assert client_frames["RECV"]["MSG"] == {5}
    assert client_frames["HEARTBEAT"]["HB"] == {4}
    # TM frames carry a trailing <len> for the payload that follows.
    assert client_frames["TELEM"]["TM"] == {5}
    # ROLE replies with a 4-token frame: ROLE <role> <epoch> <seq>.
    assert client_frames["ROLE"]["ROLE"] == {4}
    # SHARD replies with a 3-token frame: SHARD <shard> <nshards>.
    assert client_frames["SHARD"]["SHARD"] == {3}

    cpp_tokens, cpp_frames = ps.cpp_reply_contract()
    assert "PONG" in cpp_tokens["PING"]
    assert cpp_frames["RECV"]["MSG"] == 5
    assert cpp_frames["HEARTBEAT"]["HB"] == 4
    assert cpp_frames["ROLE"]["ROLE"] == 4
    assert cpp_frames["TELEM"]["TM"] == 5
    assert cpp_frames["SHARD"]["SHARD"] == 3


def _mutated(tmp_path: Path, src: Path, old: str, new: str) -> Path:
    text = src.read_text()
    assert old in text, f"fixture drift: {old!r} not found in {src}"
    out = tmp_path / src.name
    out.write_text(text.replace(old, new))
    return out


def test_spec_comment_arg_drop_fires_dlc300(tmp_path):
    """The acceptance scenario: contract.py's machine-read spec loses an
    argument -> both the client and the C++ extractor disagree with it."""
    mutated = _mutated(
        tmp_path,
        ps.CONTRACT_PY,
        "# RECV <queue> <max> <vis_ms>",
        "# RECV <queue> <max>",
    )
    violations = ps.check_protocol(contract_py=mutated)
    assert violations and all(v.rule == "DLC300" for v in violations)
    messages = "\n".join(v.message for v in violations)
    assert "client sends RECV with 3 argument token(s)" in messages
    assert "broker.cpp extracts 3 argument token(s) for RECV" in messages


def test_missing_spec_comment_fires_dlc300(tmp_path):
    mutated = _mutated(
        tmp_path,
        ps.CONTRACT_PY,
        '"PURGE",  # PURGE <queue>',
        '"PURGE",  #',
    )
    violations = ps.check_protocol(contract_py=mutated)
    assert any(
        v.rule == "DLC300" and "no request-shape spec comment" in v.message
        for v in violations
    )


def test_reply_token_rename_fires_dlc301(tmp_path):
    mutated = _mutated(tmp_path, ps.BROKER_CPP, '"PONG\\n"', '"PONGX\\n"')
    violations = ps.check_protocol(broker_cpp=mutated)
    assert any(
        v.rule == "DLC301" and "'PONG'" in v.message and "PING" in v.message
        for v in violations
    )


def test_frame_field_drop_fires_dlc302(tmp_path):
    # Merge the HB frame's age and count fields (drop one separator):
    # the broker would emit 3-token HB lines the client can't unpack.
    mutated = _mutated(
        tmp_path,
        ps.BROKER_CPP,
        'std::to_string(r.age_ms) + " " +',
        "std::to_string(r.age_ms) +",
    )
    violations = ps.check_protocol(broker_cpp=mutated)
    assert any(
        v.rule == "DLC302" and "'HB'" in v.message and "arity" in v.message
        for v in violations
    )


def test_frame_tag_removal_fires_dlc302(tmp_path):
    mutated = _mutated(tmp_path, ps.BROKER_CPP, 'resp += "HB "', 'resp += "XB "')
    violations = ps.check_protocol(broker_cpp=mutated)
    assert any(
        v.rule == "DLC302" and "'HB'" in v.message and "never emits" in v.message
        for v in violations
    )


# --- DLC303: lifecycle kinds -------------------------------------------------

def test_dlc303_flags_undefined_event_kind(tmp_path):
    bad = tmp_path / "user.py"
    bad.write_text(
        "from deeplearning_cfn_tpu.provision.events import EventKind\n"
        "KIND = EventKind.SPOT_REAP\n"
    )
    violations = ps.check_lifecycle(files=[bad])
    assert [v.rule for v in violations] == ["DLC303"]
    assert "EventKind.SPOT_REAP" in violations[0].message


def test_dlc303_flags_published_but_never_dispatched_kind(tmp_path):
    events = _mutated(
        tmp_path,
        ps.EVENTS_PY,
        'TEST_NOTIFICATION = "test-notification"',
        'TEST_NOTIFICATION = "test-notification"\n'
        '    SPOT_INTERRUPT = "spot-interrupt"',
    )
    publisher = tmp_path / "publisher.py"
    publisher.write_text(
        "def warn(bus, EventKind, LifecycleEvent):\n"
        "    bus.publish(LifecycleEvent(kind=EventKind.SPOT_INTERRUPT,\n"
        "                               group='g', instance_id='i'))\n"
    )
    violations = ps.check_lifecycle(events_py=events, files=[publisher])
    assert [v.rule for v in violations] == ["DLC303"]
    assert "SPOT_INTERRUPT" in violations[0].message
    assert "never dispatches" in violations[0].message


def test_dlc303_flags_consumed_but_never_produced_journal_kind(tmp_path):
    reader = tmp_path / "reader.py"
    reader.write_text(
        "def load(read_journal, recorder):\n"
        "    recorder.record('span', name='x')\n"
        "    return read_journal('j.jsonl', kind='ghost')\n"
    )
    violations = ps.check_lifecycle(files=[reader])
    assert [v.rule for v in violations] == ["DLC303"]
    assert "'ghost'" in violations[0].message


# --- the suppression baseline (ratchet) --------------------------------------

def _v(message: str, line: int = 3) -> Violation:
    return Violation(
        rule="DLC201",
        path=str(runner.REPO_ROOT / "deeplearning_cfn_tpu" / "x.py"),
        line=line,
        col=1,
        message=message,
    )


def test_baseline_roundtrip_suppresses_known_flags_new(tmp_path):
    known, new = _v("known race"), _v("new race")
    path = tmp_path / "baseline.json"
    runner.write_baseline([known], path)
    baseline = runner.load_baseline(path)
    fresh, stale = runner.apply_baseline([known, new], baseline)
    assert fresh == [new]
    assert stale == []


def test_baseline_keys_survive_line_churn(tmp_path):
    """Entries key on (rule, path, message), not line numbers: edits above
    a suppressed finding must not invalidate the baseline."""
    path = tmp_path / "baseline.json"
    runner.write_baseline([_v("known race", line=3)], path)
    moved = _v("known race", line=99)
    fresh, stale = runner.apply_baseline([moved], runner.load_baseline(path))
    assert fresh == []
    assert stale == []


def test_baseline_reports_stale_entries(tmp_path):
    path = tmp_path / "baseline.json"
    runner.write_baseline([_v("fixed since")], path)
    fresh, stale = runner.apply_baseline([], runner.load_baseline(path))
    assert fresh == []
    assert stale == [
        ("DLC201", "deeplearning_cfn_tpu/x.py", "fixed since")
    ]


def test_committed_baseline_carries_only_the_comms_sentinel_debt():
    """The ratchet's floor: every STATIC namespace carries zero
    suppressed findings.  The accepted debt is the comms-audit
    sentinel's: the DLC511 entries — the tiny audit model's known batch
    gathers on the fsdp train path, ratcheted deliberately (see
    docs/STATIC_ANALYSIS.md, "reading a comms report") — and one DLC512:
    on HLO the installed compiler lowers for the CPU, the bucketed dp
    program no longer beats its monolithic baseline's overlap_score
    (parallel/overlap.py's fate is decided on chips, ROADMAP S8)."""
    entries = runner.load_baseline(runner.DEFAULT_BASELINE)
    assert {rule for rule, _, _ in entries} == {"DLC511", "DLC512"}
    assert [m for rule, _, m in entries if rule == "DLC512"] == [
        m for _, _, m in entries if "train_step_dp_overlap path" in m
    ]
    assert {path for _, path, _ in entries} == {
        "deeplearning_cfn_tpu/train/trainer.py"
    }


# --- runner gating ------------------------------------------------------------

_RACY = (
    "import threading\n\n\n"
    "class Counter(threading.Thread):\n"
    "    def __init__(self):\n"
    "        super().__init__(daemon=True)\n"
    "        self._halt = threading.Event()\n"
    "        self.total = 0\n\n"
    "    def run(self):\n"
    "        self.total += 1\n"
)


def test_run_lint_gates_concurrency_pass(tmp_path):
    target = tmp_path / "racy.py"
    target.write_text(_RACY)
    plain = runner.run_lint(targets=[target], root=tmp_path, contract=False)
    gated = runner.run_lint(
        targets=[target], root=tmp_path, contract=False, concurrency=True
    )
    assert plain == []
    assert [v.rule for v in gated] == ["DLC201"]


def test_run_lint_select_enables_gated_rules(tmp_path):
    target = tmp_path / "racy.py"
    target.write_text(_RACY)
    out = runner.run_lint(
        targets=[target], root=tmp_path, select={"DLC201"}, contract=False
    )
    assert [v.rule for v in out] == ["DLC201"]


def test_run_lint_protocol_pass_runs_dlc3xx():
    out = runner.run_lint(targets=[], protocol_pass=True, contract=False)
    # clean repo: the pass ran (no crash) and found nothing
    assert out == []
