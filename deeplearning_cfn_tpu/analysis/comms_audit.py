"""The dynamic comms-audit sentinel: machine-read the HLO a step ships.

The DLC50x static rules (analysis/collectives.py) catch the *source
patterns* that tend to produce accidental collectives; this module
measures the collectives that actually end up in the compiled program.
It lowers and compiles the real ``Trainer`` train step, the multi-step
scan body, and the serve decode step on the virtual CPU mesh, then reads
three machine signals off each executable:

- the optimized HLO text (``compiled.as_text()``), scanned for
  ``all-gather`` / ``all-reduce`` / ``reduce-scatter`` /
  ``collective-permute`` / ``all-to-all`` ops (async ``-start`` variants
  count once; their ``-done`` halves are skipped) with per-op result
  shapes and byte sizes;
- ``cost_analysis()`` flops and bytes-accessed, normalized the same way
  as ``obs.profiler.program_cost``;
- ``memory_analysis()`` — argument/output/temp/alias sizes folded into a
  peak-HBM estimate, the number that decides whether a sharding change
  fits on a 16 GiB chip.

Each audited program yields a **comms budget**
``{collective_count, collective_bytes, peak_hbm_bytes, overlap_score}``.
The budget is committed (scripts/comms_budget.json) and ratcheted:
DLC510 fires when a program's collective op count or bytes regress over
the committed numbers, DLC511 when an fsdp-strategy step contains an
all-gather the strategy doesn't predict — fsdp shards *parameters*, so
the only gathers it earns are parameter/optimizer-state shaped; a gather
matching no train-state leaf means a batch or activation got
materialized replicated (the classic missing
``with_sharding_constraint``).

``overlap_score`` machine-reads the optimized *schedule*, not just the
op set: per computation, every collective issue point is charged the
number of non-collective ops between it and the next collective
boundary — the compute the scheduler has available to hide that
collective behind (for an async pair, the ops between ``-start`` and
``-done`` fall out of the same walk).  The score is mean slack per
collective; a bucketed program that issues sync early scores strictly
higher than the monolithic end-of-backward bundle.  DLC512 ratchets it:
a score falling below the committed number — or a ``*_overlap``
program failing to strictly beat its monolithic baseline — is a
serialized collective that a bucket boundary could hide
(parallel/overlap.py; docs/PERFORMANCE.md "Hiding the collectives").

Findings are ordinary :class:`Violation`\\ s flowing through the same
suppression-baseline ratchet as the DLC41x compile audit
(scripts/lint_baseline.json, namespace-scoped via
``runner.apply_audit_baseline``), and results are journaled to the
flight recorder as ``comms_audit`` events so communication history rides
the same JSONL stream as retraces and step times.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import jax

from deeplearning_cfn_tpu.analysis.collectives import (
    AUDIT_RULE_BUDGET,
    AUDIT_RULE_OVERLAP,
    AUDIT_RULE_UNPREDICTED,
)
from deeplearning_cfn_tpu.analysis.core import Violation
from deeplearning_cfn_tpu.obs.profiler import program_cost

REPO_ROOT = Path(__file__).resolve().parents[2]
# Findings anchor on the file that owns the audited step (baseline key
# is (rule, repo-relative path, message) — same contract as DLC41x).
AUDITED_FILE = REPO_ROOT / "deeplearning_cfn_tpu" / "train" / "trainer.py"
SERVE_AUDITED_FILE = REPO_ROOT / "deeplearning_cfn_tpu" / "serve" / "engine.py"
DEFAULT_BUDGET_PATH = REPO_ROOT / "scripts" / "comms_budget.json"

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# An HLO collective instruction looks like
#   %all-gather.1 = f32[16,64]{1,0} all-gather(f32[2,64]{1,0} %p), ...
# or, async, `... all-gather-start(...)` paired with a `-done` op that
# carries the same bytes (count the start, skip the done).  The result
# shape is either one `dtype[dims]{layout}` token or a tuple
# `(f32[..]{..}, u32[], ...)` which may contain spaces.
_COLLECTIVE_RE = re.compile(
    r"=\s+((?:\([^)]*\))|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"(pred|[a-z]\d*[a-z]*\d*)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1,
    "u8": 1,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "s16": 2,
    "u16": 2,
    "f16": 2,
    "bf16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
}


@dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction read out of optimized HLO."""

    op: str
    result_shapes: tuple[tuple[int, ...], ...]
    nbytes: int

    def to_dict(self) -> dict:
        return {
            "op": self.op,
            "result_shapes": [list(s) for s in self.result_shapes],
            "nbytes": self.nbytes,
        }


def _parse_shapes(shape_text: str) -> tuple[list[tuple[int, ...]], int]:
    """All ``dtype[dims]`` members of an HLO shape string -> (shapes, bytes)."""
    shapes: list[tuple[int, ...]] = []
    nbytes = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        shape = tuple(int(d) for d in dims.split(",") if d)
        shapes.append(shape)
        elems = 1
        for d in shape:
            elems *= d
        nbytes += elems * _DTYPE_BYTES.get(dtype, 4)
    return shapes, nbytes


def hlo_collectives(hlo_text: str) -> list[CollectiveOp]:
    """Scan optimized HLO text for collective ops with result sizes."""
    out: list[CollectiveOp] = []
    for match in _COLLECTIVE_RE.finditer(hlo_text):
        shapes, nbytes = _parse_shapes(match.group(1))
        out.append(
            CollectiveOp(
                op=match.group(2), result_shapes=tuple(shapes), nbytes=nbytes
            )
        )
    return out


# --- the schedule reader (overlap_score) -------------------------------------

# An instruction line is indented and assigns a %-named value; the op
# name follows the result shape (a single `dtype[..]{..}` token or a
# parenthesized tuple, which may contain spaces and `/*index=k*/`
# comments).
_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?[\w.\-]+\s+=\s+")
_OP_RE = re.compile(r"=\s+(?:\([^)]*\)|\S+)\s+([\w\-]+)\(")
_COLLECTIVE_NAMES = frozenset(
    name + suffix
    for name in COLLECTIVE_OPS
    for suffix in ("", "-start", "-done")
)


def hlo_computation_ops(hlo_text: str) -> dict[str, list[str]]:
    """Optimized HLO text -> ordered op names per computation.

    HLO prints instructions in SCHEDULE order inside each computation
    (`ENTRY`/`%fused`/`%while_body` headers start at column zero and end
    with ``{``), which is what makes positional slack a faithful read of
    what the backend will execute between two collectives.
    """
    comps: dict[str, list[str]] = {}
    cur: list[str] | None = None
    for line in hlo_text.splitlines():
        if (
            line.rstrip().endswith("{")
            and not line.startswith((" ", "\t"))
            and ("%" in line or line.startswith("ENTRY"))
        ):
            cur = comps[line.split("(")[0].strip()] = []
        elif line.strip() == "}":
            cur = None
        elif cur is not None and _INSTR_RE.match(line):
            m = _OP_RE.search(line)
            if m:
                cur.append(m.group(1))
    return comps


def schedule_overlap(hlo_text: str) -> dict:
    """Mean compute slack per collective across the whole module.

    For every collective ISSUE point (plain or ``-start``; ``-done``
    halves are not issue points but do act as boundaries), slack is the
    count of non-collective ops strictly between it and the next
    collective boundary — or the end of its computation for the last
    one.  Async pairs need no special case: the ops between ``-start``
    and ``-done`` are exactly the start's slack.  A slack-0 issue point
    is a SERIALIZED collective — nothing is scheduled for the backend
    to hide it behind.

    Returns ``{"overlap_score": float, "serialized_collectives": int,
    "scheduled_collectives": int}``; score is 0.0 for collective-free
    programs.
    """
    total_slack = 0
    n_issue = 0
    n_serialized = 0
    for ops in hlo_computation_ops(hlo_text).values():
        idxs = [i for i, op in enumerate(ops) if op in _COLLECTIVE_NAMES]
        for j, i in enumerate(idxs):
            if ops[i].endswith("-done"):
                continue
            boundary = idxs[j + 1] if j + 1 < len(idxs) else len(ops)
            slack = boundary - i - 1
            total_slack += slack
            n_issue += 1
            if slack == 0:
                n_serialized += 1
    return {
        "overlap_score": round(total_slack / max(n_issue, 1), 4),
        "serialized_collectives": n_serialized,
        "scheduled_collectives": n_issue,
    }


def _peak_hbm_bytes(compiled: Any) -> int:
    """Fold ``memory_analysis()`` into one peak-HBM estimate.

    arguments + outputs + temporaries, minus aliased (donated) bytes —
    the resident set the program needs at its widest point, which is the
    number a sharding mistake inflates.
    """
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return 0
    if mem is None:
        return 0
    total = 0
    for name, sign in (
        ("argument_size_in_bytes", 1),
        ("output_size_in_bytes", 1),
        ("temp_size_in_bytes", 1),
        ("alias_size_in_bytes", -1),
    ):
        total += sign * int(getattr(mem, name, 0) or 0)
    return max(total, 0)


def program_comms(compiled: Any) -> dict:
    """The full comms/memory readout for one AOT-compiled program."""
    try:
        text = compiled.as_text()
    except Exception:
        text = ""
    ops = hlo_collectives(text)
    by_op = {name: 0 for name in COLLECTIVE_OPS}
    bytes_by_op = {name: 0 for name in COLLECTIVE_OPS}
    for op in ops:
        by_op[op.op] += 1
        bytes_by_op[op.op] += op.nbytes
    cost = program_cost(compiled)
    overlap = schedule_overlap(text)
    return {
        "collective_count": len(ops),
        "collective_bytes": sum(op.nbytes for op in ops),
        "peak_hbm_bytes": _peak_hbm_bytes(compiled),
        "overlap_score": overlap["overlap_score"],
        "serialized_collectives": overlap["serialized_collectives"],
        "by_op": {k: v for k, v in by_op.items() if v},
        "bytes_by_op": {k: v for k, v in bytes_by_op.items() if v},
        "flops": cost["flops"],
        "bytes_accessed": cost["bytes_accessed"],
        "ops": ops,
    }


# --- strategy prediction (DLC511) -------------------------------------------


@dataclass(frozen=True)
class StrategyPrediction:
    """The all-gathers an fsdp step is entitled to emit.

    fsdp shards parameters and optimizer state across the ``fsdp`` axis
    and gathers them around use — so every legitimate all-gather result
    is shaped like a train-state leaf.  Anything else (a batch array, a
    hidden activation) means the partitioner materialized data
    replicated that the strategy meant to keep sharded.
    """

    leaf_shapes: frozenset[tuple[int, ...]]

    @classmethod
    def from_state(cls, state: Any) -> "StrategyPrediction":
        shapes = {
            tuple(getattr(leaf, "shape", ()))
            for leaf in jax.tree_util.tree_leaves(state)
        }
        return cls(leaf_shapes=frozenset(shapes))

    def predicts(self, shape: tuple[int, ...]) -> bool:
        return tuple(shape) in self.leaf_shapes


def _dims(shape: tuple[int, ...]) -> str:
    return "x".join(str(d) for d in shape) if shape else "scalar"


# --- the watcher ------------------------------------------------------------


@dataclass
class ProgramComms:
    """One audited program's comms budget + DLC511 evidence."""

    name: str
    collective_count: int
    collective_bytes: int
    peak_hbm_bytes: int
    by_op: dict[str, int]
    bytes_by_op: dict[str, int]
    flops: float | None
    bytes_accessed: float | None
    # Mean compute slack per collective in the optimized schedule
    # (schedule_overlap) — the ratcheted latency-hiding signal — and the
    # count of slack-0 (fully serialized) collectives behind it.
    overlap_score: float = 0.0
    serialized_collectives: int = 0
    # Distinct all-gather result shapes the strategy does not predict
    # (empty when no prediction applies, e.g. the serve decode path).
    unpredicted_gathers: tuple[tuple[int, ...], ...] = ()
    audited_file: str | None = None

    @property
    def budget(self) -> dict:
        return {
            "collective_count": self.collective_count,
            "collective_bytes": self.collective_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "overlap_score": self.overlap_score,
        }

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            **self.budget,
            "serialized_collectives": self.serialized_collectives,
            "by_op": dict(sorted(self.by_op.items())),
            "bytes_by_op": dict(sorted(self.bytes_by_op.items())),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "unpredicted_gathers": [list(s) for s in self.unpredicted_gathers],
        }


class CommsWatcher:
    """Accumulates per-program comms budgets from AOT-compiled modules.

    Unlike :class:`~.compile_audit.CompileWatcher` (which listens to the
    dispatch layer while code *runs*), this watcher is fed explicitly:
    ``watch()`` takes an already-compiled executable, reads its HLO, and
    records the budget — compilation is the measurement, no execution
    happens.
    """

    def __init__(self) -> None:
        self.programs: list[ProgramComms] = []

    def watch(
        self,
        name: str,
        compiled: Any,
        prediction: StrategyPrediction | None = None,
        audited_file: str | None = None,
    ) -> ProgramComms:
        comms = program_comms(compiled)
        unpredicted: list[tuple[int, ...]] = []
        if prediction is not None:
            seen: set[tuple[int, ...]] = set()
            for op in comms["ops"]:
                if op.op != "all-gather":
                    continue
                for shape in op.result_shapes:
                    # Async gathers carry u32[] control members; only
                    # real payload shapes can be "unpredicted".
                    if len(shape) == 0:
                        continue
                    if not prediction.predicts(shape) and shape not in seen:
                        seen.add(shape)
                        unpredicted.append(shape)
        program = ProgramComms(
            name=name,
            collective_count=comms["collective_count"],
            collective_bytes=comms["collective_bytes"],
            peak_hbm_bytes=comms["peak_hbm_bytes"],
            by_op=comms["by_op"],
            bytes_by_op=comms["bytes_by_op"],
            flops=comms["flops"],
            bytes_accessed=comms["bytes_accessed"],
            overlap_score=comms["overlap_score"],
            serialized_collectives=comms["serialized_collectives"],
            unpredicted_gathers=tuple(sorted(unpredicted)),
            audited_file=audited_file,
        )
        self.programs.append(program)
        return program

    def budgets(self) -> dict[str, dict]:
        return {p.name: p.budget for p in self.programs}


# --- committed budget (the ratchet's numbers) -------------------------------


def load_budget(path: Path | str = DEFAULT_BUDGET_PATH) -> dict | None:
    """The committed per-program budget, or None when not yet written."""
    p = Path(path)
    if not p.exists():
        return None
    data = json.loads(p.read_text())
    if not isinstance(data, dict) or "programs" not in data:
        return None
    return data


def write_budget(
    programs: list[ProgramComms],
    path: Path | str = DEFAULT_BUDGET_PATH,
    device_count: int | None = None,
) -> dict:
    payload = {
        "device_count": (
            device_count if device_count is not None else jax.device_count()
        ),
        "programs": {p.name: p.budget for p in programs},
    }
    Path(path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


# --- findings ---------------------------------------------------------------


def violations_for(
    programs: list[ProgramComms],
    budget: dict | None,
    device_count: int,
) -> list[Violation]:
    """Fold audited programs into baseline-ratchet findings.

    Messages are count-free and shape-stable: the audit model, batch
    size, and mesh are fixed constants, so the same program compiles to
    the same collectives run over run — a changed message IS a changed
    program.  DLC511 emits one finding per distinct unpredicted gather
    shape so a future regression fails fresh instead of hiding behind an
    existing entry.
    """
    out: list[Violation] = []
    budget_programs = {}
    if budget is not None and int(budget.get("device_count", -1)) == device_count:
        budget_programs = budget.get("programs", {})
    by_name = {p.name: p for p in programs}
    for p in programs:
        # The overlap pair invariant needs no committed budget: a
        # `<name>_overlap` program exists to BEAT `<name>`, so a score
        # that fails to strictly exceed the monolithic baseline's means
        # the bucket schedule serialized a collective it was built to
        # hide.
        base = by_name.get(p.name[: -len("_overlap")]) if p.name.endswith(
            "_overlap"
        ) else None
        if base is not None and p.overlap_score <= base.overlap_score:
            out.append(
                Violation(
                    rule=AUDIT_RULE_OVERLAP,
                    path=p.audited_file or str(AUDITED_FILE),
                    line=1,
                    col=1,
                    message=(
                        f"serialized collective on the {p.name} path: the "
                        "bucketed program's overlap_score does not strictly "
                        f"exceed the monolithic {base.name} baseline's — the "
                        "explicit bucket schedule is buying no latency "
                        "hiding (parallel/overlap.py; comms-audit sentinel, "
                        "see docs/STATIC_ANALYSIS.md comms runbook)"
                    ),
                )
            )
    for p in programs:
        anchor = p.audited_file or str(AUDITED_FILE)
        for shape in p.unpredicted_gathers:
            out.append(
                Violation(
                    rule=AUDIT_RULE_UNPREDICTED,
                    path=anchor,
                    line=1,
                    col=1,
                    message=(
                        f"unpredicted all-gather on the {p.name} path: the "
                        f"compiled fsdp step gathers a {_dims(shape)} array "
                        "that matches no train-state leaf — fsdp predicts "
                        "parameter/optimizer gathers only, so a batch or "
                        "activation is being materialized replicated "
                        "(comms-audit sentinel; see docs/STATIC_ANALYSIS.md "
                        "comms runbook)"
                    ),
                )
            )
        committed = budget_programs.get(p.name)
        if committed is None:
            continue
        over_count = p.collective_count > int(committed["collective_count"])
        over_bytes = p.collective_bytes > int(committed["collective_bytes"])
        if over_count or over_bytes:
            grew = " and ".join(
                what
                for what, over in (
                    ("op count", over_count),
                    ("bytes", over_bytes),
                )
                if over
            )
            out.append(
                Violation(
                    rule=AUDIT_RULE_BUDGET,
                    path=anchor,
                    line=1,
                    col=1,
                    message=(
                        f"comms budget regression on the {p.name} path: "
                        f"collective {grew} exceed the committed budget "
                        "(scripts/comms_budget.json; re-measure with "
                        "scripts/comms_audit.py --write-budget if the "
                        "increase is intended — comms-audit sentinel, see "
                        "docs/STATIC_ANALYSIS.md comms runbook)"
                    ),
                )
            )
        committed_score = committed.get("overlap_score")
        if committed_score is not None and p.overlap_score < float(
            committed_score
        ):
            out.append(
                Violation(
                    rule=AUDIT_RULE_OVERLAP,
                    path=anchor,
                    line=1,
                    col=1,
                    message=(
                        f"overlap regression on the {p.name} path: the "
                        "compiled schedule's overlap_score fell below the "
                        "committed budget — a gradient-sync collective that "
                        "a bucket boundary could hide is now serialized "
                        "(scripts/comms_budget.json; re-measure with "
                        "scripts/comms_audit.py --write-budget if the drop "
                        "is intended — comms-audit sentinel, see "
                        "docs/STATIC_ANALYSIS.md comms runbook)"
                    ),
                )
            )
    return out


# --- the audit itself -------------------------------------------------------


@dataclass
class CommsAuditReport:
    programs: list[ProgramComms]
    violations: list[Violation]
    device_count: int
    budget_checked: bool
    measured: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "programs": [p.to_dict() for p in self.programs],
            "budgets": {p.name: p.budget for p in self.programs},
            "violations": [v.to_dict() for v in self.violations],
            "device_count": self.device_count,
            "budget_checked": self.budget_checked,
            "clean": not self.violations,
        }


# The audit model is a fixed constant: its train state must contain at
# least one leaf big enough for the fsdp heuristic to shard (Dense(256)
# kernel = 64*256 elements, exactly the min-shard threshold), and the
# global batch must divide the 8-way mesh.  Changing any of these
# numbers changes the committed budget — regenerate it deliberately.
AUDIT_BATCH_SIZE = 16
AUDIT_HIDDEN = 256
AUDIT_CLASSES = 4
AUDIT_INPUT_SHAPE = (8, 8, 1)


def _audit_model():
    import flax.linen as nn

    hidden, classes = AUDIT_HIDDEN, AUDIT_CLASSES

    class _CommsAuditMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(hidden)(x)
            x = nn.relu(x)
            return nn.Dense(classes)(x)

    return _CommsAuditMLP()


def run_comms_audit(
    k: int = 2,
    journal: bool = True,
    budget_path: Path | str | None = DEFAULT_BUDGET_PATH,
    serve: bool = True,
) -> CommsAuditReport:
    """Audit the real fsdp train step, multi-step scan body, serve
    decode step, and the dp comms-overlap pair for communication and
    HBM pressure.

    The dp pair is the overlap ratchet's proof surface: the SAME model,
    batch, and mesh lowered monolithically (``train_step_dp``) and
    through the bucketed engine (``train_step_dp_overlap``,
    ``multi_step_dp_overlap`` with grad accumulation pipelining sync
    into the scan body) — DLC512 requires the bucketed schedule's
    overlap_score to strictly exceed the monolithic baseline's.

    Pure lower+compile — no step executes, so the audit is fast and
    deterministic: the same source compiles to the same HLO, which is
    what makes an exact-match budget ratchet possible.
    """
    import numpy as np

    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import SyntheticDataset
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    devices = jax.devices()
    n = 8 if len(devices) >= 8 else len(devices)
    mesh = build_mesh(MeshSpec.fsdp_parallel(n), devices[:n])
    ds = SyntheticDataset(
        shape=AUDIT_INPUT_SHAPE,
        num_classes=AUDIT_CLASSES,
        batch_size=AUDIT_BATCH_SIZE,
        seed=0,
    )
    trainer = Trainer(
        _audit_model(),
        mesh,
        TrainerConfig(learning_rate=0.05, optimizer="sgd", strategy="fsdp"),
    )
    sample = next(iter(ds.batches(1)))
    watcher = CommsWatcher()
    with jax.set_mesh(mesh):
        state = trainer.init(jax.random.PRNGKey(0), sample.x)
        prediction = StrategyPrediction.from_state(state)

        compiled_step = trainer.step_fn.lower(state, sample.x, sample.y).compile()
        watcher.watch("train_step", compiled_step, prediction=prediction)

        kfn = trainer.multi_step_fn(k)
        stack = list(ds.batches(k))
        xs = np.stack([b.x for b in stack])
        ys = np.stack([b.y for b in stack])
        compiled_multi = kfn.lower(state, xs, ys).compile()
        watcher.watch("multi_step", compiled_multi, prediction=prediction)

    # The dp overlap pair: monolithic vs bucketed sync on an identical
    # dp mesh/model/batch.  The small bucket target (32 KiB against the
    # ~270 KiB audit param tree) forces several fused buckets so the
    # schedule genuinely interleaves sync with compute; grad accumulation
    # on the multi-step variant exercises the pipelined scan body.
    dp_mesh = build_mesh(MeshSpec.data_parallel(n), devices[:n])
    dp_kwargs = dict(learning_rate=0.05, optimizer="sgd", strategy="dp")
    mono_dp = Trainer(_audit_model(), dp_mesh, TrainerConfig(**dp_kwargs))
    overlap_dp = Trainer(
        _audit_model(),
        dp_mesh,
        TrainerConfig(
            comms_overlap=True, overlap_bucket_bytes=32 * 1024, **dp_kwargs
        ),
    )
    overlap_accum_dp = Trainer(
        _audit_model(),
        dp_mesh,
        TrainerConfig(
            comms_overlap=True,
            overlap_bucket_bytes=32 * 1024,
            grad_accum_steps=2,
            **dp_kwargs,
        ),
    )
    with jax.set_mesh(dp_mesh):
        dp_state = mono_dp.init(jax.random.PRNGKey(0), sample.x)
        dp_prediction = StrategyPrediction.from_state(dp_state)
        watcher.watch(
            "train_step_dp",
            mono_dp.step_fn.lower(dp_state, sample.x, sample.y).compile(),
            prediction=dp_prediction,
        )
        ov_state = overlap_dp.init(jax.random.PRNGKey(0), sample.x)
        watcher.watch(
            "train_step_dp_overlap",
            overlap_dp.step_fn.lower(ov_state, sample.x, sample.y).compile(),
            prediction=dp_prediction,
        )
        acc_state = overlap_accum_dp.init(jax.random.PRNGKey(0), sample.x)
        kfn_ov = overlap_accum_dp.multi_step_fn(k)
        watcher.watch(
            "multi_step_dp_overlap",
            kfn_ov.lower(acc_state, xs, ys).compile(),
            prediction=dp_prediction,
        )

    if serve:
        watcher.programs.append(_audit_serve_decode())

    budget = load_budget(budget_path) if budget_path is not None else None
    violations = violations_for(watcher.programs, budget, device_count=n)
    report = CommsAuditReport(
        programs=watcher.programs,
        violations=violations,
        device_count=n,
        budget_checked=bool(
            budget is not None
            and int(budget.get("device_count", -1)) == n
        ),
    )
    if journal:
        from deeplearning_cfn_tpu.obs.recorder import get_recorder

        get_recorder().record(
            "comms_audit",
            clean=not violations,
            device_count=n,
            programs={p.name: p.to_dict() for p in watcher.programs},
        )
    return report


def _audit_serve_decode() -> ProgramComms:
    """Lower+compile the real paged decode step on the default device.

    Single-device serving has no collectives by construction; the decode
    budget's load-bearing number is ``peak_hbm_bytes`` — the paged K/V
    pool must stay aliased (donated), not doubled.
    """
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from deeplearning_cfn_tpu.models.llama import LlamaConfig, init_params
    from deeplearning_cfn_tpu.serve.engine import (
        ContinuousBatchingEngine,
        ServeConfig,
        paged_decode_step,
    )

    cfg = dataclasses.replace(
        LlamaConfig.tiny(vocab_size=64, seq_len=64), dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    scfg = ServeConfig(num_slots=4, block_size=4, blocks_per_slot=8, prefill_len=16)
    engine = ContinuousBatchingEngine(
        cfg, params, scfg, clock=lambda: 0.0, journal=False
    )
    tokens = np.zeros(scfg.num_slots, np.int32)
    lengths = np.zeros(scfg.num_slots, np.int32)
    tables = np.zeros((scfg.num_slots, scfg.blocks_per_slot), np.int32)
    active = np.zeros(scfg.num_slots, bool)
    compiled = paged_decode_step.lower(
        cfg,
        engine.params,
        engine.cache,
        tokens,
        lengths,
        tables,
        active,
        engine._key,
        temperature=scfg.temperature,
    ).compile()
    watcher = CommsWatcher()
    return watcher.watch(
        "serve_decode", compiled, audited_file=str(SERVE_AUDITED_FILE)
    )
