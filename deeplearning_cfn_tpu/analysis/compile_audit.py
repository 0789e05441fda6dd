"""The dynamic compile-audit sentinel: prove steady-state zero-retrace.

The DLC4xx static rules (analysis/sharding.py) catch retrace *hazards*;
this module catches retraces that actually happen.  It runs the real
``Trainer.fit()`` single-step path and the bench multi-step path for a
few steps on CPU, watching JAX's own compilation machinery:

- per-function trace and compile counts, read from the
  ``jax_log_compiles`` log stream (the only per-function signal JAX
  exposes; ``jax.monitoring``'s ``backend_compile`` events carry
  durations but no names, so they are kept as an aggregate cross-check);
- the jit dispatch-cache size of each audited wrapper
  (``fn._cache_size()``) — a second, independent retrace witness;
- donation effectiveness, observed directly: after one step, every
  donated input buffer reports ``is_deleted()`` — so "someone dropped
  ``donate_argnums``" shows up as ``donated_bytes == 0``, not as an OOM
  three weeks later on a 16 GiB chip.

After a warmup phase the watcher marks steady state; any function whose
compile count then grows is a finding (DLC410), and a step whose state
donation is completely ineffective is a finding (DLC411).  Findings are
ordinary :class:`Violation`\\ s against the audited source file, flowing
through the same suppression-baseline ratchet as every other DLC rule
(scripts/lint_baseline.json) — a future PR that introduces a retrace or
drops a donation fails ``scripts/check.sh``, it does not get a warning.

Results are journaled to the flight recorder as a ``compile_audit``
event so retrace history rides the same JSONL stream as heartbeats and
reshard events.
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from deeplearning_cfn_tpu.analysis.core import Violation
from deeplearning_cfn_tpu.analysis.sharding import (
    AUDIT_RULE_DONATION,
    AUDIT_RULE_RETRACE,
)

REPO_ROOT = Path(__file__).resolve().parents[2]
# Findings anchor on the file that owns the audited step loop: the
# baseline key is (rule, repo-relative path, message).
AUDITED_FILE = REPO_ROOT / "deeplearning_cfn_tpu" / "train" / "trainer.py"
SERVE_AUDITED_FILE = REPO_ROOT / "deeplearning_cfn_tpu" / "serve" / "engine.py"

# jax_log_compiles emits two shapes the watcher reads:
#   "Finished tracing + transforming {name} for pjit in {t} sec"
#     (logger jax._src.dispatch)
#   "Compiling jit({name}) with global shapes and types [...]"
#     (logger jax._src.interpreters.pxla)
# Both are keyed by the bare function name, so a trace and its compile
# land under the same key.
_TRACE_RE = re.compile(r"Finished tracing \+ transforming (.+?) for pjit")
_COMPILE_RE = re.compile(r"^Compiling jit\((.+)\) with global shapes")
_COMPILE_LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla")

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_ACTIVE_WATCHERS: list["CompileWatcher"] = []
_MONITORING_INSTALLED = False


def _install_monitoring_listener() -> None:
    """One process-wide listener fanning out to active watchers (the
    monitoring API has no unregister, so never register per-watcher)."""
    global _MONITORING_INSTALLED
    if _MONITORING_INSTALLED:
        return
    try:

        def _on_event(event: str, duration: float, **_kw: Any) -> None:
            if event == _BACKEND_COMPILE_EVENT:
                for w in _ACTIVE_WATCHERS:
                    w.backend_compiles += 1

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _MONITORING_INSTALLED = True
    except Exception:  # pragma: no cover - monitoring API drift
        _MONITORING_INSTALLED = True  # don't retry every watcher


class CompileWatcher(logging.Handler):
    """Context manager counting per-function traces/compiles while active.

    ``mark_steady()`` snapshots the counters; ``new_compiles_since_mark``
    is then the retrace report: any function compiled after the mark.
    """

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.traces: dict[str, int] = {}
        self.compiles: dict[str, int] = {}
        self.backend_compiles = 0
        self._mark_traces: dict[str, int] = {}
        self._mark_compiles: dict[str, int] = {}
        self._saved_flag: bool | None = None
        self._saved_propagate: dict[str, bool] = {}

    # --- logging.Handler ------------------------------------------------
    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # pragma: no cover - malformed record
            return
        m = _TRACE_RE.search(msg)
        if m:
            self.traces[m.group(1)] = self.traces.get(m.group(1), 0) + 1
            return
        m = _COMPILE_RE.search(msg)
        if m:
            self.compiles[m.group(1)] = self.compiles.get(m.group(1), 0) + 1

    # --- context --------------------------------------------------------
    def __enter__(self) -> "CompileWatcher":
        self._saved_flag = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        for name in _COMPILE_LOGGERS:
            logger = logging.getLogger(name)
            self._saved_propagate[name] = logger.propagate
            # Handlers attached to the logger fire regardless of
            # propagate; cutting propagation keeps N-steps-worth of
            # "Compiling ..." noise out of the operator's console.
            logger.propagate = False
            logger.addHandler(self)
        _install_monitoring_listener()
        _ACTIVE_WATCHERS.append(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        if self in _ACTIVE_WATCHERS:
            _ACTIVE_WATCHERS.remove(self)
        for name in _COMPILE_LOGGERS:
            logger = logging.getLogger(name)
            logger.removeHandler(self)
            logger.propagate = self._saved_propagate.get(name, True)
        if self._saved_flag is not None:
            jax.config.update("jax_log_compiles", self._saved_flag)

    # --- counters -------------------------------------------------------
    def mark_steady(self) -> None:
        self._mark_traces = dict(self.traces)
        self._mark_compiles = dict(self.compiles)

    def _delta(self, now: dict[str, int], mark: dict[str, int]) -> dict[str, int]:
        out = {}
        for fn, count in now.items():
            grew = count - mark.get(fn, 0)
            if grew > 0:
                out[fn] = grew
        return out

    def new_compiles_since_mark(self) -> dict[str, int]:
        return self._delta(self.compiles, self._mark_compiles)

    def new_traces_since_mark(self) -> dict[str, int]:
        return self._delta(self.traces, self._mark_traces)

    @property
    def compile_count(self) -> int:
        return sum(self.compiles.values())

    @property
    def retrace_count(self) -> int:
        """Compiles beyond the first per function — 0 in a healthy run."""
        return sum(c - 1 for c in self.compiles.values() if c > 1)

    def snapshot(self) -> dict:
        return {
            "traces": dict(sorted(self.traces.items())),
            "compiles": dict(sorted(self.compiles.items())),
            "compile_count": self.compile_count,
            "retrace_count": self.retrace_count,
            "backend_compiles": self.backend_compiles,
        }


# --- donation ---------------------------------------------------------------


@dataclass(frozen=True)
class DonationReport:
    donated_bytes: int
    retained_bytes: int
    donated_leaves: int
    retained_leaves: int

    @property
    def effective(self) -> bool:
        return self.donated_bytes > 0

    def to_dict(self) -> dict:
        return {
            "donated_bytes": self.donated_bytes,
            "retained_bytes": self.retained_bytes,
            "donated_leaves": self.donated_leaves,
            "retained_leaves": self.retained_leaves,
            "effective": self.effective,
        }


def measure_donation(fn: Callable, state: Any, *args: Any) -> tuple[Any, DonationReport]:
    """Call ``fn(state, *args)`` and report how much of ``state`` the
    compiled program actually donated (buffer deleted after dispatch).

    Works because donation is observable from the host: a donated jax
    Array's buffer is invalidated the moment the computation consumes
    it, and ``is_deleted()`` says so — on CPU just as on TPU.
    """
    leaves = [
        leaf
        for leaf in jax.tree_util.tree_leaves(state)
        if hasattr(leaf, "is_deleted")
    ]
    sizes = [(leaf, int(getattr(leaf, "nbytes", 0))) for leaf in leaves]
    out = fn(state, *args)
    jax.block_until_ready(out)
    donated_bytes = retained_bytes = donated_leaves = retained_leaves = 0
    for leaf, nbytes in sizes:
        if leaf.is_deleted():
            donated_bytes += nbytes
            donated_leaves += 1
        else:
            retained_bytes += nbytes
            retained_leaves += 1
    return out, DonationReport(
        donated_bytes=donated_bytes,
        retained_bytes=retained_bytes,
        donated_leaves=donated_leaves,
        retained_leaves=retained_leaves,
    )


# --- the audit itself -------------------------------------------------------


@dataclass
class PathAudit:
    """One audited dispatch path (single_step / multi_step)."""

    name: str
    steady_steps: int
    new_compiles: dict[str, int] = field(default_factory=dict)
    new_traces: dict[str, int] = field(default_factory=dict)
    cache_size: int | None = None
    donation: DonationReport | None = None
    # Which source file findings anchor on (the baseline key's path);
    # None -> the trainer (the pre-serve audits' anchor).
    audited_file: str | None = None

    @property
    def clean(self) -> bool:
        return not self.new_compiles and (
            self.donation is None or self.donation.effective
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "steady_steps": self.steady_steps,
            "new_compiles": dict(sorted(self.new_compiles.items())),
            "new_traces": dict(sorted(self.new_traces.items())),
            "cache_size": self.cache_size,
            "donation": self.donation.to_dict() if self.donation else None,
            "clean": self.clean,
        }


@dataclass
class CompileAuditReport:
    paths: list[PathAudit]
    watcher: dict
    violations: list[Violation]

    def to_dict(self) -> dict:
        return {
            "paths": [p.to_dict() for p in self.paths],
            "watcher": self.watcher,
            "violations": [v.to_dict() for v in self.violations],
            "clean": not self.violations,
        }


def violations_for(paths: list[PathAudit]) -> list[Violation]:
    """Fold path audits into baseline-ratchet findings.

    Messages are deliberately count-free: the baseline keys on
    (rule, path, message), and a retrace that fires 3 times vs 4 times
    across runs is the same finding.
    """
    out: list[Violation] = []
    for p in paths:
        anchor = p.audited_file or str(AUDITED_FILE)
        if p.new_compiles:
            fns = ", ".join(sorted(p.new_compiles))
            out.append(
                Violation(
                    rule=AUDIT_RULE_RETRACE,
                    path=anchor,
                    line=1,
                    col=1,
                    message=(
                        f"steady-state retrace on the {p.name} path: "
                        f"{fns} recompiled after warmup (compile-audit "
                        "sentinel; see docs/STATIC_ANALYSIS.md retrace "
                        "runbook)"
                    ),
                )
            )
        if p.donation is not None and not p.donation.effective:
            out.append(
                Violation(
                    rule=AUDIT_RULE_DONATION,
                    path=anchor,
                    line=1,
                    col=1,
                    message=(
                        f"state donation ineffective on the {p.name} "
                        "path: no input buffer was deleted by the step "
                        "(donate_argnums dropped or aliasing declined; "
                        "compile-audit sentinel)"
                    ),
                )
            )
    return out


def _cache_size(jitted: Any) -> int | None:
    try:
        return int(jitted._cache_size())
    except Exception:  # pragma: no cover - private API drift
        return None


def run_compile_audit(
    steady_steps: int = 4,
    warmup_steps: int = 2,
    k: int = 2,
    batch_size: int = 8,
    journal: bool = True,
) -> CompileAuditReport:
    """Run the real trainer on CPU and assert steady-state zero-retrace.

    Small on purpose (tiny MLP, a handful of steps): the sentinel's
    question is "does the dispatch layer reach a fixed point", which is
    shape-independent — the production model would answer it identically
    at 1000x the compile bill.
    """
    import flax.linen as nn

    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import SyntheticDataset
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    class _AuditMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(32)(x)
            x = nn.relu(x)
            return nn.Dense(4)(x)

    devices = jax.devices()
    n = 2 if len(devices) >= 2 else 1
    mesh = build_mesh(MeshSpec.data_parallel(n), devices[:n])
    ds = SyntheticDataset(
        shape=(8, 8, 1), num_classes=4, batch_size=batch_size, seed=0
    )
    trainer = Trainer(
        _AuditMLP(), mesh, TrainerConfig(learning_rate=0.05, optimizer="sgd")
    )
    sample = next(iter(ds.batches(1)))
    paths: list[PathAudit] = []
    with CompileWatcher() as watcher:
        state = trainer.init(jax.random.PRNGKey(0), sample.x)

        # --- single-step fit path (the production loop) ----------------
        state, _ = trainer.fit(
            state, ds.batches(warmup_steps), steps=warmup_steps, prefetch=0
        )
        watcher.mark_steady()
        state, losses = trainer.fit(
            state, ds.batches(steady_steps), steps=steady_steps, prefetch=0
        )
        assert len(losses) == steady_steps
        single = PathAudit(
            name="single_step",
            steady_steps=steady_steps,
            new_compiles=watcher.new_compiles_since_mark(),
            new_traces=watcher.new_traces_since_mark(),
            cache_size=_cache_size(trainer.step_fn),
        )
        x = jax.device_put(sample.x, trainer.batch_sharding)
        y = jax.device_put(sample.y, trainer.batch_sharding)
        (state, _metrics), single.donation = measure_donation(
            trainer.train_step, state, x, y
        )
        paths.append(single)

        # --- multi-step bench path -------------------------------------
        # One wrapper, many calls: multi_step_fn() constructs a NEW jit
        # object per invocation (its own cache), so the audited idiom —
        # and bench.py's — is build-once-call-many.
        kfn = trainer.multi_step_fn(k)
        stack = list(ds.batches(2 * k))
        xs = np.stack([b.x for b in stack[:k]])
        ys = np.stack([b.y for b in stack[:k]])
        state, _ = kfn(state, xs, ys)  # compile
        watcher.mark_steady()
        multi = PathAudit(name="multi_step", steady_steps=steady_steps)
        for i in range(steady_steps):
            xs2 = np.stack([b.x for b in stack[k:]])
            ys2 = np.stack([b.y for b in stack[k:]])
            if i == steady_steps - 1:
                (state, _losses), multi.donation = measure_donation(
                    kfn, state, xs2, ys2
                )
            else:
                state, _losses = kfn(state, xs2, ys2)
        multi.new_compiles = watcher.new_compiles_since_mark()
        multi.new_traces = watcher.new_traces_since_mark()
        multi.cache_size = _cache_size(kfn)
        paths.append(multi)
        jax.block_until_ready(state.params)
        snapshot = watcher.snapshot()

    violations = violations_for(paths)
    if journal:
        from deeplearning_cfn_tpu.obs.recorder import get_recorder

        get_recorder().record(
            "compile_audit",
            clean=not violations,
            compile_count=snapshot["compile_count"],
            retrace_count=snapshot["retrace_count"],
            backend_compiles=snapshot["backend_compiles"],
            paths={p.name: p.to_dict() for p in paths},
        )
    return CompileAuditReport(paths=paths, watcher=snapshot, violations=violations)


def run_serve_audit(
    steady_requests: int = 24,
    journal: bool = True,
) -> CompileAuditReport:
    """The serving-plane sentinel: continuous batching must reach ONE
    compiled decode step and stay there.

    Warms a tiny engine (one request through prefill + decode compiles
    both jits), marks steady, then pushes ``steady_requests`` requests of
    MIXED prompt/output lengths through the scheduler — every admission,
    every occupancy pattern, every page placement must hit the same two
    executables.  Any post-warmup compile is a DLC410 finding anchored on
    serve/engine.py; a decode step that stops donating the paged pool
    (two pool-sized buffers resident per step) is a DLC411 finding.
    """
    import dataclasses

    import jax.numpy as jnp

    from deeplearning_cfn_tpu.models.llama import LlamaConfig, init_params
    from deeplearning_cfn_tpu.serve.engine import (
        ContinuousBatchingEngine,
        ServeConfig,
        ServeRequest,
        paged_decode_step,
    )

    cfg = dataclasses.replace(
        LlamaConfig.tiny(vocab_size=64, seq_len=64), dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    scfg = ServeConfig(
        num_slots=4, block_size=4, blocks_per_slot=8, prefill_len=16
    )
    engine = ContinuousBatchingEngine(
        cfg, params, scfg, clock=lambda: 0.0, journal=False
    )
    rng = np.random.default_rng(0)

    def make_request(i: int) -> ServeRequest:
        prompt = rng.integers(0, 64, size=int(rng.integers(1, 17)))
        return ServeRequest(
            f"audit-{i}", prompt.astype(np.int32), int(rng.integers(1, 17))
        )

    paths: list[PathAudit] = []
    with CompileWatcher() as watcher:
        engine.submit(make_request(0))
        while engine.pending():
            engine.step()
        watcher.mark_steady()

        decode_steps = 0
        for i in range(1, steady_requests + 1):
            engine.submit(make_request(i))
        while engine.pending():
            engine.step()
            decode_steps += 1

        audit = PathAudit(
            name="serve_decode",
            steady_steps=decode_steps,
            new_compiles=watcher.new_compiles_since_mark(),
            new_traces=watcher.new_traces_since_mark(),
            cache_size=_cache_size(paged_decode_step),
            audited_file=str(SERVE_AUDITED_FILE),
        )
        # Donation check on the real steady-state call: the paged pool
        # must be consumed (deleted), not copied, by the decode step.
        scfg_t = engine.serve_cfg
        tokens = np.zeros(scfg_t.num_slots, np.int32)
        lengths = np.zeros(scfg_t.num_slots, np.int32)
        tables = np.zeros(
            (scfg_t.num_slots, scfg_t.blocks_per_slot), np.int32
        )
        active = np.zeros(scfg_t.num_slots, bool)
        (_, engine.cache), audit.donation = measure_donation(
            lambda cache: paged_decode_step(
                cfg,
                engine.params,
                cache,
                tokens,
                lengths,
                tables,
                active,
                engine._key,
                temperature=scfg_t.temperature,
            ),
            engine.cache,
        )
        paths.append(audit)
        snapshot = watcher.snapshot()

    violations = violations_for(paths)
    if journal:
        from deeplearning_cfn_tpu.obs.recorder import get_recorder

        get_recorder().record(
            "compile_audit",
            clean=not violations,
            compile_count=snapshot["compile_count"],
            retrace_count=snapshot["retrace_count"],
            backend_compiles=snapshot["backend_compiles"],
            paths={p.name: p.to_dict() for p in paths},
        )
    return CompileAuditReport(paths=paths, watcher=snapshot, violations=violations)
