"""Training metrics / throughput logging.

The _LoggerHook analog (cifar10_multi_machine_train.py:38-60): every N
steps, log step, loss, and examples/sec.  Also the first-class profiling
hook SURVEY §5 calls for: optional JAX profiler trace capture around a step
window.
"""

from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import jax

from deeplearning_cfn_tpu.utils.logging import get_logger
from deeplearning_cfn_tpu.utils.timeouts import Clock, MonotonicClock

log = get_logger("dlcfn.train")

# Peak dense bf16 matmul throughput per chip, by JAX device_kind — the
# denominator of MFU.  The reference had no utilization readout at all
# (its closest artifact is examples/sec in the _LoggerHook,
# cifar10_multi_machine_train.py:38-60); on TPU the honest headline metric
# is model FLOPs utilization against the MXU peak.
PEAK_BF16_FLOPS_PER_CHIP: dict[str, float] = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,  # v5p reports "TPU v5"
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> float | None:
    """Peak bf16 FLOP/s for a JAX device.  Longest-prefix match so
    'TPU v5 lite' wins over 'TPU v5'.  None off a TPU (the CPU mesh the
    tests run on has no MXU to be a fraction of); on platform ``tpu`` a
    kind missing from the table raises — a chip run never logs a silent
    ``mfu: null``."""
    return _device_peak(PEAK_BF16_FLOPS_PER_CHIP, device)


# Peak HBM bandwidth per chip (bytes/s, public Cloud TPU figures) — the
# denominator of MBU (model-bandwidth utilization), the honest headline
# for autoregressive DECODE the way MFU is for training: each decode step
# must stream the weights from HBM once, so tokens/s is bandwidth-bound.
PEAK_HBM_BYTES_PER_CHIP: dict[str, float] = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,  # v5p reports "TPU v5"
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}


def peak_hbm_bytes_per_chip(device=None) -> float | None:
    """Peak HBM bytes/s for a JAX device; same contract as
    :func:`peak_flops_per_chip`."""
    return _device_peak(PEAK_HBM_BYTES_PER_CHIP, device)


def _device_peak(table: dict[str, float], device) -> float | None:
    d = device if device is not None else jax.devices()[0]
    kind = str(getattr(d, "device_kind", ""))
    best: tuple[int, float] | None = None
    for prefix, value in table.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), value)
    if best is not None:
        return best[1]
    if getattr(d, "platform", None) == "tpu":
        raise ValueError(
            f"no published peak for TPU device_kind {kind!r}; add it to the "
            "tables in train/metrics.py with its source"
        )
    return None


def utilization(
    numerator: float | None, denominator: float | None, ndigits: int = 4
) -> float | None:
    """``round(numerator / denominator, ndigits)`` with None propagation.

    The MFU/MBU ratio for bench emitters: either side is None off a TPU
    (the CPU test backend has no peak) or when the measurement is
    unavailable, and the honest JSON output is ``null`` — never the NaN
    that a ``x or float('nan')`` fallback would smuggle into json.dumps
    as an unparseable bare token.
    """
    if numerator is None or denominator is None or denominator == 0:
        return None
    value = numerator / denominator
    if value != value or value in (float("inf"), float("-inf")):
        return None
    return round(value, ndigits)


def json_safe(obj):
    """Recursively map non-finite floats (NaN/Inf) to None so the result
    always serializes under ``json.dumps(..., allow_nan=False)``.

    Bench/metrics emitters compute ratios from measured values; a NaN
    loss or an unknown device peak must surface as ``null`` in the
    stream, not crash the run or emit invalid JSON."""
    if isinstance(obj, float):
        if obj != obj or obj in (float("inf"), float("-inf")):
            return None
        return obj
    # 0-d numpy/jax scalars (np.float32 is NOT a Python float) unwrap to
    # plain Python, then re-enter for the finiteness check.
    if getattr(obj, "shape", None) == () and hasattr(obj, "item"):
        return json_safe(obj.item())
    if isinstance(obj, dict):
        return {k: json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    return obj


@dataclass
class JsonlMetricsSink:
    """Structured per-worker metrics stream on (shared) storage — the
    analog of the reference's per-rank training logs collected on EFS
    (mpirun --output-filename, run.sh:82), machine-readable instead of
    free text.  One JSONL file per process; every record carries the
    wallclock and process index so multi-worker runs collate trivially.
    """

    path: str | Path
    _fh: object = field(default=None, repr=False)

    def __post_init__(self) -> None:
        p = Path(self.path)
        p.parent.mkdir(parents=True, exist_ok=True)
        self._fh = open(p, "a", buffering=1)  # line-buffered

    def write(self, record: dict) -> None:
        # json_safe first: a NaN loss must land in the stream as null,
        # not crash training (allow_nan=False alone would raise) or emit
        # a bare NaN token nothing can parse back.
        self._fh.write(
            json.dumps(
                json_safe(
                    {"ts": time.time(), "process": jax.process_index(), **record}
                ),
                allow_nan=False,
            )
            + "\n"
        )

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    @classmethod
    def for_run(cls, base_dir: str | Path, run_name: str) -> "JsonlMetricsSink":
        """<base>/<run>/worker<pid>.jsonl, base typically the cluster's
        shared storage mount."""
        return cls(
            Path(base_dir) / run_name / f"worker{jax.process_index()}.jsonl"
        )


class MetricsOutage(RuntimeError):
    """The metrics sink stayed down past the configured grace window."""

    def __init__(self, grace_s: float, buffered: int):
        super().__init__(
            f"metrics sink down for more than {grace_s:.0f}s "
            f"({buffered} records buffered)"
        )
        self.grace_s = grace_s
        self.buffered = buffered


@dataclass
class ResilientSink:
    """Keep training through a metrics-plane outage (graceful degradation).

    Wraps any sink with ``write``/``close``.  When the inner sink starts
    raising OSError (broker gone, shared storage unmounted), records are
    buffered — bounded in memory and mirrored to the flight recorder ring
    as ``metric_buffered`` events so nothing is silently dropped — and the
    trainer keeps stepping.  The first successful write flushes the buffer
    in order.  Only after ``grace_s`` of continuous outage (measured on
    the injected clock, so chaos soaks run in virtual time) does the
    typed :class:`MetricsOutage` escape to the caller.
    """

    inner: Any
    grace_s: float = 120.0
    clock: Clock = field(default_factory=MonotonicClock)
    max_buffered: int = 10_000

    def __post_init__(self) -> None:
        self._buffer: deque[dict] = deque(maxlen=self.max_buffered)
        self._outage_start: float | None = None

    @property
    def degraded(self) -> bool:
        return self._outage_start is not None

    @property
    def buffered(self) -> int:
        return len(self._buffer)

    def write(self, record: dict) -> None:
        try:
            while self._buffer:
                self.inner.write(self._buffer[0])
                self._buffer.popleft()
            self.inner.write(record)
        except OSError as exc:
            self._on_failure(record, exc)
            return
        if self._outage_start is not None:
            self._outage_start = None
            self._record("metrics_recovered", buffered=0)

    def _on_failure(self, record: dict, exc: OSError) -> None:
        now = self.clock.now()
        if self._outage_start is None:
            self._outage_start = now
            log.warning("metrics sink down, buffering (%s)", exc)
        self._buffer.append(record)
        self._record(
            "metric_buffered",
            buffered=len(self._buffer),
            record=json_safe(record),
        )
        if now - self._outage_start > self.grace_s:
            raise MetricsOutage(self.grace_s, len(self._buffer)) from exc

    def _record(self, kind: str, **fields) -> None:
        try:
            from deeplearning_cfn_tpu.obs.recorder import get_recorder

            get_recorder().record(kind, **fields)
        except Exception:  # pragma: no cover - journaling is best-effort
            pass

    def close(self) -> None:
        self.inner.close()


@dataclass
class ThroughputLogger:
    """Per-N-steps throughput/loss logger.  ``loss`` may be a device
    scalar: it is materialized (forcing a host sync) only on log steps,
    so callers in async-dispatch loops stay sync-free between logs.

    With ``flops_per_step`` and ``peak_flops``, each record also carries
    MFU.  Match the two scopes: per-device flops (what
    ``Trainer.compile_stats`` reports — cost_analysis is per-device under
    SPMD partitioning) pair with the per-chip peak; GLOBAL analytic flops
    (e.g. llama.train_flops_per_token x global tokens) pair with
    ``n_chips * peak_flops_per_chip()``.
    """

    global_batch_size: int
    log_every: int = 10
    name: str = "train"
    sink: JsonlMetricsSink | None = None
    flops_per_step: float | None = None
    peak_flops: float | None = None
    _t0: float = field(default_factory=time.perf_counter)
    _last_step: int = 0
    history: list[dict] = field(default_factory=list)

    def step(self, step: int, loss) -> None:
        if step % self.log_every:
            return
        # Wait for the step BEFORE reading the clock: dispatch is
        # asynchronous, and a window closed at enqueue time charges this
        # step's device time to the next window (on the chip the window
        # after the first sync read twice the true rate).
        loss = float(loss)
        now = time.perf_counter()
        dsteps = step - self._last_step
        dt = now - self._t0
        examples_per_sec = (
            self.global_batch_size * dsteps / dt if dsteps else 0.0
        )
        record = {
            "step": step,
            "loss": loss,
            "examples_per_sec": examples_per_sec,
        }
        if self.flops_per_step and self.peak_flops and dsteps and dt > 0:
            record["mfu"] = self.flops_per_step * dsteps / dt / self.peak_flops
        self.history.append(record)
        if self.sink is not None:
            self.sink.write({"event": "train_step", "run": self.name, **record})
        log.info(
            "%s step=%d loss=%.4f examples/sec=%.1f%s",
            self.name,
            step,
            record["loss"],
            examples_per_sec,
            f" mfu={record['mfu']:.3f}" if "mfu" in record else "",
        )
        self._t0 = now
        self._last_step = step


@contextlib.contextmanager
def profile_trace(log_dir: str | None):
    """JAX profiler capture for a step window (xprof-viewable)."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def block_and_time(fn, *args, **kwargs) -> tuple[object, float]:
    """Run fn, block on its outputs, return (result, seconds)."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0
