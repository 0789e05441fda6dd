"""Job set-up and compile: seconds JAX spent tracing functions to jaxprs and
lowering them to MLIR modules before the first step completed
(`obs.tracing.counters()`, the `first_step.` copies that `Trainer.fit`
freezes).  The counts (how often something was traced, lowered, compiled,
read from the cache) go to the notes."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    frozen = host_spans.first_step_counters(run)
    if frozen is None:
        return None
    return sum(frozen.get(name, {"total": 0.0})["total"] for name in ("trace_s", "lower_s"))
