"""Job set-up and compile: seconds in the backend's part of every compile
request before the first step completed: the compile itself or, on a hit of
the persistent cache, reading and loading the executable
(`first_step.compile.backend_s` of `obs.tracing.counters()`)."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    frozen = host_spans.first_step_counters(run)
    if frozen is None or "backend_s" not in frozen:
        return None
    return frozen["backend_s"]["total"]
