"""Input pipeline: ms per step that `fit` waited for a batch
(`PipelineStats.consumer_wait_seconds / batches`, whole `fit` call)."""


def read(run: dict) -> float | None:
    stats = run["pipeline"]
    if not stats.get("batches"):
        return None
    return 1e3 * stats["consumer_wait_seconds"] / stats["batches"]
