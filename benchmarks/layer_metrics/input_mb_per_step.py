"""Input pipeline: MB moved host to device per step
(`PipelineStats.bytes_transferred / batches`, whole `fit` call)."""


def read(run: dict) -> float | None:
    stats = run["pipeline"]
    if not stats.get("batches"):
        return None
    return stats["bytes_transferred"] / stats["batches"] / 1e6
