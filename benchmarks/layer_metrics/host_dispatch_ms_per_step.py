"""Trainer: the host's time in the call of the jitted step, mean over the
whole `fit` (`obs.tracing.span_aggregates()["fit.dispatch"]`), in
milliseconds.  The first call, which compiles or reads the cache, goes to the
notes apart from it as the aggregate's longest."""

from benchmarks import host_spans


def read(run: dict) -> float | None:
    from deeplearning_cfn_tpu.obs import tracing

    seam = tracing.span_aggregates().get("fit.dispatch")
    if not seam or seam["count"] < 2 or not host_spans.has_device_trace(run):
        return None
    run.setdefault("notes", {})["fit_seams"] = {
        name: stats for name, stats in tracing.span_aggregates().items()
        if name.startswith(("fit.", "prefetch.", "trainer."))
    }
    # Without the longest call: the first, with the compile in it.
    return 1e3 * (seam["total_s"] - seam["max_s"]) / (seam["count"] - 1)
