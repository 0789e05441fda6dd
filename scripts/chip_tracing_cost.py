"""What the program's tracing costs: the seams' host time per step with no
capture running (a loop over a jitted no-op), alone and with a drain's row
every tenth step (ResNet's `log_every`) and the collector's hook installed;
a drain's row, its judgement by `_FitSeams` and a collection's two hook
calls by themselves; and
ResNet-50's steps per second inside a `jax.profiler` capture with and without
the spans' annotations, with the capture's size and the collections a step of
that loop makes.  Through chiprun; the last line is the result."""

from __future__ import annotations

import itertools
import json
import shutil
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def seams_per_step(steps: int = 20000) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.obs import tracing
    from deeplearning_cfn_tpu.obs.tracing import span
    from deeplearning_cfn_tpu.train.trainer import _FitSeams

    noop = jax.jit(lambda x: x)
    x = jnp.zeros((), jnp.float32)
    noop(x).block_until_ready()

    def bare():
        t = time.perf_counter()
        for _ in range(steps):
            noop(x)
        return (time.perf_counter() - t) / steps

    def seamed():
        t = time.perf_counter()
        for i in range(steps):
            with span("fit.data_wait", journal=False):
                pass
            with span("fit.step", journal=False, step_num=i):
                with span("fit.h2d", journal=False):
                    pass
                with span("fit.dispatch", journal=False):
                    noop(x)
                with span("fit.log", journal=False):
                    pass
        return (time.perf_counter() - t) / steps

    def through_fit_seams(rows: bool, every: int = 10):
        """The seamed loop with a `fit.sync` every `every` steps, through
        `_FitSeams` itself: as the parent ran it, and with `rows` as
        `Trainer.fit` runs it now, the check behind every dispatch and a
        row every drain."""
        seams = _FitSeams(types.SimpleNamespace(), None)
        t = time.perf_counter()
        for i in range(steps):
            with seams("fit.data_wait"):
                pass
            with span("fit.step", journal=False, step_num=i):
                with seams("fit.h2d"):
                    pass
                with seams("fit.dispatch"):
                    noop(x)
                if rows:
                    seams.dispatched()
                with seams("fit.log"):
                    pass
                if i % every == every - 1:
                    with seams.drain(i + 1, every) if rows else seams("fit.sync", every):
                        pass
        return (time.perf_counter() - t) / steps

    def one_drain():
        drains = tracing.Drains()
        t = time.perf_counter()
        for i in range(steps):
            drains.returned(i + 1, 1)
            drains.dispatched()
        return (time.perf_counter() - t) / steps

    def one_judgement():
        """`_FitSeams._judge` on a drain of a second a step: over the floor,
        so the median of the last 64 is taken; no stall among them."""
        seams = _FitSeams(types.SimpleNamespace(), None)
        row = {"interval_s": 2.0, "steps": 2}
        t = time.perf_counter()
        for _ in range(steps):
            seams._judge(row, row)
        return (time.perf_counter() - t) / steps

    def one_collection():
        t = time.perf_counter()
        for _ in range(steps):
            tracing._on_gc("start", {"generation": 0})
            tracing._on_gc("stop", {"generation": 0})
        return (time.perf_counter() - t) / steps

    rounds = [(bare(), seamed(), through_fit_seams(False), through_fit_seams(True), one_drain(), one_collection(),
               one_judgement()) for _ in range(3)]
    b, s, y, d, row, hook, judged = (min(r[k] for r in rounds) for k in range(7))
    return {"bare_us_per_step": b * 1e6, "seamed_us_per_step": s * 1e6,
            "five_seams_us_per_step": (s - b) * 1e6,
            "drain_every_10_us_per_step": (d - y) * 1e6,
            "us_per_drain": row * 1e6, "us_per_judgement": judged * 1e6,
            "hook_us_per_collection": hook * 1e6}


def capture_cost(steps: int = 60) -> dict:
    """`Trainer.fit` on the cell's own ResNet-50 step, `steps` steps inside a
    capture per arm: host tracer on with the annotations, host tracer on
    with `obs.tracing` annotating nothing, and host tracer off."""
    import jax

    from benchmarks import traffic_gen
    from benchmarks.job import seed_key
    from benchmarks.manifest import DEFAULT, Manifest
    from deeplearning_cfn_tpu.obs import tracing
    from deeplearning_cfn_tpu.train.data import Batch
    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    manifest = Manifest(DEFAULT)
    cell = manifest.workload("resnet50.train-b128")
    config = manifest.config(cell["config"])
    traffic = manifest.json("traffic", cell["traffic"])
    builder = manifest.module("builders", config["kind"])
    reference = manifest.module("reference", config["kind"])
    key = seed_key(7)
    pool = traffic_gen.make_pool(traffic, config, 7)
    built = builder.build(config, traffic, key, pool[0][0], reference)
    state, built.state = built.state, None
    trainer = built.trainer
    batches = (Batch(x, y) for x, y in itertools.cycle(pool))
    state, _ = trainer.fit(state, batches, steps=30)  # compile and settle
    out = {}
    annotate = tracing._annotation
    for arm, level, on in (("host_tracer_annotated", 1, True), ("host_tracer_bare", 1, False),
                           ("host_tracer_off", 0, True), ("no_capture", None, True)):
        tracing._annotation = annotate if on else (lambda *a, **k: None)
        trace_dir = ROOT / "chiprun_out" / "tracing_cost" / arm
        shutil.rmtree(trace_dir, ignore_errors=True)
        if level is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = level
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        # Ten steps for the capture's start to pass, then the timed ones.
        state, _ = trainer.fit(state, batches, steps=10)
        collections = tracing.counters().get("gc.pause_s", {"count": 0, "total": 0.0})
        t = time.perf_counter()
        state, losses = trainer.fit(state, batches, steps=steps)
        seconds = time.perf_counter() - t
        after = tracing.counters().get("gc.pause_s", collections)
        size = None
        if level is not None:
            jax.profiler.stop_trace()
            size = max(p.stat().st_size for p in trace_dir.rglob("*.xplane.pb"))
            shutil.rmtree(trace_dir, ignore_errors=True)
        out[arm] = {
            "steps_per_s": len(losses) / seconds, "xplane_bytes": size,
            "collections_per_step": (after["count"] - collections["count"]) / len(losses),
            "gc_ms_per_step": 1e3 * (after["total"] - collections["total"]) / len(losses),
        }
    tracing._annotation = annotate
    return out


def readback_ms(rounds: int = 200) -> dict:
    """The host's time to read ready device scalars back, the device idle:
    what `fit.sync` costs after the device has finished (ten losses with
    `log_every` 10, two with 2), by `device_get` of a list and of one
    stacked array."""
    import jax
    import jax.numpy as jnp

    out = {}
    for n in (1, 2, 10):
        scalars = [jnp.float32(i) + 1 for i in range(n)]
        jax.block_until_ready(scalars)
        stack = jax.jit(lambda *v: jnp.stack(v))
        jax.block_until_ready(stack(*scalars))
        for name, read in (
            ("list", lambda: jax.device_get(scalars)),
            ("stacked", lambda: jax.device_get(stack(*scalars))),
        ):
            times = []
            for _ in range(rounds):
                # fresh arrays: a read array keeps its host copy
                scalars = [s + 0 for s in scalars]
                jax.block_until_ready(scalars)
                t = time.perf_counter()
                read()
                times.append(time.perf_counter() - t)
            times.sort()
            out[f"{name}_{n}"] = {"p50_ms": 1e3 * times[len(times) // 2], "p95_ms": 1e3 * times[int(len(times) * 0.95)]}
    return out


def main() -> int:
    import jax

    result = {"device": jax.devices()[0].device_kind, "seams": seams_per_step(),
              "readback": readback_ms()}
    if jax.devices()[0].platform == "tpu" and "--no-capture" not in sys.argv:
        result["capture"] = capture_cost()
    print(json.dumps(result, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
