"""What the program's tracing costs: the seams' host time per step with no
capture running (a loop over a jitted no-op), and ResNet-50's steps per second
inside a `jax.profiler` capture with and without the spans' annotations, with
the capture's size.  Through chiprun; the last line is the result."""

from __future__ import annotations

import itertools
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def seams_per_step(steps: int = 20000) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.obs.tracing import span

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

    rounds = [(bare(), seamed()) for _ in range(3)]
    b, s = min(r[0] for r in rounds), min(r[1] for r in rounds)
    return {"bare_us_per_step": b * 1e6, "seamed_us_per_step": s * 1e6,
            "five_seams_us_per_step": (s - b) * 1e6}


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
        t = time.perf_counter()
        state, losses = trainer.fit(state, batches, steps=steps)
        seconds = time.perf_counter() - t
        size = None
        if level is not None:
            jax.profiler.stop_trace()
            size = max(p.stat().st_size for p in trace_dir.rglob("*.xplane.pb"))
            shutil.rmtree(trace_dir, ignore_errors=True)
        out[arm] = {"steps_per_s": len(losses) / seconds, "xplane_bytes": size}
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
