"""The `train` job: what `dlcfn run benchmarks/template.json` launches.

`cli.cmd_run` imports this module and calls `main(argv)` in its own
process, after template -> provision -> contract -> launch plan, as it does
for any example.  One configuration, one traffic file, one `Trainer.fit`
call; what it returns is the run's record, which `benchmarks.run` turns
into metrics.  A traffic file of another `kind` gets a driver of its own
beside this one.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import math
import shutil
import time
from pathlib import Path


def seed_key(seed: int):
    """A PRNG key from any whole number, also one over 2**31."""
    import jax

    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31)


def device_memory() -> dict[int, dict]:
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[d.id] = {
            "bytes_in_use": stats.get("bytes_in_use", 0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
        }
    return out


def main(argv: list[str] | None = None) -> dict:
    t_job = time.perf_counter()
    import jax

    from benchmarks import check, traffic_gen
    from benchmarks.manifest import ROOT, Manifest
    from benchmarks.probe import StateProbe
    from benchmarks.recorder import CompileCounter, StepRecorder
    from deeplearning_cfn_tpu.parallel.sharding import bytes_by_device
    from deeplearning_cfn_tpu.train.data import Batch
    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default="benchmarks/out")
    args = p.parse_args(argv)

    manifest = Manifest(ROOT / args.manifest)
    cell = manifest.workload(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.json("traffic", cell["traffic"])
    limits = manifest.json("limits", args.workload)
    builder = manifest.module("builders", config["kind"])
    reference = manifest.module("reference", config["kind"])
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)

    cache_dir = enable_compile_cache()
    # Every program, however quick to compile, is in the cache for the
    # next run of the cell: set-up has to find all of them there.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()

    chips = len(jax.devices())
    key = seed_key(args.seed)
    pool = traffic_gen.make_pool(traffic, config, args.seed)
    built = builder.build(config, traffic, key, pool[0][0], reference)
    trainer = built.trainer
    follow_steps = int(traffic["check_steps"])
    trace_dir = out / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    recorder = StepRecorder(
        warm_seconds=float(traffic["warm_seconds"]),
        seconds=args.seconds,
        compile_total=counter.total,
        trace_dir=str(trace_dir) if args.trace else None,
        trace_seconds=float(traffic["trace_seconds"]),
    )
    probe = StateProbe(built, key, follow_steps, on_done=recorder.mark_ready)
    # The compiled step's own account of its memory, through the trainer's
    # API; the compile is the one fit's first dispatch uses.
    _, compiled = trainer.compile_stats(built.state, *pool[0], return_compiled=True)
    analysis = compiled.memory_analysis()
    step_memory = {
        name: int(getattr(analysis, name, 0) or 0)
        for name in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
        )
    }
    state, built.state = built.state, None
    batches = (Batch(x, y) for x, y in itertools.cycle(pool))
    try:
        state, losses = trainer.fit(
            state, batches, steps=10**9, logger=recorder,
            stop_fn=recorder.should_stop, checkpointer=probe,
        )
    finally:
        if recorder.trace_started is not None and recorder.trace_stopped is None:
            jax.profiler.stop_trace()
    recorder.close()

    step_counter = int(jax.device_get(state.step))
    ids = sorted(d.id for d in jax.devices())
    state_bytes = bytes_by_device(state)
    batch_bytes = trainer.batch_bytes_by_device or {}
    program = {"loss": losses[:follow_steps], **probe.readings()}
    memory = device_memory()
    pipeline = trainer.last_pipeline_stats.snapshot()
    window = recorder.window()
    compiles_in_window = (
        recorder.compiles[window[1]] - recorder.compiles[window[0]] if window else None
    )
    conditions = {
        "window_closed": window is not None,
        "losses_finite": all(math.isfinite(v) for v in losses),
        "step_counter": step_counter == len(losses) == len(recorder.times),
        "parameters_moved": all(v > 0 for v in program["update_norm"].values()),
        "state_on_every_chip": sorted(state_bytes) == ids
        and all(v > 0 for v in state_bytes.values()),
        "batch_on_every_chip": sorted(batch_bytes) == ids
        and len(set(batch_bytes.values())) == 1,
        "no_compile_in_window": compiles_in_window == 0,
    }

    # The program's state goes before the reference runs, so that the
    # float32 batch fits and the memory read above stays the program's.
    batch_sharding = trainer.batch_sharding if chips > 1 else None
    del state, built, probe, trainer, compiled
    gc.collect()
    t_check = time.perf_counter()
    followed = reference.follow(
        key, config, pool, follow_steps, batch_sharding=batch_sharding
    )
    rows = check.compare(program, followed, limits)
    check_seconds = time.perf_counter() - t_check

    return {
        "workload": args.workload,
        "seed": args.seed,
        "chips": chips,
        "examples_per_step": int(traffic["global_batch"]),
        "t_job": t_job,
        "t_first_step": recorder.times[0] if recorder.times else None,
        "steps": recorder.steps,
        "times": recorder.times,
        "window": window,
        "losses": losses,
        "conditions": conditions,
        "check": rows,
        "check_seconds": check_seconds,
        "program": {"loss": program["loss"]},
        "reference": {"loss": followed["loss"]},
        "compile": {**counter.snapshot(), "in_window": compiles_in_window, "cache_dir": cache_dir},
        "pipeline": pipeline,
        "memory": memory,
        "step_memory": step_memory,
        "state_bytes_by_device": state_bytes,
        "batch_bytes_by_device": batch_bytes,
        "trace_dir": str(trace_dir) if args.trace else None,
        # Steps the trace holds before and after it settled.
        "trace_skip_steps": (
            recorder.trace_settled[1] - recorder.trace_started[1]
            if recorder.trace_stopped else None
        ),
        "trace_steps": (
            recorder.trace_stopped[1] - recorder.trace_settled[1]
            if recorder.trace_stopped else None
        ),
        "config": config,
        "traffic": traffic,
    }

