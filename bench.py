"""Headline benchmark: ResNet-50 synthetic ImageNet throughput per chip.

BASELINE.json's driver metric is "ResNet-50 ImageNet images/sec/chip".  The
reference's corresponding workload is the Horovod synthetic ResNet-50
benchmark (README.md:149-163), for which it publishes **no number**
(BASELINE.md).  ``vs_baseline`` is therefore computed against the era's
publicly documented tensorpack+Horovod ResNet-50 throughput on the
reference's own hardware class (~350 images/sec per V100 on p3.16xlarge,
fp16, batch 64/GPU) — the workload the reference stack existed to run.

Input regime (the PR 13 overlap architecture, docs/PERFORMANCE.md): batches
cross the host->device link as uint8 (4x fewer bytes than f32) and
dequantize+normalize INSIDE the compiled step (TrainerConfig.input_stats) —
the scanned multi-step program therefore carries its own input stage, and
the multi-step phase consumes DISTINCT pre-staged [k, B, ...] stacks kept
double-buffered on device by DevicePrefetcher, each freed (donated) right
after its dispatch.  An int8-WEIGHTS forward variant is reported alongside
(ops/quant.py), riding the same compact-transfer idea one level up.

Measures a TPU and nothing else: on any other platform it exits non-zero
before compiling anything, so a CPU number can never be printed under the
metric's name.  Prints exactly one JSON line.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


# Per-GPU throughput of the reference's flagship stack on its own hardware
# (tensorpack ResNet-50 + Horovod on V100, the workload of README.md:149-163).
REFERENCE_IMAGES_PER_SEC_PER_DEVICE = 350.0

BATCH_PER_CHIP = 128
IMAGE_SIZE = 224
WARMUP_STEPS = 5
MEASURE_STEPS = 20
# Iterations per compiled program (Trainer.multi_step_fn).  Round-5
# measurement (docs/BENCH_NOTES.md): putting k consecutive iterations in
# ONE module leaves cost-model bytes/iteration unchanged (no
# cross-iteration data reuse exists — activations are batch-unique) but
# measures ~9-14% faster per step: XLA pipelines the iteration boundary
# and the per-dispatch overhead amortizes.  k=4 is the measured knee.
STEPS_PER_CALL = 4

# Forward-only window for the int8-weights variant: cheaper per step than
# training, so fewer steps still average out dispatch jitter.
QUANT_WARMUP_STEPS = 2
QUANT_MEASURE_STEPS = 10

PIPELINE_WORKERS = 2
PIPELINE_POOL_BATCHES = 4

# Device-resident stacks the multi-step phase keeps ahead of compute: 2 =
# double buffering (one consumed by the in-flight program, one staged).
STACK_BUFFER = 2


def measure_input_pipeline(
    trainer, state, batch: int, n_chips: int
) -> tuple[dict, dict]:
    """End-to-end device-resident input pipeline measurement: pooled
    uint8 synthetic batches (4x smaller PCIe payload than float32)
    through ``DevicePrefetcher(workers=2)`` straight into the ALREADY-
    compiled train step — with ``TrainerConfig.input_stats`` set the
    step program itself dequantizes, so the uint8 batch IS the step's
    input signature and this phase adds zero compiles.  Returns the
    per-chip throughput plus the PipelineStats counters, and the
    StepProfiler snapshot (data_wait here includes consumer waits on
    the prefetch buffer; h2d is producer-side and overlapped)."""
    from deeplearning_cfn_tpu.obs.profiler import StepProfiler
    from deeplearning_cfn_tpu.train.data import DevicePrefetcher, SyntheticDataset
    from deeplearning_cfn_tpu.train.pipeline import PipelineStats

    ds = SyntheticDataset.imagenet_like(
        batch_size=batch,
        image_size=IMAGE_SIZE,
        dtype="uint8",
        pool_batches=PIPELINE_POOL_BATCHES,
    )

    steps = WARMUP_STEPS + MEASURE_STEPS
    stats = PipelineStats(name="bench")
    profiler = StepProfiler(name="input_pipeline")
    prefetcher = DevicePrefetcher(
        ds.batches(steps),
        trainer.batch_sharding,
        size=2,
        workers=PIPELINE_WORKERS,
        stats=stats,
        profiler=profiler,
    )
    step = trainer.step_fn
    t0 = None
    metrics = None
    try:
        with jax.set_mesh(trainer.mesh):
            profiler.start()
            for i, b in enumerate(profiler.wrap_source(prefetcher)):
                with profiler.phase("dispatch"):
                    state, metrics = step(state, b.x, b.y)
                if i == WARMUP_STEPS - 1:
                    # Sync before opening the timed window.
                    with profiler.sync_boundary(WARMUP_STEPS):
                        float(metrics["loss"])
                    t0 = time.perf_counter()
                profiler.step_done(step=i)
        with profiler.sync_boundary(MEASURE_STEPS):
            final_loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
    finally:
        prefetcher.close()
    assert np.isfinite(final_loss)
    snap = stats.snapshot()
    per_chip = batch * MEASURE_STEPS / dt / n_chips
    return {
        "images_per_sec_per_chip": round(per_chip, 2),
        "transfer_dtype": "uint8",
        "workers": PIPELINE_WORKERS,
        "bytes_transferred": snap["bytes_transferred"],
        "bytes_per_image": round(snap["bytes_transferred"] / (batch * steps), 1),
        "host_input_seconds": snap["host_input_seconds"],
        "producer_stall_seconds": snap["producer_stall_seconds"],
        "consumer_wait_seconds": snap["consumer_wait_seconds"],
        "overlap_fraction": snap["overlap_fraction"],
    }, profiler.journal()


def measure_quantized(trainer, model, state, x, batch: int, n_chips: int) -> dict:
    """int8-WEIGHTS forward variant (ops/quant.py): conv/dense kernels
    cross HBM as int8 + per-channel scales and upcast inside the jitted
    apply, next to their consumers.  Measured as eval-mode forward
    throughput against the same program with float weights, plus the
    worst-case logit deviation on one batch — the compact-weights
    counterpart of the uint8 input plumbing, reported alongside the bf16
    training numbers rather than replacing them."""
    from deeplearning_cfn_tpu.ops.quant import (
        dequantize_tree,
        quantize_tree,
        quantized_nbytes,
        tree_nbytes,
    )

    params, model_state = state.params, state.model_state
    # One jitted program for the whole-tree quantization: eager per-kernel
    # jnp ops would compile a tiny program per layer shape and read as
    # dozens of retraces in the compile watcher.
    qparams, passthrough = jax.jit(quantize_tree)(params)

    @jax.jit
    def fwd_float(p, ms, xb):
        return model.apply({"params": p, **ms}, trainer._normalize_input(xb), train=False)

    @jax.jit
    def fwd_int8(q, pth, ms, xb):
        p = dequantize_tree(q, pth)
        return model.apply({"params": p, **ms}, trainer._normalize_input(xb), train=False)

    def timed(fn, *args) -> tuple[float, jax.Array]:
        out = None
        for _ in range(QUANT_WARMUP_STEPS):
            out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(QUANT_MEASURE_STEPS):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0, out

    with jax.set_mesh(trainer.mesh):
        dt_float, logits_float = timed(fwd_float, params, model_state, x)
        dt_int8, logits_int8 = timed(fwd_int8, qparams, passthrough, model_state, x)
    # Host-side diff (numpy after device_get): eager jnp here would add
    # spurious tiny-program compiles to the watcher's tally.
    lf = np.asarray(jax.device_get(logits_float), np.float32)
    li = np.asarray(jax.device_get(logits_int8), np.float32)
    diff = float(np.max(np.abs(lf - li)))
    per_chip = lambda dt: round(batch * QUANT_MEASURE_STEPS / dt / n_chips, 2)
    float_bytes = tree_nbytes(params)
    int8_bytes = quantized_nbytes(qparams) + tree_nbytes(passthrough)
    return {
        "weights_dtype": "int8",
        "param_bytes_float": float_bytes,
        "param_bytes_int8": int8_bytes,
        "param_bytes_ratio": round(int8_bytes / float_bytes, 3) if float_bytes else None,
        "forward_images_per_sec_per_chip_float": per_chip(dt_float),
        "forward_images_per_sec_per_chip_int8": per_chip(dt_int8),
        "max_abs_logit_diff": round(diff, 4),
    }


def main() -> None:
    from deeplearning_cfn_tpu.analysis.compile_audit import (
        CompileWatcher,
        measure_donation,
    )
    from deeplearning_cfn_tpu.obs.profiler import (
        StepProfiler,
        program_attribution,
        program_cost,
    )
    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache
    from deeplearning_cfn_tpu.models.resnet import ResNet50
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import (
        DevicePrefetcher,
        SyntheticDataset,
        device_put_tree,
        donate_buffers,
        stack_batches,
    )
    from deeplearning_cfn_tpu.train.pipeline import PipelineStats
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}). Run it "
            "through the chip tool; on the CPU use the test suite."
        )
    enable_compile_cache()
    n_chips = len(devices)
    batch = BATCH_PER_CHIP * n_chips

    mesh = build_mesh(MeshSpec.data_parallel(n_chips), devices)
    model = ResNet50(dtype=jnp.bfloat16)
    ds = SyntheticDataset.imagenet_like(
        batch_size=batch,
        image_size=IMAGE_SIZE,
        dtype="uint8",
        pool_batches=PIPELINE_POOL_BATCHES,
    )
    trainer = Trainer(
        model,
        mesh,
        TrainerConfig(
            strategy="dp",
            learning_rate=0.1,
            has_train_arg=True,
            label_smoothing=0.1,
            # uint8 inputs dequantize+normalize INSIDE the compiled step
            # (and inside the multi-step scan body) — the host never
            # touches a float image and every program owns its input stage.
            input_stats=ds.input_stats,
        ),
    )

    # One resident uint8 batch for the dispatch-bound phases (single-step
    # loop, donation probe, quantized forward): placed once, reused.
    b0 = next(iter(ds.batches(1)))
    x = jax.device_put(b0.x, trainer.batch_sharding)
    y = jax.device_put(b0.y, trainer.batch_sharding)

    # The watcher turns the whole bench into its own compile audit:
    # per-function compile counts from the jax_log_compiles stream, so a
    # retrace silently eating the timed window shows up as
    # retrace_count > 0 in the JSON instead of as an unexplained MFU dip
    # (docs/STATIC_ANALYSIS.md retrace runbook).
    with CompileWatcher() as watcher:
        state = trainer.init(jax.random.key(0), x)
        # Cost analysis before any donated execution: flops per compiled
        # step is the MFU numerator.  Keep the executable: its HLO is the
        # comms block's source (collectives + peak HBM).
        stats, step_exe = trainer.compile_stats(state, x, y, return_compiled=True)
        flops_per_step = stats.get("flops_per_step")

        step = trainer.step_fn
        # The ambient mesh is part of the jit cache key: compile_stats
        # AOT-compiles under set_mesh, so dispatching bare here would
        # miss that cache entry and pay the full ResNet-50 compile a
        # second time (this run's own compile audit caught exactly that:
        # step_fn compiled twice until the phase moved under set_mesh).
        with jax.set_mesh(trainer.mesh):
            for _ in range(WARMUP_STEPS):
                state, metrics = step(state, x, y)
            # The readback ends the warm-up: nothing of it is still in
            # flight when the timed window opens.
            float(metrics["loss"])
            # One extra untimed step proving the state buffers actually
            # get donated (is_deleted after dispatch): donated_bytes == 0
            # means the step holds two state copies live.
            (state, metrics), donation = measure_donation(step, state, x, y)

            # Phase attribution for the timed window: dispatch is the
            # per-call enqueue cost, compute surfaces at the final
            # readback (amortized over the window), host is the loop
            # residual.  The profiler's overhead budget is enforced by
            # scripts/perf_smoke.py (<2% of step time).
            prof_single = StepProfiler(name="single_step")
            t0 = time.perf_counter()
            prof_single.start()
            for _ in range(MEASURE_STEPS):
                with prof_single.phase("dispatch"):
                    state, metrics = step(state, x, y)
                prof_single.step_done()
            with prof_single.sync_boundary(MEASURE_STEPS):
                final_loss = float(metrics["loss"])
            dt_single = dt = time.perf_counter() - t0
        assert np.isfinite(final_loss)
        single_step_per_chip = batch * MEASURE_STEPS / dt / n_chips

        # Headline mode: k iterations per compiled program (STEPS_PER_CALL)
        # fed DISTINCT pre-staged batch stacks.  The prefetcher keeps
        # STACK_BUFFER [k, B, ...] uint8 stacks device-resident (producer
        # H2D overlaps the in-flight program's compute) and each consumed
        # stack is freed right after its dispatch — deletion is safe
        # in-flight, and it caps input HBM at ~STACK_BUFFER+1 stacks
        # (docs/PERFORMANCE.md, "the overlap architecture").
        k = STEPS_PER_CALL
        warmup_calls = max(1, WARMUP_STEPS // k)
        outer = max(1, MEASURE_STEPS // k)
        stacked_sharding = NamedSharding(mesh, P(None, *trainer.batch_sharding.spec))
        prof_multi = StepProfiler(name=f"multi_step_k{k}")
        stack_stats = PipelineStats(name="bench_stacks")
        stacked = stack_batches(ds.batches((warmup_calls + outer) * k), k)
        prefetcher = DevicePrefetcher(
            stacked,
            stacked_sharding,
            size=STACK_BUFFER,
            workers=PIPELINE_WORKERS,
            stats=stack_stats,
            profiler=prof_multi,
        )
        kfn = trainer.multi_step_fn(k)
        kexe = kcost = None
        stack_donated = 0
        resident_stacks_peak = 0
        t0 = None
        try:
            with jax.set_mesh(trainer.mesh):
                prof_multi.start()
                for i, stack in enumerate(prof_multi.wrap_source(prefetcher)):
                    with prof_multi.phase("h2d"):
                        # Prefetched stacks are already resident with the
                        # stacked sharding — an identity check per leaf.
                        xs = device_put_tree(stack.x, stacked_sharding)
                        ys = device_put_tree(stack.y, stacked_sharding)
                    if kexe is None:
                        # AOT compile BEFORE the first dispatch: the
                        # per-program cost model for the k-step program
                        # (its flops cover all k iterations), and — like
                        # compile_stats for the single step — it populates
                        # the jit dispatch cache under this mesh, so the
                        # dispatch below hits the cache instead of
                        # compiling a second time (compile_count unchanged).
                        kexe = kfn.lower(state, xs, ys).compile()
                        kcost = program_cost(kexe)
                    resident_stacks_peak = max(
                        resident_stacks_peak, len(prefetcher.buffered())
                    )
                    with prof_multi.phase("dispatch"):
                        state, losses = kfn(state, xs, ys)
                    # The stack is this loop's own placement; XLA cannot
                    # donate it (no same-shaped output to alias into), so
                    # free it explicitly (train/data.donate_buffers).
                    stack_donated += donate_buffers((xs, ys))
                    if i == warmup_calls - 1:
                        with prof_multi.sync_boundary(warmup_calls * k):
                            float(np.asarray(jax.device_get(losses))[-1])
                        t0 = time.perf_counter()
                    prof_multi.step_done(steps=k)
                with prof_multi.sync_boundary(outer * k):
                    final_loss = float(np.asarray(jax.device_get(losses))[-1])
            dt_multi = dt = time.perf_counter() - t0
        finally:
            prefetcher.close()
        assert np.isfinite(final_loss)
        multi_step_per_chip = batch * outer * k / dt / n_chips

        # Quantized-forward first: the pipeline phase dispatches the
        # DONATING step, after which this scope's `state` buffers are gone.
        quantized = measure_quantized(trainer, model, state, x, batch, n_chips)
        pipeline, pipeline_profile = measure_input_pipeline(
            trainer, state, batch, n_chips
        )
    # Both modes are honest measurements and BOTH are reported (the old
    # harness silently dropped the loser); the headline is the better one.
    if multi_step_per_chip >= single_step_per_chip:
        per_chip, mode = multi_step_per_chip, f"multi_step_k{k}"
        mode_reason = (
            f"multi_step_k{k} ({multi_step_per_chip:.0f}) >= "
            f"single_step ({single_step_per_chip:.0f})"
        )
    else:
        per_chip, mode = single_step_per_chip, "single_step"
        mode_reason = (
            f"single_step ({single_step_per_chip:.0f}) beat "
            f"multi_step_k{k} ({multi_step_per_chip:.0f}) on this draw"
        )
    # Tag each phase profiler with ITS OWN dispatch mode (not the
    # winner — that's parsed.mode) so journaled step_profile events and
    # the step_time block attribute timings to the loop that produced
    # them.
    prof_single.set_label("mode", "single_step")
    prof_multi.set_label("mode", f"multi_step_k{k}")

    from deeplearning_cfn_tpu.train.metrics import peak_flops_per_chip

    # On a TPU the peak is known or peak_flops_per_chip raises.
    peak = peak_flops_per_chip(devices[0])
    # cost_analysis flops are PER-DEVICE for an SPMD-partitioned module
    # (verified empirically on an 8-device mesh), so per-device flop rate
    # over per-chip peak is the per-chip MFU at any scale.
    steps_per_sec = per_chip * n_chips / batch
    mfu = flops_per_step * steps_per_sec / peak

    # Per-phase step-time breakdown (the MFU-plateau attribution): the
    # single-vs-multi-step gap must be explained by the phases — the
    # delta in per-step dispatch + host overhead is the mechanism the
    # k-step mode exists to amortize (docs/BENCH_NOTES.md); compute is
    # the same program body in both.
    snap_single = prof_single.journal()
    snap_multi = prof_multi.journal()
    gap_ms = (dt_single / MEASURE_STEPS - dt_multi / (outer * k)) * 1e3
    overhead_delta_ms = (
        snap_single["dispatch_ms"]
        + snap_single["host_ms"]
        - snap_multi["dispatch_ms"]
        - snap_multi["host_ms"]
    )
    step_time = {
        "single_step": snap_single,
        f"multi_step_k{k}": snap_multi,
        "input_pipeline": pipeline_profile,
        "gap": {
            "single_minus_multi_ms_per_step": round(gap_ms, 3),
            "dispatch_host_delta_ms_per_step": round(overhead_delta_ms, 3),
            "explained_fraction": round(overhead_delta_ms / gap_ms, 3)
            if abs(gap_ms) > 1e-6
            else None,
        },
    }
    # The overlap block is the acceptance surface for the double-buffered
    # input path: >= 2 stacks were device-resident during the timed
    # window, consumed stacks were actually freed, and the consumer's
    # data_wait stayed ~0 (the prefetcher ran ahead of compute).
    stack_snap = stack_stats.snapshot()
    overlap = {
        "steps_per_call": k,
        "stack_buffer": STACK_BUFFER,
        "device_resident_stacks_peak": resident_stacks_peak,
        "input_stack_donated_bytes": stack_donated,
        "stack_bytes_transferred": stack_snap["bytes_transferred"],
        "stack_overlap_fraction": stack_snap["overlap_fraction"],
        "data_wait_p50_ms": snap_multi.get("phases", {})
        .get("data_wait", {})
        .get("p50_ms", 0.0),
    }
    # Communication + HBM pressure per compiled program, read straight
    # off the executables' HLO/memory analysis (the other two MFU
    # killers the step-time blocks can't see — docs/STATIC_ANALYSIS.md
    # comms runbook).  Bytes are normalized per STEP so single- and
    # multi-step modes compare directly.
    from deeplearning_cfn_tpu.analysis.comms_audit import program_comms

    def comms_block(exe, steps_per_call: int) -> dict:
        c = program_comms(exe)
        return {
            "collective_count": c["collective_count"],
            "collective_bytes_per_step": c["collective_bytes"] // steps_per_call,
            "peak_hbm_bytes": c["peak_hbm_bytes"],
            # Schedule slack per collective (comms_audit.schedule_overlap)
            # — how much compute the scheduler has to hide each
            # collective behind; the DLC512-ratcheted number.
            "overlap_score": c["overlap_score"],
        }

    comms = {
        "train_step": comms_block(step_exe, 1),
        f"multi_step_k{k}": comms_block(kexe, k),
    }
    # Per-compiled-program MFU/MBU from each program's own cost model
    # and measured call time — attribution finer than whole-bench MFU.
    # "headline" marks the program the top-level value came from.
    programs = {
        "train_step": program_attribution(
            flops=stats.get("cost_flops_per_step"),
            bytes_accessed=stats.get("bytes_accessed"),
            seconds_per_call=dt_single / MEASURE_STEPS,
            steps_per_call=1,
            peak_flops=peak,
        ),
        f"multi_step_k{k}": program_attribution(
            flops=kcost["flops"],
            bytes_accessed=kcost["bytes_accessed"],
            seconds_per_call=dt_multi / outer,
            steps_per_call=k,
            peak_flops=peak,
        ),
    }
    programs["train_step"]["headline"] = mode == "single_step"
    programs[f"multi_step_k{k}"]["headline"] = mode == f"multi_step_k{k}"
    print(
        json.dumps(
            {
                "metric": "resnet50_synthetic_images_per_sec_per_chip",
                "value": round(per_chip, 2),
                "unit": "images/sec/chip",
                "vs_baseline": round(per_chip / REFERENCE_IMAGES_PER_SEC_PER_DEVICE, 3),
                "mfu": round(mfu, 4),
                "mode": mode,
                "mode_reason": mode_reason,
                # What fed the step loop: "synthetic" (in-memory generated
                # batches) vs "records" (the train/datastream DLC1 shard
                # path).  Throughput numbers are only comparable within
                # one input mode — bench_compare refuses to diff across
                # them.
                "input_mode": "synthetic",
                "transfer_dtype": "uint8",
                "single_step_images_per_sec_per_chip": round(
                    single_step_per_chip, 2
                ),
                "multi_step_images_per_sec_per_chip": round(
                    multi_step_per_chip, 2
                ),
                "input_pipeline": pipeline,
                "overlap": overlap,
                "quantized": quantized,
                "step_time": step_time,
                "programs": programs,
                # Compile-behavior correlates for the MFU trajectory
                # (ISSUE 7): total XLA compiles this run, compiles beyond
                # the first per function (0 = steady-state zero-retrace),
                # and state bytes the step actually donated.
                "compile_count": watcher.compile_count,
                "retrace_count": watcher.retrace_count,
                "donated_bytes": donation.donated_bytes,
                "comms": comms,
                "flops_per_step": flops_per_step,
                "device_kind": devices[0].device_kind,
                "n_chips": n_chips,
            },
            allow_nan=False,
        )
    )


if __name__ == "__main__":
    main()
