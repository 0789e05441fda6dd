"""Perf-smoke gate: compact-dtype input path + profiler overhead/sentinel.

Runs a tiny CPU pipeline microbench — the same uint8 synthetic stream a
real bench uses, through ``DevicePrefetcher(workers=2)`` with counters —
against a float32 baseline of identical shape, and asserts structural
properties (byte counts, batch counts, dtype preservation).  Wall-clock
is asserted only as RATIOS with wide margins (never absolute CI-machine
speed): the StepProfiler overhead guard compares an instrumented loop
against a bare one around a step big enough (~ms) that the <2% budget
is ~30x the profiler's actual per-step cost, median-of-3 to shrug off
scheduler noise; the step-time regression sentinel asserts ordering
(p99 >= p50) and a deliberately loose absolute ceiling.
docs/PERFORMANCE.md covers how to read the timing counters it prints.
A serving-plane scheduler stage, a 1k-agent broker-failover soak (both
on virtual clocks, structural asserts only), a fleet-telemetry payload
cost check (TELEM snapshots stay O(entries) with summaries truncated at
the wire cap), an input-overlap stage (double-buffered stacked batches
stay >= 2 deep on device, consumed stacks are freed by donate_buffers,
and the consumer holds its single post-warmup compile), a datastream
stage (per-host shard assignment is an exact partition, one epoch reads
every record exactly once, and the async sharded checkpointer's save()
provably never blocks a step — its writer is parked on a gate while the
step path keeps enqueuing), a fleet-scheduler stage (placement is a
deterministic pure function under permuted submission, quota invariants
hold, and the sched package never reads the wall clock), and an
exact-match check of the audited train step's collective bytes against
the committed comms budget (8-virtual-device runs only) ride along,
plus a comms-overlap stage (the bucketed gradient-sync program's
bucket byte accounting sums exactly to the grad tree, and the overlap
step holds zero steady-state retraces).

Exit 0 and one JSON line on success; exit 1 with a message on violation.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np


BATCH = 8
IMAGE = 32
STEPS = 6
WORKERS = 2


def run_pipeline(dtype: str) -> tuple[dict, object]:
    from deeplearning_cfn_tpu.train.data import DevicePrefetcher, SyntheticDataset
    from deeplearning_cfn_tpu.train.pipeline import PipelineStats

    ds = SyntheticDataset(
        shape=(IMAGE, IMAGE, 3),
        num_classes=10,
        batch_size=BATCH,
        dtype=dtype,
        pool_batches=3,
    )
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    stats = PipelineStats(name=f"smoke-{dtype}")
    prefetcher = DevicePrefetcher(
        ds.batches(STEPS), sharding, size=2, workers=WORKERS, stats=stats
    )
    last_x = None
    n = 0
    try:
        for batch in prefetcher:
            last_x = batch.x
            n += 1
    finally:
        prefetcher.close()
    assert n == STEPS, f"{dtype}: consumed {n} batches, expected {STEPS}"
    return stats.snapshot(), last_x


PROFILE_STEPS = 30
PROFILE_REPEATS = 3
OVERHEAD_BUDGET = 0.02  # enabling the profiler may cost <2% of step time


def profiler_overhead() -> dict:
    """Measure StepProfiler cost against a bare loop over a jitted step.

    The step (1024x1024 matmul) runs ~1 ms on CPU, so the 2% budget is
    tens of microseconds against the profiler's ~1-2 us of bookkeeping —
    a wide structural margin, not a tight wall-clock bet.  Median of
    three interleaved repeats absorbs scheduler noise.  Also returns the
    profiler's snapshot for the step-time regression sentinel.
    """
    import time

    from deeplearning_cfn_tpu.obs.profiler import StepProfiler

    @jax.jit
    def step(a):
        return a @ a

    a = jnp.ones((1024, 1024), jnp.float32)
    step(a).block_until_ready()  # compile outside every timed window

    def bare_loop() -> float:
        t0 = time.perf_counter()
        out = a
        for _ in range(PROFILE_STEPS):
            out = step(out)
        out.block_until_ready()
        return time.perf_counter() - t0

    def profiled_loop(prof: StepProfiler) -> float:
        t0 = time.perf_counter()
        out = a
        prof.start()
        for i in range(PROFILE_STEPS):
            with prof.phase("dispatch"):
                out = step(out)
            prof.step_done(step=i)
        with prof.sync_boundary(PROFILE_STEPS):
            out.block_until_ready()
        return time.perf_counter() - t0

    bare, profiled = [], []
    prof = StepProfiler(name="perf_smoke")
    for _ in range(PROFILE_REPEATS):
        bare.append(bare_loop())
        profiled.append(profiled_loop(prof))
    bare_s = sorted(bare)[len(bare) // 2]
    profiled_s = sorted(profiled)[len(profiled) // 2]
    return {
        "bare_s": round(bare_s, 6),
        "profiled_s": round(profiled_s, 6),
        "overhead_fraction": round(profiled_s / bare_s - 1.0, 6),
        "snapshot": prof.snapshot(),
    }


SERVE_REQUESTS = 40
SERVE_STARVATION_BOUND = 80  # scheduler steps a queued request may wait


def serve_scheduler() -> tuple[dict, list[str]]:
    """Serving-plane scheduler stage: structural asserts only, no
    wall-clock.  Drives seeded mixed-length traffic through one
    continuous-batching engine on a virtual clock and checks the
    scheduler's contracts: occupancy never exceeds the slot count, FIFO
    admission never starves a request beyond a generous step bound, every
    accepted request completes, and the decode path stays on its single
    post-warmup compile (the DLC410 property, observed live)."""
    import dataclasses

    from deeplearning_cfn_tpu.analysis.compile_audit import CompileWatcher
    from deeplearning_cfn_tpu.analysis.schedules import VirtualClock
    from deeplearning_cfn_tpu.models.llama import LlamaConfig, init_params
    from deeplearning_cfn_tpu.serve import (
        ContinuousBatchingEngine,
        ServeConfig,
        ServeRequest,
        TrafficConfig,
        run_load,
    )

    failures: list[str] = []
    cfg = dataclasses.replace(
        LlamaConfig.tiny(vocab_size=64, seq_len=64), dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    scfg = ServeConfig(
        num_slots=4, block_size=4, blocks_per_slot=8, prefill_len=16
    )
    clock = VirtualClock()
    engine = ContinuousBatchingEngine(
        cfg, params, scfg, clock=clock, journal=False
    )
    # Warmup: one request compiles the prefill and decode executables.
    engine.submit(ServeRequest("warm", np.array([1, 2, 3], np.int32), 4))
    while engine.pending():
        engine.step()

    occupancy_ok = True

    def watch_occupancy(_step: int) -> None:
        nonlocal occupancy_ok
        occupancy_ok = occupancy_ok and engine.active_slots <= scfg.num_slots

    with CompileWatcher() as watcher:
        watcher.mark_steady()
        report = run_load(
            engine,
            TrafficConfig(requests=SERVE_REQUESTS, seed=0),
            clock,
            on_step=watch_occupancy,
        )
        retraces = watcher.new_compiles_since_mark()
    snap = engine.snapshot()
    if report.completed != SERVE_REQUESTS:
        failures.append(
            f"serve scheduler lost requests: {report.completed}/{SERVE_REQUESTS}"
        )
    if not occupancy_ok:
        failures.append(
            f"serve scheduler overfilled its {scfg.num_slots} slots"
        )
    if snap["max_wait_steps"] > SERVE_STARVATION_BOUND:
        failures.append(
            f"serve scheduler starved a request for {snap['max_wait_steps']} "
            f"steps (bound {SERVE_STARVATION_BOUND})"
        )
    if retraces:
        failures.append(
            f"serve decode retraced after warmup: {sorted(retraces)}"
        )
    return {
        "requests": SERVE_REQUESTS,
        "completed": report.completed,
        "steps": report.steps,
        "max_wait_steps": snap["max_wait_steps"],
        "recycled_blocks": snap["recycled_blocks"],
        "post_warmup_compiles": len(retraces),
    }, failures


def comms_budget() -> tuple[dict, list[str]]:
    """Comms-budget stage: the audited fsdp train step's collective
    bytes must match scripts/comms_budget.json EXACTLY — not a ceiling.

    The audit is pure lower+compile of a fixed program on a fixed mesh,
    so its HLO (and therefore its collective inventory) is
    deterministic; any drift in either direction means the partitioner
    output changed and the budget must be consciously re-measured
    (scripts/comms_audit.py --write-budget).  Needs the 8 virtual
    devices check.sh provides; skipped structurally elsewhere so a bare
    `python scripts/perf_smoke.py` still runs."""
    from deeplearning_cfn_tpu.analysis.comms_audit import (
        load_budget,
        run_comms_audit,
    )

    failures: list[str] = []
    budget = load_budget()
    if budget is None:
        return {"skipped": "no committed budget"}, failures
    if jax.device_count() != int(budget.get("device_count", -1)):
        return {
            "skipped": f"device_count {jax.device_count()} != "
            f"budget's {budget.get('device_count')}"
        }, failures
    report = run_comms_audit(journal=False, budget_path=None, serve=False)
    committed = budget.get("programs", {}).get("train_step", {})
    measured = next(
        (p for p in report.programs if p.name == "train_step"), None
    )
    if measured is None:
        failures.append("comms audit produced no train_step program")
        return {}, failures
    if measured.collective_bytes != int(committed.get("collective_bytes", -1)):
        failures.append(
            f"train_step collective_bytes {measured.collective_bytes} != "
            f"committed {committed.get('collective_bytes')} "
            "(scripts/comms_budget.json; re-measure deliberately with "
            "scripts/comms_audit.py --write-budget)"
        )
    return {
        "train_step": measured.budget,
        "committed": committed,
    }, failures


OVERLAP_BUCKET_BYTES = 32 * 1024


def comms_overlap() -> tuple[dict, list[str]]:
    """Comms-overlap stage: the bucketed gradient-sync engine
    (parallel/overlap.py), checked structurally on the 8-device virtual
    mesh:

    (1) the bucket plan's byte accounting sums exactly to the gradient
        tree — every leaf lands in exactly one bucket, nothing double-
        synced or dropped;
    (2) the overlap step compiles once and never again across
        steady-state steps (zero retraces under ``CompileWatcher`` —
        the trace-time bucket planning must be compile-stable).

    Both programs' audited ``overlap_score`` are reported, not gated:
    whether the bucketed schedule beats the monolithic one is DLC512's
    pair invariant (scripts/comms_audit.py), and on HLO the installed
    compiler lowers for the CPU it does not — one fused all-reduce
    either way; the finding is carried in scripts/lint_baseline.json
    until chips decide the engine's fate (ROADMAP S8)."""
    from deeplearning_cfn_tpu.analysis.comms_audit import (
        AUDIT_BATCH_SIZE,
        AUDIT_CLASSES,
        AUDIT_INPUT_SHAPE,
        _audit_model,
        program_comms,
    )
    from deeplearning_cfn_tpu.analysis.compile_audit import CompileWatcher
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.parallel.overlap import plan_buckets
    from deeplearning_cfn_tpu.train.data import SyntheticDataset
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    failures: list[str] = []
    if jax.device_count() < 8:
        return {
            "skipped": f"needs 8 virtual devices, have {jax.device_count()}"
        }, failures
    mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
    ds = SyntheticDataset(
        shape=AUDIT_INPUT_SHAPE,
        num_classes=AUDIT_CLASSES,
        batch_size=AUDIT_BATCH_SIZE,
        seed=0,
    )
    sample = next(iter(ds.batches(1)))
    kwargs = dict(learning_rate=0.05, optimizer="sgd", strategy="dp")
    mono = Trainer(_audit_model(), mesh, TrainerConfig(**kwargs))
    bucketed = Trainer(
        _audit_model(),
        mesh,
        TrainerConfig(
            comms_overlap=True,
            overlap_bucket_bytes=OVERLAP_BUCKET_BYTES,
            **kwargs,
        ),
    )
    with jax.set_mesh(mesh):
        mono_state = mono.init(jax.random.PRNGKey(0), sample.x)
        mono_score = program_comms(
            mono.step_fn.lower(mono_state, sample.x, sample.y).compile()
        )["overlap_score"]
        with CompileWatcher() as watcher:
            state = bucketed.init(jax.random.PRNGKey(0), sample.x)
            bucket_score = program_comms(
                bucketed.step_fn.lower(state, sample.x, sample.y).compile()
            )["overlap_score"]
            state, metrics = bucketed.train_step(state, sample.x, sample.y)
            jax.block_until_ready(metrics["loss"])
            watcher.mark_steady()
            for _ in range(3):
                state, metrics = bucketed.train_step(
                    state, sample.x, sample.y
                )
            jax.block_until_ready(metrics["loss"])
            retraces = watcher.new_compiles_since_mark()
    specs = jax.tree_util.tree_map(
        lambda s: s.spec, bucketed.state_shardings.params
    )
    plan = plan_buckets(state.params, specs, OVERLAP_BUCKET_BYTES)
    leaves = jax.tree_util.tree_leaves(state.params)
    tree_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
    if plan.total_bytes != tree_bytes:
        failures.append(
            f"bucket byte accounting {plan.total_bytes} != grad tree "
            f"{tree_bytes} — a leaf was dropped or double-bucketed"
        )
    bucketed_leaves = sum(len(b.indices) for b in plan.buckets)
    if bucketed_leaves != len(leaves):
        failures.append(
            f"bucket plan covers {bucketed_leaves} leaves of {len(leaves)}"
        )
    if retraces:
        failures.append(
            f"overlap step recompiled after warmup: {sorted(retraces)}"
        )
    return {
        "monolithic_overlap_score": mono_score,
        "bucketed_overlap_score": bucket_score,
        "buckets": len(plan.buckets),
        "bucket_bytes": plan.total_bytes,
        "post_warmup_compiles": len(retraces),
    }, failures


TELEM_GAUGES = 12
TELEM_OVERSIZE_SAMPLES = 4096


def telemetry_overhead() -> tuple[dict, list[str]]:
    """Fleet-telemetry stage: structural asserts only, no wall-clock.
    The TELEM payload rides the heartbeat path, so its cost model must
    hold by construction: the encoded snapshot carries exactly the
    gauges handed in (no hidden amplification), summary samples are
    truncated to MAX_SUMMARY_SAMPLES regardless of how many the caller
    accumulated, non-finite values serialize as null (never a parse
    error at the controller), and payload size is O(entries) — bounded
    by a per-entry budget, not proportional to run length."""
    from deeplearning_cfn_tpu.obs.aggregator import (
        MAX_SUMMARY_SAMPLES,
        FleetAggregator,
        agent_snapshot,
        decode_snapshot,
        encode_snapshot,
    )

    failures: list[str] = []
    gauges = {f"dlcfn_fleet_gauge_probe_{i}": float(i) for i in range(TELEM_GAUGES)}
    gauges["dlcfn_serve_tokens_per_s"] = float("nan")
    payload = encode_snapshot(
        agent_snapshot(
            gauges=gauges,
            summaries={"dlcfn_step_ms": [float(i) for i in range(TELEM_OVERSIZE_SAMPLES)]},
        )
    )
    body = decode_snapshot(payload)
    if body is None:
        failures.append("telemetry snapshot failed to round-trip")
        return {}, failures
    if len(body["gauges"]) != len(gauges):
        failures.append(
            f"telemetry gauge count amplified: {len(body['gauges'])} != {len(gauges)}"
        )
    if body["gauges"]["dlcfn_serve_tokens_per_s"] is not None:
        failures.append("non-finite gauge escaped json_safe onto the wire")
    shipped = len(body["summaries"]["dlcfn_step_ms"])
    if shipped != MAX_SUMMARY_SAMPLES:
        failures.append(
            f"summary samples not truncated: shipped {shipped}, "
            f"cap {MAX_SUMMARY_SAMPLES}"
        )
    # O(entries) bound: generous per-entry byte budget (name + float +
    # JSON punctuation), independent of the 4096 samples accumulated.
    entries = len(gauges) + MAX_SUMMARY_SAMPLES
    budget = 64 * entries + 256
    if len(payload) > budget:
        failures.append(
            f"telemetry payload {len(payload)}B over the structural "
            f"budget {budget}B for {entries} entries"
        )
    # The controller-side merge stays a pure fold of its input table.
    agg = FleetAggregator().merge({"g/0": (1.0, 1, payload), "g/1": (1.0, 1, payload)})
    if agg["hosts"] != 2 or agg["summaries"]["dlcfn_step_ms"]["count"] != 2 * MAX_SUMMARY_SAMPLES:
        failures.append("fleet merge dropped or duplicated snapshot samples")
    return {
        "gauges": len(gauges),
        "samples_shipped": shipped,
        "samples_accumulated": TELEM_OVERSIZE_SAMPLES,
        "payload_bytes": len(payload),
        "payload_budget_bytes": budget,
    }, failures


OVERLAP_K = 2        # batches per stacked multi-step call
OVERLAP_CALLS = 5    # stacks consumed by the stage
OVERLAP_BUFFER = 2   # DevicePrefetcher depth — the double buffer


def input_overlap() -> tuple[dict, list[str]]:
    """Overlap-architecture stage: structural asserts only, no wall-clock.

    Drives stacked uint8 batches through ``DevicePrefetcher`` exactly the
    way ``Trainer._fit_multi`` and the bench multi-step phase do, and
    checks the three properties docs/PERFORMANCE.md's overlap section
    promises: (1) the prefetcher keeps >= 2 batches device-resident
    while one is being consumed (double buffering, observed via
    ``buffered()``); (2) every consumed stack's leaves are actually
    freed by ``donate_buffers`` (``is_deleted``) — the explicit-delete
    stand-in for donation on input stacks; (3) the consuming program
    compiles once and never again across the remaining same-shape calls
    (zero post-warmup compiles)."""
    import time

    from deeplearning_cfn_tpu.analysis.compile_audit import CompileWatcher
    from deeplearning_cfn_tpu.train.data import (
        DevicePrefetcher,
        SyntheticDataset,
        donate_buffers,
        stack_batches,
    )

    failures: list[str] = []
    ds = SyntheticDataset(
        shape=(IMAGE, IMAGE, 3), num_classes=10, batch_size=BATCH, dtype="uint8"
    )
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    @jax.jit
    def consume(xs, ys):
        return jnp.sum(xs.astype(jnp.float32)) + jnp.sum(ys)

    stacks = stack_batches(ds.batches(OVERLAP_CALLS * OVERLAP_K), OVERLAP_K)
    prefetcher = DevicePrefetcher(
        stacks, sharding, size=OVERLAP_BUFFER, workers=WORKERS
    )
    peak_resident = 0
    donated_bytes = 0
    calls = 0
    out = None
    try:
        with CompileWatcher() as watcher:
            for i, stack in enumerate(prefetcher):
                if i == 0:
                    # Let the producer refill behind the in-hand stack so
                    # the double buffer is observable, then freeze the
                    # compile ledger: everything past this call is steady
                    # state.
                    deadline = time.monotonic() + 10.0
                    while (
                        len(prefetcher.buffered()) < OVERLAP_BUFFER
                        and time.monotonic() < deadline
                    ):
                        time.sleep(0.001)
                peak_resident = max(peak_resident, 1 + len(prefetcher.buffered()))
                out = consume(stack.x, stack.y)
                if i == 0:
                    out.block_until_ready()
                    watcher.mark_steady()
                # Explicit free of the consumed stack — deletion after
                # dispatch is safe (the running program holds its own
                # reference) and is what keeps k-deep stacks from
                # accumulating in HBM.
                donated_bytes += donate_buffers((stack.x, stack.y))
                if not (stack.x.is_deleted() and stack.y.is_deleted()):
                    failures.append(
                        "consumed stack leaves survive donate_buffers "
                        "(is_deleted False) — stacks would accumulate in HBM"
                    )
                calls += 1
            out.block_until_ready()
            retraces = watcher.new_compiles_since_mark()
    finally:
        prefetcher.close()
    if calls != OVERLAP_CALLS:
        failures.append(
            f"overlap stage consumed {calls} stacks, expected {OVERLAP_CALLS}"
        )
    if peak_resident < 2:
        failures.append(
            f"prefetcher never held 2 device-resident stacks "
            f"(peak {peak_resident}) — no overlap to hide transfers behind"
        )
    if retraces:
        failures.append(
            f"overlap consumer recompiled after warmup: {sorted(retraces)}"
        )
    expected_stack_bytes = OVERLAP_CALLS * OVERLAP_K * BATCH * (
        IMAGE * IMAGE * 3 + 4
    )
    if donated_bytes != expected_stack_bytes:
        failures.append(
            f"donated bytes {donated_bytes} != expected {expected_stack_bytes} "
            "(uint8 images + int32 labels across every consumed stack)"
        )
    return {
        "steps_per_call": OVERLAP_K,
        "calls": calls,
        "device_resident_stacks_peak": peak_resident,
        "donated_bytes": donated_bytes,
        "post_warmup_compiles": len(retraces),
    }, failures


DATASTREAM_SHARDS = 4
DATASTREAM_HOSTS = ("host-a", "host-b")


def datastream() -> tuple[dict, list[str]]:
    """Data-plane stage: structural asserts only, no wall-clock.

    Checks the three contracts docs/DATA.md promises: (1) the per-host
    shard assignment is an exact partition of the shard set for every
    epoch probed; (2) draining one epoch across all hosts reads every
    record exactly once (record ids are baked into the shards, so the
    claim is literally ``sorted(seen) == range(total)``); (3) the async
    sharded checkpointer never blocks a step — proven by construction,
    not by timing: the writer is parked on a threading.Event while the
    step path keeps enqueuing, so zero bytes can land while the gate is
    closed, latest-wins supersedes the middle save, and releasing the
    gate commits exactly the first-picked and last-enqueued steps."""
    import shutil
    import tempfile
    import threading

    from deeplearning_cfn_tpu.train.datastream import (
        AsyncShardedCheckpointer,
        HostShardStream,
        assign_shards,
    )
    from deeplearning_cfn_tpu.train.records import (
        Field,
        RecordSpec,
        write_records,
    )

    failures: list[str] = []
    for epoch in range(3):
        assigned = assign_shards(
            DATASTREAM_HOSTS, DATASTREAM_SHARDS, seed=7, epoch=epoch
        )
        flat = sorted(s for w in assigned.values() for s in w)
        if flat != list(range(DATASTREAM_SHARDS)):
            failures.append(
                f"epoch {epoch}: shard assignment is not an exact "
                f"partition: {assigned}"
            )

    spec = RecordSpec((Field("x", "uint8", (1,)), Field("y", "int32", ())))
    root = Path(tempfile.mkdtemp(prefix="dlcfn-perf-datastream-"))
    try:
        gid = 0
        paths = []
        for sid in range(DATASTREAM_SHARDS):
            recs = []
            for _ in range(11 + sid):  # uneven on purpose
                recs.append(
                    spec.encode(
                        x=np.array([gid % 251], np.uint8), y=np.int32(gid)
                    )
                )
                gid += 1
            p = root / f"shard-{sid}.dlc"
            write_records(p, spec, recs)
            paths.append(p)
        seen: list[int] = []
        for host in DATASTREAM_HOSTS:
            stream = HostShardStream(
                paths,
                spec,
                batch_size=4,
                host=host,
                hosts=DATASTREAM_HOSTS,
                seed=7,
                loop=False,
            )
            for b in stream.batches():
                seen.extend(int(v) for v in b.y)
        if sorted(seen) != list(range(gid)):
            failures.append(
                f"epoch drain not exactly-once: {len(seen)} reads of "
                f"{gid} records"
            )

        class _GatedDisk:
            """CheckpointIO-compatible; every write parks on a gate, so
            the step path demonstrably runs ahead of the writer."""

            def __init__(self):
                self.entered = threading.Event()
                self.release = threading.Event()

            def write_bytes(self, path, data):
                self.entered.set()
                if not self.release.wait(timeout=30):
                    raise OSError("gate never released")
                Path(path).write_bytes(data)

            def replace(self, src, dst):
                import os

                os.replace(src, dst)

            def read_bytes(self, path):
                return Path(path).read_bytes()

        disk = _GatedDisk()
        state = {"w": np.arange(8, dtype=np.float32)}
        ck = AsyncShardedCheckpointer(
            root / "ckpt", every_steps=1, n_shards=2, io=disk
        )
        ck.save(1, state, stream_state={"host": "host-a", "cursor": 1})
        if not disk.entered.wait(timeout=30):
            failures.append("async writer never started after save()")
        # The step path is HERE, running, while the writer is parked on
        # the gate: save() returned with zero bytes on disk.
        if list((root / "ckpt").glob("ckpt-*.manifest.json")):
            failures.append(
                "a manifest landed while the writer was gated — "
                "save() blocked on IO"
            )
        ck.save(2, {"w": state["w"] + 1})
        ck.save(3, {"w": state["w"] + 2})
        if ck.superseded_total != 1:
            failures.append(
                f"latest-wins supersede count {ck.superseded_total} != 1 "
                "(step 2 should yield to step 3)"
            )
        disk.release.set()
        ck.wait(timeout_s=60)
        steps = ck.steps()
        if steps != [1, 3]:
            failures.append(
                f"committed steps {steps} != [1, 3] "
                "(first-picked + last-enqueued)"
            )
        restored = ck.restore_latest()
        if restored is None or restored[1] != 3:
            failures.append(
                "restore_latest did not return the last committed step"
            )
        ck.close()
        return {
            "shards": DATASTREAM_SHARDS,
            "hosts": len(DATASTREAM_HOSTS),
            "records": gid,
            "epoch_reads": len(seen),
            "superseded": ck.superseded_total,
            "committed_steps": steps,
        }, failures
    finally:
        shutil.rmtree(root, ignore_errors=True)


BROKER_SOAK_AGENTS = 1000
BROKER_SOAK_SENDERS = 100


def broker_soak() -> tuple[dict, list[str]]:
    """Control-plane failover stage: structural asserts only, no
    wall-clock.  Runs the 1k-agent warm-standby soak on a virtual clock
    (primary killed mid-term, standby promoted, clients blind-re-send)
    and checks the control plane's contracts: every killed agent's
    INSTANCE_TERMINATE fires exactly once across the failover, the
    idempotent re-send storm lands exactly-once, the promoted standby
    replays every shipped journal entry, and no write was fenced in a
    clean (single-partition) failover."""
    from deeplearning_cfn_tpu.analysis.schedules import soak_failover

    failures: list[str] = []
    soak = soak_failover(agents=BROKER_SOAK_AGENTS, seed=0)
    if soak["lost_terminates"]:
        failures.append(
            f"broker failover lost {soak['lost_terminates']} "
            f"INSTANCE_TERMINATE events"
        )
    for kind in ("spurious", "duplicate", "premature"):
        if soak[f"{kind}_terminates"]:
            failures.append(
                f"broker failover produced {soak[f'{kind}_terminates']} "
                f"{kind} terminates"
            )
    if soak["duplicate_sends"] or soak["work_depth"] != BROKER_SOAK_SENDERS:
        failures.append(
            f"idempotent re-send not exactly-once: depth "
            f"{soak['work_depth']}/{BROKER_SOAK_SENDERS}, "
            f"{soak['duplicate_sends']} duplicates"
        )
    # Bounded replay lag: the promoted standby holds every entry the
    # primary shipped before dying — journaled minus replayed is exactly
    # the tail the kill left unshipped, never more.
    if soak["replayed_seq"] != soak["journaled_seq"] - soak["unshipped_at_kill"]:
        failures.append(
            f"standby replay lag unbounded: replayed {soak['replayed_seq']} "
            f"of {soak['journaled_seq']} journaled "
            f"({soak['unshipped_at_kill']} unshipped at kill)"
        )
    if soak["fenced_writes"]:
        failures.append(
            f"clean failover fenced {soak['fenced_writes']} writes"
        )
    if soak["client_failovers"] != BROKER_SOAK_SENDERS:
        failures.append(
            f"client failover count {soak['client_failovers']} != "
            f"{BROKER_SOAK_SENDERS} senders"
        )
    return soak, failures


FLEET_SIM_AGENTS = 10_000
FLEET_SIM_SHARDS = 8


def fleet_sim() -> tuple[dict, list[str]]:
    """Sharded-fleet stage: the 10k-agent deterministic soak, wall-clock
    bounded.  Runs :func:`soak_fleet` twice at the same seed on a
    VirtualClock — concurrent multi-shard failovers, a split brain,
    auto-re-provision races — and checks (1) exactly-once delivery and
    zero lost/spurious INSTANCE_TERMINATE at 10k agents, (2) no shard
    pair left degraded, (3) byte-determinism: both runs serialize to
    identical JSON, and (4) the hot loop never touches ``time.sleep`` —
    all waiting is virtual, so the stage's cost is CPU, not wall
    clock."""
    import time as _time

    from deeplearning_cfn_tpu.analysis.schedules import soak_fleet

    failures: list[str] = []
    sleep_calls = 0
    real_sleep = _time.sleep

    def counting_sleep(seconds: float) -> None:
        nonlocal sleep_calls
        sleep_calls += 1
        real_sleep(seconds)

    _time.sleep = counting_sleep
    try:
        first = soak_fleet(agents=FLEET_SIM_AGENTS, shards=FLEET_SIM_SHARDS, seed=0)
        second = soak_fleet(agents=FLEET_SIM_AGENTS, shards=FLEET_SIM_SHARDS, seed=0)
    finally:
        _time.sleep = real_sleep
    if sleep_calls:
        failures.append(
            f"fleet sim hot loop slept {sleep_calls} time(s) — the soak "
            f"must wait on the VirtualClock only"
        )
    serialized = json.dumps(first, sort_keys=True, allow_nan=False)
    if serialized != json.dumps(second, sort_keys=True, allow_nan=False):
        diff = {
            k for k in set(first) | set(second) if first.get(k) != second.get(k)
        }
        failures.append(
            f"fleet sim not byte-deterministic at seed 0: fields {sorted(diff)}"
        )
    if first["lost_terminates"] or first["terminated"] != first["killed"]:
        failures.append(
            f"fleet sim lost terminates: {first['terminated']} of "
            f"{first['killed']} killed agents terminated "
            f"({first['lost_terminates']} lost)"
        )
    for kind in ("spurious", "duplicate", "premature"):
        if first[f"{kind}_terminates"]:
            failures.append(
                f"fleet sim produced {first[f'{kind}_terminates']} "
                f"{kind} terminates"
            )
    expected = first["senders"] + first["stale_writes"]
    if first["duplicate_sends"] or first["delivered"] != expected:
        failures.append(
            f"fleet sim delivery not exactly-once: {first['delivered']} "
            f"delivered of {expected} sent, "
            f"{first['duplicate_sends']} duplicates"
        )
    if first["degraded_pairs"]:
        failures.append(
            f"fleet sim left {first['degraded_pairs']} shard pair(s) "
            f"degraded after auto-heal"
        )
    if first["diverged_entries"]:
        failures.append(
            f"split-brain shard diverged by {first['diverged_entries']} "
            f"entries past the fence"
        )
    if first["unaffected_shard_failovers"]:
        failures.append(
            f"failovers leaked across shards: {first['unaffected_shard_failovers']} "
            f"client failovers on healthy shards"
        )
    return first, failures


DETERMINISM_SCENARIO = "silent-death"
DETERMINISM_SOAK_AGENTS = 200
DETERMINISM_WALL_BUDGET_S = 120.0


def determinism() -> tuple[dict, list[str]]:
    """Replay-determinism stage: the DLC610 sentinel's mechanics, smoke-
    sized.  Double-runs one chaos scenario plus a scaled-down
    ``soak_failover`` through :mod:`analysis.replay_audit` and checks
    (1) both double-runs are byte-identical, (2) the double run never
    touches ``time.sleep`` — scenarios and soaks wait on virtual clocks
    only, so replaying them twice costs CPU, not wall clock — and
    (3) wall time stays inside DETERMINISM_WALL_BUDGET_S.  The full
    sweep over every scenario and both soaks is scripts/replay_audit.py;
    this stage pins the sentinel's cost model."""
    import time as _time

    from deeplearning_cfn_tpu.analysis.replay_audit import (
        ReplayCase,
        default_cases,
        run_replay_audit,
    )
    from deeplearning_cfn_tpu.analysis.schedules import soak_failover

    failures: list[str] = []
    sleep_calls = 0
    real_sleep = _time.sleep

    def counting_sleep(seconds: float) -> None:
        nonlocal sleep_calls
        sleep_calls += 1
        real_sleep(seconds)

    cases = default_cases(scenarios=[DETERMINISM_SCENARIO], soaks=False)
    cases.append(
        ReplayCase(
            name="soak_failover_smoke",
            kind="soak",
            run=lambda seed: soak_failover(
                agents=DETERMINISM_SOAK_AGENTS,
                seed=seed,
                kill_count=10,
                senders=20,
                unshipped_tail=5,
            ),
            audited_file="scripts/perf_smoke.py",
        )
    )
    start = _time.monotonic()
    _time.sleep = counting_sleep
    try:
        report = run_replay_audit(cases=cases, journal=False)
    finally:
        _time.sleep = real_sleep
    wall_s = round(_time.monotonic() - start, 3)
    for replay in report.replays:
        if not replay.identical:
            failures.append(
                f"determinism stage: {replay.kind} '{replay.name}' diverged "
                f"across a same-seed double run (first divergence at "
                f"{replay.divergence})"
            )
    if sleep_calls:
        failures.append(
            f"determinism stage slept {sleep_calls} time(s) — the double "
            f"run must wait on virtual clocks only"
        )
    if wall_s > DETERMINISM_WALL_BUDGET_S:
        failures.append(
            f"determinism stage took {wall_s}s, over the "
            f"{DETERMINISM_WALL_BUDGET_S}s wall budget"
        )
    snapshot = {
        "replays": [r.to_dict() for r in report.replays],
        "sleep_calls": sleep_calls,
        "wall_s": wall_s,
    }
    return snapshot, failures


SCHED_JOBS = 6
SCHED_SLICES = 5


def sched_placer() -> tuple[dict, list[str]]:
    """Fleet-scheduler stage: structural asserts only, no wall-clock.

    Checks the placer's contracts (docs/SCHEDULER.md): (1) placement is
    a deterministic pure function — repeated calls AND permuted
    submission orders produce byte-identical placements; (2) quota
    invariants hold by verify_placement (each slice assigned at most
    once, every placed job within [min_slices, max_slices], every job
    placed or carrying a reason); (3) the sched package never touches
    the wall clock — all of its timing flows through the injected
    broker/journal seams, so decisions replay deterministically."""
    import itertools

    from deeplearning_cfn_tpu.sched import JobSpec, place, verify_placement

    failures: list[str] = []
    inventory = {f"s{i}": 4 for i in range(SCHED_SLICES)}
    jobs = [
        JobSpec(name="chat", kind="serve", priority="prod-serve"),
        JobSpec(name="train-a", kind="train", priority="prod-train",
                min_slices=1, max_slices=2),
        JobSpec(name="train-b", kind="train", priority="prod-train",
                min_slices=2, max_slices=2),
        JobSpec(name="nightly", kind="train", priority="batch",
                min_slices=1, max_slices=3),
        JobSpec(name="eval", kind="serve", priority="batch"),
        JobSpec(name="hopeless", kind="train", priority="batch",
                min_slices=SCHED_SLICES + 1, max_slices=SCHED_SLICES + 1),
    ]
    assert len(jobs) == SCHED_JOBS
    baseline = place(jobs, inventory)
    for trial, ordering in enumerate(itertools.permutations(jobs, len(jobs))):
        if trial >= 24:  # two dozen permutations is plenty of shuffle
            break
        if place(list(ordering), inventory).to_dict() != baseline.to_dict():
            failures.append(
                f"placement depends on submission order (permutation {trial})"
            )
            break
    quota_errors = verify_placement(baseline, jobs, inventory)
    failures.extend(f"quota invariant: {e}" for e in quota_errors)
    if "hopeless" not in baseline.unplaced:
        failures.append(
            "over-quota job was placed instead of explained in unplaced"
        )
    if baseline.assignments.get("chat") != ("s0",):
        failures.append(
            f"prod-serve did not get the first slice: {baseline.assignments}"
        )
    # No wall clock anywhere in the package: a sched decision must be a
    # pure function of (ledger, intents), or crash-resume cannot replay.
    sched_dir = Path(__file__).resolve().parent.parent / (
        "deeplearning_cfn_tpu/sched"
    )
    clocked = [
        p.name
        for p in sorted(sched_dir.glob("*.py"))
        if any(
            probe in p.read_text()
            for probe in ("time.time(", "time.monotonic(", "time.sleep(")
        )
    ]
    if clocked:
        failures.append(
            f"sched package touches the wall clock in {clocked} — "
            "decisions must be replayable from the ledger alone"
        )
    return {
        "jobs": SCHED_JOBS,
        "slices": SCHED_SLICES,
        "assignments": {j: list(s) for j, s in sorted(baseline.assignments.items())},
        "unplaced": dict(sorted(baseline.unplaced.items())),
        "permutations_checked": 24,
        "quota_errors": len(quota_errors),
    }, failures


def main() -> int:
    u8_snap, u8_x = run_pipeline("uint8")
    f32_snap, f32_x = run_pipeline("float32")

    failures = []
    if u8_x.dtype != jnp.uint8:
        failures.append(f"uint8 pipeline delivered {u8_x.dtype} to the device")
    if f32_x.dtype != jnp.float32:
        failures.append(f"float32 baseline delivered {f32_x.dtype}")
    if u8_snap["batches"] != STEPS or f32_snap["batches"] != STEPS:
        failures.append(
            f"batch counters diverged: u8={u8_snap['batches']} "
            f"f32={f32_snap['batches']} expected={STEPS}"
        )
    # THE gate: the compact path must move strictly fewer bytes than the
    # float32 baseline at identical shapes.  Labels (int32) are shared
    # payload, so the ratio is < 1/4 + epsilon rather than exactly 1/4.
    if not u8_snap["bytes_transferred"] < f32_snap["bytes_transferred"]:
        failures.append(
            f"compact-dtype path not taken: uint8 moved "
            f"{u8_snap['bytes_transferred']} bytes vs float32 "
            f"{f32_snap['bytes_transferred']}"
        )
    image_bytes_u8 = STEPS * BATCH * IMAGE * IMAGE * 3
    label_bytes = STEPS * BATCH * 4
    if u8_snap["bytes_transferred"] != image_bytes_u8 + label_bytes:
        failures.append(
            f"uint8 byte counter {u8_snap['bytes_transferred']} != expected "
            f"{image_bytes_u8 + label_bytes} (images + int32 labels)"
        )
    # The in-step dequantize must invert the quantization: mean of the
    # dequantized uint8 stream tracks the float stream's mean.
    from deeplearning_cfn_tpu.train.pipeline import dequantize_normalize
    from deeplearning_cfn_tpu.train.data import SyntheticDataset

    ds = SyntheticDataset(
        shape=(IMAGE, IMAGE, 3), num_classes=10, batch_size=BATCH, dtype="uint8"
    )
    mean, std = ds.input_stats
    dq = np.asarray(dequantize_normalize(jnp.asarray(u8_x), mean, std))
    if not np.isfinite(dq).all() or abs(float(dq.mean())) > 1.0:
        failures.append(f"dequantized stream off-distribution (mean {dq.mean():.3f})")

    # Profiling must be OFF by default outside bench/status paths: fit's
    # default is None (-> NULL_PROFILER), and a disabled profiler's
    # wrap_source is the identity (zero iterator indirection).
    import inspect

    from deeplearning_cfn_tpu.obs.profiler import NULL_PROFILER
    from deeplearning_cfn_tpu.train.trainer import Trainer

    if inspect.signature(Trainer.fit).parameters["profiler"].default is not None:
        failures.append("Trainer.fit profiles by default (profiler default != None)")
    probe = iter(())
    if NULL_PROFILER.wrap_source(probe) is not probe:
        failures.append("disabled profiler wraps the batch source (overhead when off)")

    # Overhead guard: enabling the profiler may cost <2% of step time.
    overhead = profiler_overhead()
    if overhead["overhead_fraction"] >= OVERHEAD_BUDGET:
        failures.append(
            f"StepProfiler overhead {overhead['overhead_fraction']:.2%} "
            f">= {OVERHEAD_BUDGET:.0%} budget "
            f"(bare {overhead['bare_s']}s vs profiled {overhead['profiled_s']}s)"
        )
    # Step-time regression sentinel: distribution shape, not raw speed —
    # quantile ordering must hold and p99 of a ~1 ms matmul step must
    # stay under a deliberately loose ceiling even on a slow runner.
    snap = overhead["snapshot"]
    p50, p99 = snap["step_ms"].get("p50"), snap["step_ms"].get("p99")
    if p50 is None or p99 is None or not (0 < p50 <= p99):
        failures.append(f"step-time quantiles malformed: p50={p50} p99={p99}")
    elif p99 > 2000.0:
        failures.append(f"step-time p99 {p99}ms blew the 2000ms sentinel bound")
    for phase in ("dispatch", "compute", "host"):
        if phase not in snap["phases"]:
            failures.append(f"profiler snapshot missing phase {phase!r}")

    overlap_snap, overlap_failures = input_overlap()
    failures.extend(overlap_failures)

    serve_snap, serve_failures = serve_scheduler()
    failures.extend(serve_failures)

    broker_snap, broker_failures = broker_soak()
    failures.extend(broker_failures)

    fleet_snap, fleet_failures = fleet_sim()
    failures.extend(fleet_failures)

    telem_snap, telem_failures = telemetry_overhead()
    failures.extend(telem_failures)

    datastream_snap, datastream_failures = datastream()
    failures.extend(datastream_failures)

    sched_snap, sched_failures = sched_placer()
    failures.extend(sched_failures)

    comms_snap, comms_failures = comms_budget()
    failures.extend(comms_failures)

    comms_overlap_snap, comms_overlap_failures = comms_overlap()
    failures.extend(comms_overlap_failures)

    det_snap, det_failures = determinism()
    failures.extend(det_failures)

    if failures:
        for f in failures:
            print(f"perf-smoke: {f}", file=sys.stderr)
        return 1
    print(
        json.dumps(
            {
                "uint8": u8_snap,
                "float32": f32_snap,
                "bytes_ratio": round(
                    u8_snap["bytes_transferred"] / f32_snap["bytes_transferred"], 4
                ),
                "workers": WORKERS,
                "profiler_overhead": {
                    k: overhead[k]
                    for k in ("bare_s", "profiled_s", "overhead_fraction")
                },
                "step_ms": snap["step_ms"],
                "overlap": overlap_snap,
                "serve": serve_snap,
                "broker_failover": broker_snap,
                "fleet_sim": fleet_snap,
                "telemetry": telem_snap,
                "datastream": datastream_snap,
                "sched": sched_snap,
                "comms": comms_snap,
                "comms_overlap": comms_overlap_snap,
                "determinism": det_snap,
            },
            allow_nan=False,
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
