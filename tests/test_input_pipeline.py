"""Device-resident input pipeline tests: compact-dtype transfer numerics,
the parallel-producer prefetcher's ordering/exception contract, on-device
augmentation determinism, and the pipeline counters.

The golden-numerics tests pin the on-device path to the host reference
(datasets.normalize_images / numpy crops): the two implementations must
never drift, or checkpoints trained on one path stop being comparable to
evals run on the other.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.train.augment import DeviceAugment
from deeplearning_cfn_tpu.train.data import (
    Batch,
    DevicePrefetcher,
    SyntheticDataset,
    device_put_tree,
)
from deeplearning_cfn_tpu.train.datasets import normalize_images
from deeplearning_cfn_tpu.train.pipeline import (
    PipelineStats,
    dequantize_normalize,
    fold_pipeline_events,
    nbytes_of,
)


def _sharding():
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


# --- compact-dtype transfer numerics ----------------------------------------


def test_device_dequantize_matches_host_normalize():
    # The jit-side dequantize_normalize and the host normalize_images are
    # the same function by contract; pin it numerically.
    rng = np.random.default_rng(0)
    x_u8 = rng.integers(0, 256, size=(4, 8, 8, 3), dtype=np.uint8)
    mean = np.array([0.485, 0.456, 0.406], np.float32)
    std = np.array([0.229, 0.224, 0.225], np.float32)
    host = normalize_images(x_u8, mean, std)
    device = np.asarray(
        jax.jit(lambda x: dequantize_normalize(x, mean, std))(jnp.asarray(x_u8))
    )
    np.testing.assert_allclose(device, host, rtol=1e-6, atol=1e-6)


def test_dequantize_passes_floats_through():
    x = jnp.ones((2, 4, 4, 3), jnp.float32) * 0.25
    out = dequantize_normalize(x, (0.5,) * 3, (0.25,) * 3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    # compute_dtype casts floats too (the one on-chip conversion).
    out16 = dequantize_normalize(x, (0.5,) * 3, (0.25,) * 3, jnp.bfloat16)
    assert out16.dtype == jnp.bfloat16


def test_synthetic_uint8_roundtrip_through_input_stats():
    # input_stats must exactly invert the dataset's affine quantization:
    # dequantized samples land back on the float samples to within the
    # uint8 rounding error in the unscaled domain (0.5/255/_U8_SCALE).
    f32 = SyntheticDataset(shape=(8, 8, 3), num_classes=5, batch_size=4)
    u8 = SyntheticDataset(shape=(8, 8, 3), num_classes=5, batch_size=4, dtype="uint8")
    bf = next(iter(f32.batches(1)))
    bu = next(iter(u8.batches(1)))
    np.testing.assert_array_equal(bf.y, bu.y)
    mean, std = u8.input_stats
    deq = np.asarray(dequantize_normalize(jnp.asarray(bu.x), mean, std))
    quant_step = 0.5 / 255.0 / u8._U8_SCALE
    clipped = np.abs(bf.x) > 3.9  # affine-map tails clip at [0, 255]
    np.testing.assert_allclose(
        deq[~clipped], bf.x[~clipped], atol=quant_step + 1e-6
    )


def test_uint8_batch_is_quarter_the_bytes():
    shape = (8, 16, 16, 3)
    u8 = np.zeros(shape, np.uint8)
    f32 = np.zeros(shape, np.float32)
    assert nbytes_of((u8,)) * 4 == nbytes_of((f32,))
    y = np.zeros((8,), np.int32)
    assert nbytes_of((u8, y)) == u8.nbytes + y.nbytes


# --- parallel-producer prefetcher -------------------------------------------


def _identifiable_batches(n):
    for i in range(n):
        yield Batch(
            x=np.full((2, 4, 4, 1), i, np.float32), y=np.full((2,), i, np.int32)
        )


@pytest.mark.parametrize("workers", [1, 4])
def test_prefetcher_preserves_source_order(workers):
    out = []
    pf = DevicePrefetcher(
        _identifiable_batches(50), _sharding(), size=3, workers=workers
    )
    for b in pf:
        out.append(int(np.asarray(b.y)[0]))
        assert float(np.asarray(b.x)[0, 0, 0, 0]) == out[-1]
    assert out == list(range(50))


@pytest.mark.parametrize("workers", [1, 4])
def test_prefetcher_raises_at_exact_position(workers):
    def failing():
        yield from _identifiable_batches(10)
        raise ValueError("decode exploded")

    pf = DevicePrefetcher(failing(), _sharding(), size=2, workers=workers)
    seen = []
    with pytest.raises(ValueError, match="decode exploded"):
        for b in pf:
            seen.append(int(np.asarray(b.y)[0]))
    # Every batch before the failure point is delivered, in order.
    assert seen == list(range(10))
    pf.close()  # must not hang after an error


@pytest.mark.parametrize("workers", [1, 4])
def test_prefetcher_transfer_failure_reaches_the_consumer(workers):
    """A device_put that fails on a producer thread (here: a batch of 2
    the 8-way mesh cannot divide; on a chip, device memory exhausted)
    must raise in the consumer — a producer dying alone leaves fit()
    waiting forever for a batch that never comes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sharding = NamedSharding(Mesh(np.array(jax.devices()[:8]), ("dp",)), P("dp"))
    pf = DevicePrefetcher(
        _identifiable_batches(10), sharding, size=2, workers=workers
    )
    caught: list[BaseException] = []

    def consume():
        try:
            for _ in pf:
                pass
        except ValueError as e:
            caught.append(e)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    t.join(timeout=30)
    try:
        assert not t.is_alive(), "consumer still waiting on a dead producer"
        assert len(caught) == 1 and "divisible by 8" in str(caught[0])
    finally:
        pf.close()


def test_prefetcher_workers_close_without_draining():
    # Abandoning a long stream mid-iteration must stop all workers.
    pf = DevicePrefetcher(
        _identifiable_batches(10_000), _sharding(), size=2, workers=4
    )
    it = iter(pf)
    for _ in range(5):
        next(it)
    pf.close()
    deadline = 5.0
    for t in pf._threads:
        t.join(timeout=deadline)
        assert not t.is_alive(), "producer thread leaked after close()"


def test_prefetcher_counts_bytes_and_batches():
    stats = PipelineStats(name="t")
    n = 8
    pf = DevicePrefetcher(
        _identifiable_batches(n), _sharding(), size=2, workers=2, stats=stats
    )
    for _ in pf:
        pass
    pf.close()
    snap = stats.snapshot()
    per_batch = 2 * 4 * 4 * 1 * 4 + 2 * 4  # float32 x + int32 y
    assert snap["batches"] == n
    assert snap["bytes_transferred"] == n * per_batch


def test_prefetcher_bounded_readahead():
    # Producers stay at most `size` batches ahead of the consumer even
    # with a worker pool.
    pulled = []

    def tracked():
        for i in range(40):
            pulled.append(i)
            yield Batch(
                x=np.zeros((1, 2, 2, 1), np.float32), y=np.zeros((1,), np.int32)
            )

    pf = DevicePrefetcher(tracked(), _sharding(), size=3, workers=4)
    it = iter(pf)
    next(it)
    # Let the pool catch up to the bound, then check it stopped there.
    import time as _time

    _time.sleep(0.3)
    # consumed 1, buffer bound 3, plus one in-flight pull per worker.
    assert len(pulled) <= 1 + 3 + 4
    pf.close()


# --- on-device augmentation --------------------------------------------------


def test_augment_deterministic_per_seed_and_step():
    x = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (8, 12, 12, 3), np.uint8)
    )
    aug = DeviceAugment(flip=True, crop=(8, 8), seed=3)
    a = np.asarray(aug(jnp.int32(7), x))
    b = np.asarray(aug(jnp.int32(7), x))
    np.testing.assert_array_equal(a, b)
    # A different step (and a different seed) must change the draw.
    c = np.asarray(aug(jnp.int32(8), x))
    d = np.asarray(DeviceAugment(flip=True, crop=(8, 8), seed=4)(jnp.int32(7), x))
    assert not np.array_equal(a, c)
    assert not np.array_equal(a, d)


def test_augment_preserves_dtype_and_shape():
    x = jnp.zeros((4, 12, 12, 3), jnp.uint8)
    out = DeviceAugment(flip=True, crop=(8, 8))(jnp.int32(0), x)
    assert out.dtype == jnp.uint8  # compact payload survives augmentation
    assert out.shape == (4, 8, 8, 3)
    xf = jnp.zeros((4, 32, 32, 3), jnp.float32)
    out = DeviceAugment(flip=True, crop=(32, 32), pad=4)(jnp.int32(0), xf)
    assert out.shape == xf.shape and out.dtype == xf.dtype


def test_augment_center_crop_matches_numpy():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, (3, 10, 14, 3), np.uint8)
    aug = DeviceAugment(crop=(6, 8), random_crop=False)
    out = np.asarray(aug(jnp.int32(0), jnp.asarray(x)))
    np.testing.assert_array_equal(out, x[:, 2:8, 3:11, :])


def test_augment_flip_flips_width_axis():
    rng = np.random.default_rng(2)
    x = rng.integers(0, 256, (64, 4, 6, 1), np.uint8)
    out = np.asarray(DeviceAugment(flip=True)(jnp.int32(0), jnp.asarray(x)))
    flipped = np.array(
        [not np.array_equal(out[i], x[i]) for i in range(len(x))]
    )
    # Every image is either untouched or exactly width-flipped...
    for i in np.nonzero(flipped)[0]:
        np.testing.assert_array_equal(out[i], x[i, :, ::-1, :])
    # ...and a 64-image coin flip yields both outcomes.
    assert 0 < flipped.sum() < len(x)


def test_augment_identity_and_validation():
    assert DeviceAugment().is_identity
    assert not DeviceAugment(flip=True).is_identity
    with pytest.raises(ValueError, match="cannot crop"):
        DeviceAugment(crop=(16, 16))(jnp.int32(0), jnp.zeros((1, 8, 8, 3)))


# --- pooled synthetic generation ---------------------------------------------


def test_pooled_batches_cycle_deterministically():
    ds = SyntheticDataset(
        shape=(6, 6, 3), num_classes=4, batch_size=8, pool_batches=3
    )
    got = list(ds.batches(7))
    assert len(got) == 7
    # Cycle: batch i repeats at i + pool size.
    np.testing.assert_array_equal(got[0].x, got[3].x)
    np.testing.assert_array_equal(got[1].y, got[4].y)
    # Distinct batches within the pool.
    assert not np.array_equal(got[0].x, got[1].x)
    # Same seed -> same pool on a fresh iterator.
    again = list(ds.batches(2))
    np.testing.assert_array_equal(got[0].x, again[0].x)


def test_pooled_uint8_pool_matches_unpooled_dtype():
    ds = SyntheticDataset(
        shape=(6, 6, 3), num_classes=4, batch_size=8, dtype="uint8", pool_batches=2
    )
    b = next(iter(ds.batches(1)))
    assert b.x.dtype == np.uint8
    assert ds.input_stats is not None


# --- trainer integration -----------------------------------------------------


def test_fit_worker_count_does_not_change_losses():
    # The reorder buffer must make worker count invisible to training:
    # identical losses at workers=1 and workers=4.
    from deeplearning_cfn_tpu.models.lenet import LeNet
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    ds = SyntheticDataset(
        shape=(28, 28, 1), num_classes=10, batch_size=32, dtype="uint8"
    )
    results = {}
    for workers in (1, 4):
        mesh = build_mesh(MeshSpec(dp=8))
        trainer = Trainer(
            LeNet(),
            mesh,
            TrainerConfig(
                strategy="dp",
                learning_rate=0.05,
                input_stats=ds.input_stats,
                augment=DeviceAugment(flip=True, seed=1),
            ),
        )
        sample = next(iter(ds.batches(1)))
        state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
        state, losses = trainer.fit(
            state, ds.batches(6), steps=6, prefetch_workers=workers
        )
        results[workers] = losses
        snap = trainer.last_pipeline_stats.snapshot()
        assert snap["batches"] == 6
        assert snap["bytes_transferred"] > 0
    np.testing.assert_allclose(results[1], results[4], rtol=1e-6)


def test_pipeline_stats_fresh_per_fit_and_counters_pinned():
    # Regression: each fit() must bind a FRESH PipelineStats — a second
    # fit on the same trainer reporting accumulated counters (12 batches
    # after 6+6) would wreck the journal fold's per-run averages.  Pin
    # the exact totals for both a serial and a parallel producer pool.
    from deeplearning_cfn_tpu.models.lenet import LeNet
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    ds = SyntheticDataset(
        shape=(28, 28, 1), num_classes=10, batch_size=32, dtype="uint8"
    )
    mesh = build_mesh(MeshSpec(dp=8))
    trainer = Trainer(
        LeNet(),
        mesh,
        TrainerConfig(strategy="dp", learning_rate=0.05, input_stats=ds.input_stats),
    )
    sample = next(iter(ds.batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    per_run_bytes = 6 * nbytes_of((sample.x, sample.y))
    snaps = []
    stats_objects = []
    for workers in (1, 2):
        state, _ = trainer.fit(state, ds.batches(6), steps=6, prefetch_workers=workers)
        stats_objects.append(trainer.last_pipeline_stats)
        snap = trainer.last_pipeline_stats.snapshot()
        snaps.append(snap)
        assert snap["batches"] == 6, f"workers={workers}: {snap['batches']}"
        assert snap["bytes_transferred"] == per_run_bytes
    assert stats_objects[0] is not stats_objects[1]
    # The journal fold sees the two fits as two runs of the same pipeline.
    folded = fold_pipeline_events([dict(s) for s in snaps])
    (agg,) = folded.values()
    assert agg["runs"] == 2
    assert agg["batches"] == 12
    assert agg["bytes_transferred"] == 2 * per_run_bytes


def test_device_put_tree_skips_placed_leaves():
    sharding = _sharding()
    placed = jax.device_put(jnp.ones((4, 4)), sharding)
    host = np.ones((4, 4), np.float32)
    out = device_put_tree({"a": placed, "b": host}, sharding)
    assert out["a"] is placed  # no re-transfer for equivalently-placed leaves
    assert isinstance(out["b"], jax.Array)
    np.testing.assert_array_equal(np.asarray(out["b"]), host)


# --- counters and the status fold --------------------------------------------


def test_pipeline_stats_journal_idempotent_and_empty_noop():
    class FakeRecorder:
        def __init__(self):
            self.events = []

        def record(self, kind, **fields):
            self.events.append((kind, fields))

    rec = FakeRecorder()
    empty = PipelineStats(name="never-ran")
    assert empty.journal(recorder=rec) is None  # no batches -> no event
    stats = PipelineStats(name="run")
    stats.add_transfer(1024)
    stats.add_host_input(0.5)
    stats.add_consumer_wait(0.1)
    snap = stats.journal(recorder=rec)
    assert stats.journal(recorder=rec) is None  # second call is a no-op
    assert len(rec.events) == 1
    kind, fields = rec.events[0]
    assert kind == "input_pipeline"
    assert fields["bytes_transferred"] == 1024
    assert snap["batches"] == 1
    assert 0.0 <= fields["overlap_fraction"] <= 1.0


def test_fold_pipeline_events_aggregates_per_name():
    events = [
        {"name": "fit", "batches": 10, "bytes_transferred": 100,
         "host_input_seconds": 1.0, "producer_stall_seconds": 0.0,
         "consumer_wait_seconds": 1.0, "elapsed_seconds": 4.0},
        {"name": "fit", "batches": 10, "bytes_transferred": 300,
         "host_input_seconds": 0.5, "producer_stall_seconds": 0.5,
         "consumer_wait_seconds": 1.0, "elapsed_seconds": 4.0},
        {"name": "eval", "batches": 2, "bytes_transferred": 50,
         "host_input_seconds": 0.1, "producer_stall_seconds": 0.0,
         "consumer_wait_seconds": 0.0, "elapsed_seconds": 1.0},
        {"kind": "span", "seconds": 1.0},  # non-pipeline events ignored
    ]
    out = fold_pipeline_events(events)
    assert set(out) == {"fit", "eval"}
    assert out["fit"]["runs"] == 2
    assert out["fit"]["batches"] == 20
    assert out["fit"]["bytes_transferred"] == 400
    assert out["fit"]["overlap_fraction"] == pytest.approx(0.75)
    assert out["eval"]["overlap_fraction"] == pytest.approx(1.0)


def test_stats_thread_safety_under_concurrent_folds():
    stats = PipelineStats(name="race")

    def hammer():
        for _ in range(500):
            stats.add_transfer(8)
            stats.add_host_input(0.001)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = stats.snapshot()
    assert snap["batches"] == 2000
    assert snap["bytes_transferred"] == 16000
    assert snap["host_input_seconds"] == pytest.approx(2.0)


# --- overlap architecture: stacking, donation, double buffering ---------------


def test_device_put_tree_deleted_leaf_not_treated_as_placed():
    """Regression for the double-placement gap: a donated/deleted array
    keeps its sharding metadata, so a pure sharding-equality skip would
    treat the dead buffer as already placed and hand it straight back.
    _placed_with must treat deleted as NOT placed, so device_put_tree
    re-issues jax.device_put — which raises at the placement site
    whenever an actual transfer is required (cross-sharding), instead of
    the failure surfacing at first use, far from the loop that freed the
    buffer."""
    from deeplearning_cfn_tpu.train.data import _placed_with

    sharding = _sharding()
    placed = jax.device_put(jnp.ones((8, 4)), sharding)
    assert _placed_with(placed, sharding)
    placed.delete()
    assert placed.is_deleted()
    # The skip path is off for dead buffers even though the sharding
    # metadata still matches.
    assert not _placed_with(placed, sharding)
    # Where placement does real work, the error now fires right here.
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=8))
    row = NamedSharding(mesh, P(("dp", "fsdp")))
    dead = jax.device_put(jnp.ones((8, 4)), row)
    dead.delete()
    with pytest.raises(RuntimeError, match="deleted"):
        device_put_tree({"a": dead}, NamedSharding(mesh, P()))


def test_stack_batches_shapes_and_ragged_tail():
    from deeplearning_cfn_tpu.train.data import stack_batches

    ds = SyntheticDataset(shape=(8, 8, 3), num_classes=4, batch_size=4)
    stacks = list(stack_batches(ds.batches(7), 3))
    # 7 batches at k=3 -> two stacks; the ragged single-batch tail is
    # dropped (callers route remainders through the single-step path).
    assert len(stacks) == 2
    for s in stacks:
        assert s.x.shape == (3, 4, 8, 8, 3)
        assert s.y.shape == (3, 4)
    # Stack contents are the source batches in order.
    batches = list(SyntheticDataset(
        shape=(8, 8, 3), num_classes=4, batch_size=4
    ).batches(3))
    restacked = next(iter(stack_batches(iter(batches), 3)))
    for i, b in enumerate(batches):
        np.testing.assert_array_equal(restacked.x[i], b.x)
        np.testing.assert_array_equal(restacked.y[i], b.y)

    with pytest.raises(ValueError, match="k >= 1"):
        next(stack_batches(ds.batches(2), 0))


def test_donate_buffers_frees_and_counts():
    from deeplearning_cfn_tpu.train.data import donate_buffers

    sharding = _sharding()
    x = jax.device_put(jnp.ones((4, 4), jnp.float32), sharding)
    y = jax.device_put(jnp.ones((4,), jnp.int32), sharding)
    host = np.ones((2, 2), np.float32)  # numpy leaves are skipped, not crashed
    freed = donate_buffers({"x": x, "y": y, "host": host})
    assert freed == 4 * 4 * 4 + 4 * 4
    assert x.is_deleted() and y.is_deleted()
    # Idempotent: a second donation finds nothing live to free.
    assert donate_buffers({"x": x, "y": y}) == 0


def test_prefetcher_buffered_exposes_device_resident_batches():
    """buffered() is the observability hook the bench and perf_smoke use
    to assert the double buffer actually holds >= 2 device-resident
    batches: it must report only batches already transferred and not
    yet handed to the consumer, and drain to empty at exhaustion."""
    import time

    ds = SyntheticDataset(shape=(8, 8, 3), num_classes=4, batch_size=4)
    pf = DevicePrefetcher(ds.batches(4), _sharding(), size=2, workers=2)
    try:
        deadline = time.monotonic() + 10.0
        while len(pf.buffered()) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        held = pf.buffered()
        assert len(held) == 2  # full double buffer before any consumption
        for b in held:
            assert isinstance(b.x, jax.Array) and not b.x.is_deleted()
        seen = 0
        for _ in pf:
            seen += 1
            assert len(pf.buffered()) <= 2
        assert seen == 4
        assert pf.buffered() == []
    finally:
        pf.close()
