"""SPMD trainer tests on the 8-device virtual CPU mesh (SURVEY §4 pattern b).

Checks the compute path the reference delegated to Horovod/NCCL and ps-lite:
data-parallel gradient exchange, FSDP parameter sharding, and numerical
equivalence between strategies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning_cfn_tpu.models.lenet import LeNet
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.parallel.sharding import infer_param_sharding
from deeplearning_cfn_tpu.train.data import SyntheticDataset
from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig


def test_virtual_mesh_has_8_devices():
    assert len(jax.devices()) == 8


@pytest.mark.smoke
@pytest.mark.parametrize("strategy,mesh_spec", [
    ("dp", MeshSpec(dp=8)),
    ("fsdp", MeshSpec(fsdp=8)),
    ("dp", MeshSpec(dp=4, fsdp=2)),
])
def test_lenet_loss_decreases(strategy, mesh_spec):
    mesh = build_mesh(mesh_spec)
    trainer = Trainer(
        LeNet(), mesh, TrainerConfig(strategy=strategy, learning_rate=0.05)
    )
    ds = SyntheticDataset.mnist_like(batch_size=64)
    sample = next(iter(ds.batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    state, losses = trainer.fit(state, ds.batches(30), steps=30)
    assert losses[-1] < losses[0] * 0.7, f"loss did not decrease: {losses[:3]} -> {losses[-3:]}"


def test_dp_fsdp_numerical_equivalence():
    # The same model/data must produce the same trajectory whether params
    # are replicated (dp) or sharded (fsdp): sharding is layout, not math.
    ds = SyntheticDataset.mnist_like(batch_size=32)
    sample = next(iter(ds.batches(1)))
    results = {}
    for strategy, spec in [("dp", MeshSpec(dp=8)), ("fsdp", MeshSpec(fsdp=8))]:
        mesh = build_mesh(spec)
        trainer = Trainer(
            LeNet(), mesh, TrainerConfig(strategy=strategy, learning_rate=0.05)
        )
        state = trainer.init(jax.random.key(42), jnp.asarray(sample.x))
        state, losses = trainer.fit(state, ds.batches(5), steps=5)
        results[strategy] = losses
    np.testing.assert_allclose(results["dp"], results["fsdp"], rtol=2e-4)


def test_fsdp_actually_shards_params():
    mesh = build_mesh(MeshSpec(fsdp=8))
    trainer = Trainer(LeNet(), mesh, TrainerConfig(strategy="fsdp"))
    ds = SyntheticDataset.mnist_like(batch_size=32)
    sample = next(iter(ds.batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    # The big dense kernel must be sharded, not replicated.
    fc1 = state.params["fc1"]["kernel"]
    assert fc1.sharding.spec != P()
    # Each device holds 1/8 of it.
    shard = fc1.addressable_shards[0]
    assert shard.data.size == fc1.size // 8
    # Opt state (momentum buffer) mirrors param sharding.
    flat = jax.tree_util.tree_leaves(state.opt_state)
    big = [l for l in flat if hasattr(l, "size") and l.size == fc1.size]
    assert big and all(l.sharding.spec == fc1.sharding.spec for l in big)


def test_mesh_validation():
    from deeplearning_cfn_tpu.parallel.mesh import MeshError

    with pytest.raises(MeshError, match="multiply to"):
        build_mesh(MeshSpec(dp=3))  # 3 does not equal 8 devices


def test_infer_param_sharding_replicates_small_arrays():
    mesh = build_mesh(MeshSpec(fsdp=8))
    params = {
        "kernel": jnp.zeros((256, 512)),
        "bias": jnp.zeros((512,)),
    }
    sh = infer_param_sharding(params, mesh)
    assert sh["kernel"].spec != P()
    assert sh["bias"].spec == P()  # too small to shard


def test_fsdp_spec_min_shard_elems_boundary_is_strict():
    """The size gate is `< min_shard_elems`: exactly 2**14 elements is
    big enough to shard; one element fewer is replicated.  The comms-
    overlap bucket planner keys off this spec, so the boundary is
    load-bearing, not cosmetic."""
    from deeplearning_cfn_tpu.parallel.sharding import _fsdp_spec_for_array

    mesh = build_mesh(MeshSpec(fsdp=8))
    at_threshold = jnp.zeros((128, 128))  # 2**14 exactly
    assert _fsdp_spec_for_array(at_threshold, mesh) == P("fsdp", None)
    just_under = jnp.zeros((128, 127))
    assert _fsdp_spec_for_array(just_under, mesh) == P()


def test_fsdp_spec_shards_1d_and_prefers_the_largest_divisible_dim():
    from deeplearning_cfn_tpu.parallel.sharding import _fsdp_spec_for_array

    mesh = build_mesh(MeshSpec(fsdp=8))
    # A big 1-D leaf (embeddings flattened, fused scales) shards too.
    assert _fsdp_spec_for_array(jnp.zeros((2**14,)), mesh) == P("fsdp")
    # Largest dim wins when divisible; otherwise fall through to the
    # next-largest that is.
    assert _fsdp_spec_for_array(jnp.zeros((512, 256)), mesh) == P("fsdp", None)
    assert _fsdp_spec_for_array(jnp.zeros((513, 256)), mesh) == P(None, "fsdp")


def test_fsdp_spec_replicates_when_nothing_divides_or_axis_trivial():
    from deeplearning_cfn_tpu.parallel.sharding import _fsdp_spec_for_array

    mesh = build_mesh(MeshSpec(fsdp=8))
    # Big, but no dimension divisible by the 8-way fsdp axis.
    assert _fsdp_spec_for_array(jnp.zeros((4099, 5)), mesh) == P()
    # Scalars never shard regardless of the axis.
    assert _fsdp_spec_for_array(jnp.zeros(()), mesh) == P()
    # A trivial fsdp axis replicates everything (dp-only meshes).
    dp_mesh = build_mesh(MeshSpec(dp=8))
    assert _fsdp_spec_for_array(jnp.zeros((512, 512)), dp_mesh) == P()


def test_remat_and_bf16_compile():
    mesh = build_mesh(MeshSpec(dp=8))
    trainer = Trainer(
        LeNet(), mesh, TrainerConfig(strategy="dp", remat=True, bf16_compute=True)
    )
    ds = SyntheticDataset.mnist_like(batch_size=32)
    sample = next(iter(ds.batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    state, losses = trainer.fit(state, ds.batches(3), steps=3)
    assert np.isfinite(losses).all()


def test_resnet_batchnorm_state_sharded_step():
    # Mutable model_state (BatchNorm running stats) through the sharded
    # train step: has_train_arg + mutable-collection branch under fsdp.
    from deeplearning_cfn_tpu.models.resnet import ResNet

    mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
    tiny = ResNet(stage_sizes=(1, 1), num_classes=8, num_filters=16)
    trainer = Trainer(
        tiny,
        mesh,
        TrainerConfig(strategy="fsdp", learning_rate=0.1, has_train_arg=True),
    )
    ds = SyntheticDataset(shape=(32, 32, 3), num_classes=8, batch_size=16)
    sample = next(iter(ds.batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    before = jax.tree_util.tree_map(np.asarray, state.model_state)
    state, losses = trainer.fit(state, ds.batches(3), steps=3)
    assert np.isfinite(losses).all()
    # Running stats actually updated.
    after = state.model_state
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()), before, after
    )
    assert max(jax.tree_util.tree_leaves(diffs)) > 0.0


def test_evaluate_aggregates_weighted_metrics():
    """evaluate(): no-grad eval step; example-weighted mean; BN models run
    with running statistics (train=False)."""
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
    trainer = Trainer(
        LeNet(num_classes=4),
        mesh,
        TrainerConfig(learning_rate=0.05, matmul_precision="float32"),
    )
    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=16)
    # 60 steps: enough for LeNet to clear the chance bar by a wide margin
    # (20 steps lands within noise of 0.25).
    batches = list(ds.batches(60))
    state = trainer.init(jax.random.key(0), jnp.asarray(batches[0].x))
    state, _ = trainer.fit(state, iter(batches), steps=60)

    # Same task (template_seed=0 matches training templates), fresh
    # sample stream.
    held_out = SyntheticDataset(
        shape=(8, 8, 1), num_classes=4, batch_size=16, seed=99, template_seed=0
    )
    before = trainer.evaluate(state, held_out.batches(4), steps=4)
    assert before["examples"] == 64
    assert set(before) >= {"loss", "accuracy", "examples"}
    assert 0.0 <= before["accuracy"] <= 1.0
    # A trained model beats chance on held-out data from the same
    # (learnable) synthetic distribution.
    assert before["accuracy"] > 0.3, before

    # evaluate must not mutate the state (pure read).
    again = trainer.evaluate(state, held_out.batches(4), steps=4)
    assert again == before


def test_evaluate_full_split_tail_batches():
    """Full-split eval passes (drop_remainder=False) end with a partial
    batch.  A mesh-divisible tail is consumed whole; an indivisible one
    is trimmed to the shard multiple — loudly, never silently (VERDICT
    r4 weak #1: held-out claims must cover the whole split, and when
    they cannot, the shortfall must be visible)."""
    from deeplearning_cfn_tpu.models.lenet import LeNet
    from deeplearning_cfn_tpu.train.data import Batch

    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    trainer = Trainer(
        LeNet(num_classes=4),
        mesh,
        TrainerConfig(learning_rate=0.05, matmul_precision="float32"),
    )
    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=16)
    full = list(ds.batches(2))
    state = trainer.init(jax.random.key(0), jnp.asarray(full[0].x))

    def tail(n):
        return Batch(x=full[1].x[:n], y=full[1].y[:n])

    # 16 + 6: both divide the 2-way batch sharding -> whole split scored.
    out = trainer.evaluate(state, iter([full[0], tail(6)]))
    assert out["examples"] == 22
    # 16 + 5: the 5-tail trims to 4 (largest multiple of 2 shards).
    out = trainer.evaluate(state, iter([full[0], tail(5)]))
    assert out["examples"] == 20
    # A tail smaller than the shard count is dropped entirely, not crashed.
    mesh8 = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
    trainer8 = Trainer(
        LeNet(num_classes=4), mesh8,
        TrainerConfig(learning_rate=0.05, matmul_precision="float32"),
    )
    state8 = trainer8.init(jax.random.key(0), jnp.asarray(full[0].x))
    out = trainer8.evaluate(state8, iter([full[0], tail(5)]))
    assert out["examples"] == 16


def test_evaluate_empty_iterator():
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
    trainer = Trainer(LeNet(num_classes=4), mesh, TrainerConfig())
    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=8)
    state = trainer.init(jax.random.key(0), jnp.asarray(next(iter(ds.batches(1))).x))
    assert trainer.evaluate(state, iter([]))["examples"] == 0


def test_fit_prefetch_matches_inline_and_bounds_consumption():
    """prefetch moves transfers to a background thread but must not change
    the training trajectory, and fit(steps=N, prefetch=k) consumes at most
    N batches from the caller's iterator."""
    ds = SyntheticDataset.mnist_like(batch_size=32)
    sample = next(iter(ds.batches(1)))
    results = {}
    for prefetch in (0, 2):
        mesh = build_mesh(MeshSpec(dp=8))
        trainer = Trainer(
            LeNet(), mesh, TrainerConfig(learning_rate=0.05, matmul_precision="float32")
        )
        state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
        state, losses = trainer.fit(
            state, ds.batches(6), steps=6, prefetch=prefetch
        )
        results[prefetch] = losses
    np.testing.assert_allclose(results[0], results[2], rtol=1e-6)

    # Consumption bound: islice keeps the prefetcher from draining the
    # caller's iterator past `steps`.
    mesh = build_mesh(MeshSpec(dp=8))
    trainer = Trainer(
        LeNet(), mesh, TrainerConfig(learning_rate=0.05, matmul_precision="float32")
    )
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    src = iter(list(ds.batches(8)))
    trainer.fit(state, src, steps=3, prefetch=2)
    assert len(list(src)) == 5  # 8 - 3 consumed


def test_device_prefetcher_propagates_errors_and_closes():
    from deeplearning_cfn_tpu.train.data import Batch, DevicePrefetcher
    from jax.sharding import NamedSharding

    mesh = build_mesh(MeshSpec(dp=8))
    sharding = NamedSharding(mesh, P(("dp", "fsdp")))

    def bad_batches():
        yield Batch(
            x=np.zeros((8, 4), np.float32), y=np.zeros((8,), np.int32)
        )
        raise RuntimeError("loader exploded")

    pf = DevicePrefetcher(bad_batches(), sharding, size=2)
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="loader exploded"):
        next(it)
    pf.close()

    # close() before exhaustion stops the producer without hanging.
    pf2 = DevicePrefetcher(
        iter([Batch(x=np.zeros((8, 4), np.float32), y=np.zeros((8,), np.int32))] * 100),
        sharding,
        size=1,
    )
    next(iter(pf2))
    pf2.close()


def test_evaluate_does_not_overconsume_iterator():
    """Regression: evaluate(steps=N) must take exactly N batches from the
    caller's iterator (a break-based loop pulled and discarded N+1)."""
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
    trainer = Trainer(LeNet(num_classes=4), mesh, TrainerConfig())
    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=8)
    batches = iter(list(ds.batches(5)))
    state = trainer.init(jax.random.key(0), jnp.asarray(next(batches).x))
    trainer.evaluate(state, batches, steps=2)
    assert len(list(batches)) == 2  # 5 total - 1 init - 2 evaluated


def test_mfu_numerator_is_centralized_for_flash_paths():
    """VERDICT r2 weak #4: cost-analysis flops exclude Pallas custom-call
    FLOPs, so flash-attention workloads under-reported MFU everywhere but
    the one example that hand-plumbed analytic flops.  The trainer now
    owns the choice: compile_stats and throughput_logger must agree, and
    both must use the model's analytic figure when it exists."""
    import numpy as np

    from deeplearning_cfn_tpu.models import llama
    from deeplearning_cfn_tpu.train import trainer as trainer_mod

    mesh = build_mesh(MeshSpec.data_parallel(4), jax.devices()[:4])
    cfg = llama.LlamaConfig.tiny(vocab_size=64, seq_len=16)
    tr = llama.make_trainer(
        cfg, mesh, TrainerConfig(strategy="fsdp", optimizer="adamw")
    )
    tok = np.zeros((4, 16), dtype=np.int32)
    x = jax.device_put(jnp.asarray(tok), tr.batch_sharding)
    y = jax.device_put(jnp.asarray(tok), tr.batch_sharding)
    state = tr.init(jax.random.key(0), x)

    stats = tr.compile_stats(state, x, y)
    expected = llama.train_flops_per_token(cfg, 16) * 4 * 16 / mesh.size
    assert stats["flops_source"] == "analytic"
    assert stats["flops_per_step"] == pytest.approx(expected)
    # Raw cost analysis stays visible for diagnostics.
    assert "cost_flops_per_step" in stats

    # The logger gets the same numerator (pretend a TPU peak exists: on
    # the CPU test backend peak_flops_per_chip() is None and MFU is
    # rightly skipped).
    orig = trainer_mod.peak_flops_per_chip
    trainer_mod.peak_flops_per_chip = lambda device=None: 100e12
    try:
        logger = tr.throughput_logger(x, examples_per_step=4 * 16)
    finally:
        trainer_mod.peak_flops_per_chip = orig
    assert logger.flops_per_step == pytest.approx(expected)
    assert logger.peak_flops == 100e12


def test_cost_analysis_source_for_dense_models():
    """Models without Pallas ops keep the cost-analysis numerator."""
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(4), jax.devices()[:4])
    tr = Trainer(LeNet(num_classes=4), mesh, TrainerConfig())
    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=8)
    b = next(iter(ds.batches(1)))
    state = tr.init(jax.random.key(0), jnp.asarray(b.x))
    stats = tr.compile_stats(state, jnp.asarray(b.x), jnp.asarray(b.y))
    assert stats["flops_source"] == "cost_analysis"
    assert stats["flops_per_step"] == stats["cost_flops_per_step"]


def test_compile_stats_shares_its_compile_with_fit():
    """The examples hand compile_stats a host/default-device sample.  It
    must lower the step as fit() will dispatch it (batch described by the
    trainer's batch sharding): lowered from the sample as it sits, the
    step compiled a second time at the first dispatch."""
    from deeplearning_cfn_tpu.analysis.compile_audit import CompileWatcher
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(2), jax.devices()[:2])
    tr = Trainer(LeNet(num_classes=4), mesh, TrainerConfig())
    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=8)
    b = next(iter(ds.batches(1)))
    state = tr.init(jax.random.key(0), jnp.asarray(b.x))
    with CompileWatcher() as w:
        tr.compile_stats(state, jnp.asarray(b.x), b.y)
        tr.fit(state, ds.batches(2), steps=2)
    assert w.compiles.get("train_step") == 1


def test_compile_cache_is_placed_from_outside_or_fixed_in_the_checkout(
    tmp_path, monkeypatch
):
    """JAX_COMPILATION_CACHE_DIR set: the helper touches no cache option
    (JAX reads the variable itself).  Unset: the same absolute directory
    in two fresh processes, inside the checkout — the path is part of
    where JAX looks, so one that moves between runs never hits."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert enable_compile_cache() == str(tmp_path / "placed")
    assert jax.config.jax_compilation_cache_dir == before

    repo = Path(__file__).resolve().parents[1]
    script = (
        "from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache\n"
        "import jax\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(repo)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script], env=env, cwd=tmp_path,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for _ in range(2)
    ]
    outs = [proc.communicate(timeout=120) for proc in procs]
    for proc, (_, err) in zip(procs, outs):
        assert proc.returncode == 0, err[-2000:]
    returned, configured = outs[0][0].split()
    assert outs[1][0].split() == [returned, configured]
    assert returned == configured
    assert Path(returned).is_absolute() and repo in Path(returned).parents


def test_resnet_group_norm_variant_trains():
    """ResNet(norm="group"): no batch_stats collection (GroupNorm keeps
    no running statistics), same parameter surface otherwise, and a
    train step runs — the measured normalization lever of BENCH_NOTES r4
    (kept as an option: the right normalization for
    small-per-device-batch detection fine-tuning)."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.models.resnet import ResNet
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import SyntheticDataset
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    mesh = build_mesh(MeshSpec(dp=8))
    model = ResNet(
        stage_sizes=(1, 1, 1, 1), num_filters=8, num_classes=4, norm="group"
    )
    trainer = Trainer(
        model, mesh,
        TrainerConfig(learning_rate=0.01, has_train_arg=True,
                      matmul_precision="float32"),
    )
    ds = SyntheticDataset(shape=(32, 32, 3), num_classes=4, batch_size=16)
    batches = list(ds.batches(2))
    state = trainer.init(jax.random.key(0), jnp.asarray(batches[0].x))
    assert state.model_state == {}  # no running stats
    state, losses = trainer.fit(state, iter(batches), steps=2)
    assert all(np.isfinite(l) for l in losses)


def test_resnet_norm_validation_and_gcd_groups():
    import jax
    import jax.numpy as jnp
    import pytest as _pytest

    from deeplearning_cfn_tpu.models.resnet import ResNet

    with _pytest.raises(ValueError, match="unknown norm"):
        ResNet(stage_sizes=(1,), num_filters=8, norm="grup").init(
            jax.random.key(0), jnp.zeros((1, 16, 16, 3)), train=True
        )
    # Widths that are not multiples of 32 still group-normalize (gcd).
    m = ResNet(stage_sizes=(1,), num_filters=12, num_classes=3, norm="group")
    v = m.init(jax.random.key(0), jnp.zeros((1, 16, 16, 3)), train=True)
    out = m.apply(v, jnp.ones((1, 16, 16, 3)), train=True)
    assert out.shape == (1, 3)


def test_multi_step_fn_matches_sequential_steps():
    """The k-step scan (the XLA-expressible form of cross-iteration
    fusion) must be numerically identical to k sequential jitted steps —
    it exists to measure/enable cross-iteration scheduling, never to
    change semantics."""
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])

    def make():
        return Trainer(
            LeNet(num_classes=4), mesh,
            TrainerConfig(learning_rate=0.05, matmul_precision="float32"),
        )

    ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=16)
    batches = list(ds.batches(4))
    xs = np.stack([b.x for b in batches])
    ys = np.stack([b.y for b in batches])

    t1 = make()
    s1 = t1.init(jax.random.key(0), jnp.asarray(batches[0].x))
    losses_seq = []
    for b in batches:
        s1, m = t1.train_step(s1, jnp.asarray(b.x), jnp.asarray(b.y))
        losses_seq.append(float(m["loss"]))

    t2 = make()
    s2 = t2.init(jax.random.key(0), jnp.asarray(batches[0].x))
    with jax.set_mesh(mesh):
        s2, losses = t2.multi_step_fn(4)(s2, jnp.asarray(xs), jnp.asarray(ys))
    np.testing.assert_allclose(
        np.asarray(losses), np.asarray(losses_seq), rtol=1e-5
    )
    assert int(jax.device_get(s2.step)) == 4
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s1.params)),
        jax.tree_util.tree_leaves(jax.device_get(s2.params)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_fold_batchnorm_matches_eval_forward():
    """Conv-BN folding (inference deployment): the folded model — convs
    carrying W*s and beta-mean*s, no norm modules — reproduces the
    trained model's eval-mode forward exactly, at every depth scope
    (init stem, block convs, projection shortcuts)."""
    from deeplearning_cfn_tpu.models.resnet import ResNet, fold_batchnorm

    rng = np.random.default_rng(0)
    kwargs = dict(stage_sizes=(1, 1), num_classes=8, num_filters=16,
                  dtype=jnp.float32)
    model = ResNet(**kwargs)
    x = jnp.asarray(rng.standard_normal((4, 32, 32, 3)), jnp.float32)
    variables = model.init(jax.random.key(0), x, train=False)
    # Perturb params and stats so the fold is exercised for real (fresh
    # init has mean=0/var=1/gamma∈{0,1}, which a broken fold could pass).
    params = jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(rng.normal(0, 0.05, a.shape), a.dtype),
        variables["params"],
    )
    stats = jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(rng.uniform(0.1, 1.0, a.shape), a.dtype),
        variables["batch_stats"],
    )
    ref = model.apply({"params": params, "batch_stats": stats}, x, train=False)

    folded = ResNet(**kwargs, norm="folded")
    fparams = fold_batchnorm(params, stats)
    # Same tree structure as a fresh folded-variant init (loadable).
    assert jax.tree_util.tree_structure(
        folded.init(jax.random.key(0), x, train=False)["params"]
    ) == jax.tree_util.tree_structure(fparams)
    out = folded.apply({"params": fparams}, x, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # The folded variant refuses to train (it has no normalization).
    with pytest.raises(ValueError, match="inference-only"):
        folded.init(jax.random.key(0), x, train=True)


def test_peak_tables_prefix_match():
    """Device-kind dispatch for the MFU and MBU denominators: known kinds
    resolve, longest prefix wins ('TPU v5 lite' is an 819 GB/s v5e, not a
    2765 GB/s v5p), a non-TPU device returns None so the CPU mesh reports
    no utilization instead of a wrong one — and a TPU whose kind is not in
    the table raises, so a chip run never logs a silent ``mfu: null``."""
    from deeplearning_cfn_tpu.train.metrics import (
        peak_flops_per_chip,
        peak_hbm_bytes_per_chip,
    )

    class FakeDev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    assert peak_flops_per_chip(FakeDev("TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(FakeDev("TPU v5")) == 459e12
    assert peak_hbm_bytes_per_chip(FakeDev("TPU v5 lite")) == 819e9
    assert peak_hbm_bytes_per_chip(FakeDev("TPU v5")) == 2765e9
    assert peak_hbm_bytes_per_chip(FakeDev("TPU v4")) == 1228e9
    assert peak_flops_per_chip(FakeDev("cpu", platform="cpu")) is None
    assert peak_hbm_bytes_per_chip(FakeDev("cpu", platform="cpu")) is None
    with pytest.raises(ValueError, match="TPU v9 mega"):
        peak_flops_per_chip(FakeDev("TPU v9 mega"))
    with pytest.raises(ValueError, match="TPU v9 mega"):
        peak_hbm_bytes_per_chip(FakeDev("TPU v9 mega"))


class TestGradAccumulation:
    """grad_accum_steps=k: ONE optimizer update from k microbatch
    gradients inside one compiled step — the memory-for-wallclock trade
    for effective batches the chip cannot hold.  (multi_step_fn is the
    other composition: k updates per dispatch.)"""

    def _fit_once(self, accum, strategy="fsdp", steps=3):
        mesh = build_mesh(MeshSpec(fsdp=8) if strategy == "fsdp" else MeshSpec(dp=8))
        # Momentum, not adam: the momentum update is LINEAR in the
        # gradient, so float-level reduction-order noise stays float-level
        # in the params.  Adam's step-1 update is ~sign(g) and flips on
        # near-zero gradient elements, which would demand a loose
        # tolerance that could hide real bugs.
        trainer = Trainer(
            LeNet(),
            mesh,
            TrainerConfig(
                optimizer="momentum", learning_rate=1e-2, weight_decay=1e-4,
                strategy=strategy,
                matmul_precision="float32", grad_accum_steps=accum,
            ),
        )
        ds = SyntheticDataset(batch_size=32, num_classes=10)
        batches = list(ds.batches(steps))
        state = trainer.init(jax.random.key(0), jnp.asarray(batches[0].x))
        for b in batches:
            state, metrics = trainer.train_step(
                state, jnp.asarray(b.x), jnp.asarray(b.y)
            )
        return state, metrics

    def test_accumulated_matches_full_batch(self):
        """Mean-of-microbatch-gradients equals the full-batch gradient
        (the objective is batch-mean), so k=4 must reproduce k=1 to
        float tolerance — same loss, same updated params."""
        s1, m1 = self._fit_once(accum=1)
        s4, m4 = self._fit_once(accum=4)
        assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
        for a, b in zip(
            jax.tree_util.tree_leaves(s1.params),
            jax.tree_util.tree_leaves(s4.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-6
            )

    def test_accum_with_batchnorm_state(self):
        """Mutable collections thread through the microbatch scan: the
        running stats move and training still learns."""
        from deeplearning_cfn_tpu.models.resnet import ResNet

        mesh = build_mesh(MeshSpec(dp=8))
        model = ResNet(stage_sizes=(1,), num_classes=4, num_filters=8)
        trainer = Trainer(
            model, mesh,
            TrainerConfig(optimizer="momentum", learning_rate=0.05,
                          matmul_precision="float32", grad_accum_steps=2),
        )
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((16, 32, 32, 3)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 4, 16), jnp.int32)
        state = trainer.init(jax.random.key(0), x)
        # Materialize BEFORE the first step: train_step donates its state,
        # so the original device buffers die with the first update.
        stats0 = [
            np.asarray(l) for l in jax.tree_util.tree_leaves(state.model_state)
        ]
        first = None
        for _ in range(10):
            state, metrics = trainer.train_step(state, x, y)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first
        moved = any(
            not np.allclose(np.asarray(a), np.asarray(b))
            for a, b in zip(stats0, jax.tree_util.tree_leaves(state.model_state))
        )
        assert moved, "BatchNorm stats never updated under accumulation"

    def test_indivisible_batch_fails_loudly(self):
        mesh = build_mesh(MeshSpec(dp=8))
        trainer = Trainer(
            LeNet(), mesh,
            TrainerConfig(optimizer="sgd", grad_accum_steps=3),
        )
        ds = SyntheticDataset(batch_size=32, num_classes=10)
        b = next(iter(ds.batches(1)))
        state = trainer.init(jax.random.key(0), jnp.asarray(b.x))
        with pytest.raises(ValueError, match="not divisible"):
            trainer.train_step(state, jnp.asarray(b.x), jnp.asarray(b.y))


class TestFitStepsPerCall:
    """fit(steps_per_call=k): the donated, double-buffered multi-step
    dispatch path.  k steps through one scanned program fed a pre-staged
    batch stack must be INDISTINGUISHABLE from k single-step dispatches —
    same losses, same bytes in the final state — because the whole point
    of the overlap architecture is to change scheduling, never math."""

    @staticmethod
    def _mlp():
        import flax.linen as nn

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = x.reshape(x.shape[0], -1)
                x = nn.relu(nn.Dense(32)(x))
                return nn.Dense(4)(x)

        return MLP()

    def _run(self, k, steps=4, prefetch=0):
        mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
        trainer = Trainer(
            self._mlp(), mesh,
            TrainerConfig(learning_rate=0.05, matmul_precision="float32"),
        )
        ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=16)
        batches = list(ds.batches(steps))
        state = trainer.init(jax.random.key(0), jnp.asarray(batches[0].x))
        state, losses = trainer.fit(
            state, iter(batches), steps=steps, steps_per_call=k,
            prefetch=prefetch,
        )
        return jax.device_get(state), losses

    def test_bit_parity_with_single_step(self):
        """Dense-only model: the scanned k-step program is bit-identical
        to k single-step dispatches (losses AND final params/opt_state
        bytes).  Convs reassociate under scan (~1e-7); dense does not."""
        s1, losses1 = self._run(k=1)
        s4, losses4 = self._run(k=4, prefetch=2)
        assert len(losses1) == len(losses4) == 4
        np.testing.assert_array_equal(np.asarray(losses1), np.asarray(losses4))
        for a, b in zip(
            jax.tree_util.tree_leaves(s1.params),
            jax.tree_util.tree_leaves(s4.params),
        ):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(
            jax.tree_util.tree_leaves(s1.opt_state),
            jax.tree_util.tree_leaves(s4.opt_state),
        ):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert int(s1.step) == int(s4.step) == 4

    def test_remainder_steps_still_run(self):
        """steps=5 with k=2: two stacked calls plus a single-step tail —
        all 5 losses come back and the step counter agrees."""
        state, losses = self._run(k=2, steps=5, prefetch=2)
        assert len(losses) == 5
        assert int(state.step) == 5
        assert np.isfinite(losses).all()

    def test_consumption_bound(self):
        """The stacked prefetcher must not drain the caller's iterator
        past `steps` (islice bound survives the stacking)."""
        mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
        trainer = Trainer(
            self._mlp(), mesh,
            TrainerConfig(learning_rate=0.05, matmul_precision="float32"),
        )
        ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=16)
        src = iter(list(ds.batches(8)))
        state = trainer.init(jax.random.key(0), jnp.asarray(next(src).x))
        trainer.fit(state, src, steps=4, steps_per_call=2, prefetch=2)
        assert len(list(src)) == 3  # 8 - 1 init - 4 trained

    def test_validation(self):
        mesh = build_mesh(MeshSpec.data_parallel(8), jax.devices()[:8])
        trainer = Trainer(self._mlp(), mesh, TrainerConfig())
        ds = SyntheticDataset(shape=(8, 8, 1), num_classes=4, batch_size=16)
        b = next(iter(ds.batches(1)))
        state = trainer.init(jax.random.key(0), jnp.asarray(b.x))
        with pytest.raises(ValueError, match="steps_per_call"):
            trainer.fit(state, iter([b]), steps=1, steps_per_call=0)

        class FakeReshard:
            def pending(self):
                return False

        with pytest.raises(ValueError, match="live resharding"):
            trainer.fit(
                state, iter([b]), steps=2, steps_per_call=2,
                reshard=FakeReshard(),
            )
