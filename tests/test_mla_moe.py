"""models/mla_moe.py: the parameter tree and its sizes at the published
widths, the two-term loss's targets and masks, the routing statistics on their
way to obs.tracing's counters through Trainer.fit, and the attention core's
dispatch.  The model against the plain reference (logits, loss, gradients, an
AdamW step) is tests/benchmark_tests/test_benchmark_mla_moe.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning_cfn_tpu.models import mla_moe
from deeplearning_cfn_tpu.models.mla_moe import MlaMoeConfig
from deeplearning_cfn_tpu.obs import tracing
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.data import Batch
from deeplearning_cfn_tpu.train.trainer import TrainerConfig

# The benchmark's cut of GLM-4.7-Flash: a dense layer and four routed ones,
# 16 of 64 experts and a quarter of the vocabulary held here.
CELL = MlaMoeConfig(vocab_size=38720, n_layers=5, held_experts=(0, 16))


def _count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def _batch(cfg, b=4, s=32, seed=0):
    x = np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, s), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


def test_parameter_counts_at_the_published_widths():
    """ISSUE 26's arithmetic, from the tree itself (shapes only)."""
    shapes = jax.eval_shape(lambda: mla_moe.init_params(CELL, jax.random.key(0)))
    one = lambda stack: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stack
    )
    routed = one(shapes["layers"])
    attention = {k: v for k, v in routed.items() if k not in ("moe", "mlp_norm", "attn_norm")}
    assert _count(attention) == 21_757_952 + 768 + 512  # five matrices, two latent norms
    assert _count({k: routed["moe"][k] for k in ("w_gate", "w_up", "w_down")}) == 16 * 9_437_184
    assert round(_count(routed) / 1e6, 1) == 182.3
    assert round(_count(one(shapes["dense"])) / 1e6, 1) == 84.7
    assert round(_count(shapes["mtp"]) / 1e6, 1) == 190.7
    assert _count(shapes["embed"]) == _count(shapes["output"]) == 38720 * 2048
    assert round(mla_moe.param_count(CELL) / 1e6) == 1163
    assert round(mla_moe.param_count(dataclasses.replace(CELL, n_layers=6)) / 1e6) == 1346
    # 4.24 GFLOP a token at S 8192 with four routed layers, 4.74 with five
    assert round(mla_moe.train_flops_per_token(CELL, 8192) / 1e9, 2) == 4.24
    assert round(
        mla_moe.train_flops_per_token(dataclasses.replace(CELL, n_layers=6), 8192) / 1e9, 2
    ) == 4.74


@pytest.mark.parametrize("cfg", [MlaMoeConfig.tiny(), MlaMoeConfig.tiny(n_predict=0, n_dense_layers=0)],
                         ids=["dense+routed+mtp", "routed-only"])
def test_specs_mirror_the_parameter_tree(cfg):
    params = jax.eval_shape(lambda: mla_moe.init_params(cfg, jax.random.key(0)))
    specs = mla_moe.param_specs(cfg)
    is_spec = lambda x: isinstance(x, P)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)
    )
    for p, s in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(specs, is_leaf=is_spec)):
        assert len(s) == p.ndim, (p.shape, s)
    assert params["layers"]["moe"]["router"].dtype == jnp.float32
    assert params["layers"]["moe"]["router_bias"].shape == (cfg.n_routed_layers, 8)


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="n_dense_layers"):
        MlaMoeConfig.tiny(n_dense_layers=3)
    with pytest.raises(ValueError, match="n_predict"):
        MlaMoeConfig.tiny(n_predict=2)


def test_the_two_losses_targets_and_masks_at_the_sequences_end():
    """The main head predicts t[i+1] at every position but the last, the
    prediction module t[i+2] at every position but the last two; what the
    wrapped positions hold changes nothing."""
    cfg = MlaMoeConfig.tiny()
    params = mla_moe.init_params(cfg, jax.random.key(0))
    x, y = _batch(cfg, b=2, s=16)
    loss, metrics = mla_moe.lm_loss(cfg, params, jnp.asarray(x), jnp.asarray(y))
    out = mla_moe.logits(cfg, params, jnp.asarray(x), jnp.asarray(y))

    def mean_nll(logits, targets, positions):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return float(jnp.mean(nll[:, :positions]))

    main = mean_nll(out["main"], jnp.asarray(y), 15)
    mtp = mean_nll(out["mtp"][:, :14], jnp.asarray(x)[:, 2:], 14)  # t[i+2] is x[i+2]
    assert float(metrics["mtp_loss"]) == pytest.approx(mtp, rel=1e-5)
    assert float(jnp.log(metrics["perplexity"])) == pytest.approx(main, rel=1e-5)
    assert float(loss) == pytest.approx(main + cfg.mtp_loss_weight * mtp, rel=1e-5)
    # The wrapped target of the last position (and with it the embedding the
    # prediction module joins there, and both of its wrapped targets) is masked.
    y2 = y.copy()
    y2[:, -1] = (y2[:, -1] + 7) % cfg.vocab_size
    loss2, _ = mla_moe.lm_loss(cfg, params, jnp.asarray(x), jnp.asarray(y2))
    assert float(loss2) == pytest.approx(float(loss), rel=1e-6)
    # Without the module the loss is the next-token term alone.
    plain = dataclasses.replace(cfg, n_predict=0)
    only = {k: v for k, v in params.items() if k != "mtp"}
    loss3, metrics3 = mla_moe.lm_loss(plain, only, jnp.asarray(x), jnp.asarray(y))
    assert float(loss3) == pytest.approx(main, rel=1e-5) and "mtp_loss" not in metrics3


def test_fit_trains_and_folds_the_routing_counters_at_the_log_seam():
    cfg = MlaMoeConfig.tiny()
    mesh = build_mesh(MeshSpec.fsdp_parallel(1), jax.devices()[:1])
    trainer = mla_moe.make_trainer(
        cfg, mesh,
        TrainerConfig(strategy="fsdp", optimizer="adamw", learning_rate=1e-2, log_every=2),
    )
    x, y = _batch(cfg)
    state = trainer.init(jax.random.key(0), x)
    bias0 = np.asarray(state.params["layers"]["moe"]["router_bias"])
    tracing.reset_aggregates()
    state, losses = trainer.fit(state, (Batch(x, y) for _ in range(7)), steps=7)
    assert losses[-1] < losses[0]
    counted = {k: v for k, v in tracing.counters().items() if k.startswith("moe.")}
    assert set(counted) == {
        "moe.assignments", "moe.assignments_held", "moe.rows_run", "moe.slots_read",
        "moe.expert_load_max", "moe.expert_load_mean", "moe.dropped",
    }
    assert all(v["count"] == 7 for v in counted.values())  # the odd last step too
    blocks, tokens = cfg.n_routed_layers + cfg.n_predict, 4 * 32
    assert counted["moe.assignments"]["total"] == 7 * blocks * tokens * cfg.top_k
    # the token-major gathers read a slot for each of a token's choices forward, and backward
    # for each that can be held here
    assert counted["moe.slots_read"]["total"] == 7 * blocks * tokens * (
        cfg.top_k + min(cfg.top_k, cfg.held_experts[1]))
    assert 0 < counted["moe.assignments_held"]["total"] < counted["moe.assignments"]["total"]
    # the passes over the buffer stop at the tile that holds the last held row
    assert (
        counted["moe.assignments_held"]["total"] <= counted["moe.rows_run"]["total"]
        <= counted["moe.assignments"]["total"]
    )
    assert counted["moe.dropped"]["total"] == 0
    assert counted["moe.expert_load_max"]["total"] >= counted["moe.expert_load_mean"]["total"]
    assert counted["moe.expert_load_mean"]["total"] == pytest.approx(
        counted["moe.assignments_held"]["total"] / (blocks * cfg.held_experts[1])
    )
    # The selection bias is a buffer: no gradient, no decay, it stays.
    np.testing.assert_array_equal(np.asarray(state.params["layers"]["moe"]["router_bias"]), bias0)
    # evaluate() averages the scalars and leaves the counters out
    assert "counters" not in trainer.evaluate(state, [Batch(x, y)])


def test_attention_core_dispatch():
    """Flash on a TPU at and above the crossover when all three head sizes
    agree, XLA everywhere else."""
    from deeplearning_cfn_tpu.models.llama import attention_kind

    assert attention_kind(CELL, None, 8192, backend="tpu") == "flash"
    assert attention_kind(CELL, None, 1024, backend="tpu") == "xla"
    assert attention_kind(CELL, None, 8192, backend="cpu") == "xla"
    assert CELL.qk_head_dim == CELL.v_head_dim == 256


def test_the_example_runs_from_its_template(tmp_path, monkeypatch):
    """`dlcfn run templates/mla-moe-stage.json` at the tiny size: template ->
    provision -> launch plan -> examples.mla_moe_train -> Trainer.fit."""
    import contextlib
    import io
    import json
    from pathlib import Path

    from deeplearning_cfn_tpu import cli

    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    tracing.reset_aggregates()  # the example reports the process's counters
    template = Path(__file__).resolve().parents[1] / "templates" / "mla-moe-stage.json"
    argv = ["run", str(template)]
    for name, value in (("Size", "tiny"), ("SeqLen", 32), ("Batch", 8), ("Steps", 6)):
        argv += ["-P", f"{name}={value}"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert cli.main(argv) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])["result"]
    assert result["steps"] == 6 and np.isfinite(result["final_loss"])
    assert result["experts_held"] == [0, 4] and result["attention"] == "xla"
    assert result["routing"]["moe.dropped"] == 0.0
    assert result["routing"]["moe.assignments"] == 3 * 8 * 32 * 2
