"""models/conv_attn_moe.py: the gated short convolution against an explicit
per-tap sum and its causality, the layer pattern as data (the published
24-entry list constructs, runs a step and has the published parameter count),
the specs against the tree, the routing statistics on their way to
obs.tracing's counters through Trainer.fit, the attention core's dispatch, and
the example from its template.  The model against the plain reference (logits,
loss, gradients, an AdamW step) is
tests/benchmark_tests/test_benchmark_conv_attn_moe.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning_cfn_tpu.models import conv_attn_moe
from deeplearning_cfn_tpu.models.conv_attn_moe import ConvAttnMoeConfig
from deeplearning_cfn_tpu.obs import tracing
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.data import Batch
from deeplearning_cfn_tpu.train.trainer import TrainerConfig

PUBLISHED = ConvAttnMoeConfig()
# The benchmark's cut of LFM2-8B-A1B: published layers 1-13, 8 of 32 experts
# and a quarter of the vocabulary held here.
CELL = ConvAttnMoeConfig(
    vocab_size=16384, layer_types=PUBLISHED.layer_types[1:14], n_dense_layers=1,
    held_experts=(0, 8),
)


@pytest.fixture(scope="module", autouse=True)
def leave_no_counters():
    """`fit` folds the `moe.*` counters into the process's aggregates; whoever
    runs next in this worker starts without them."""
    yield
    tracing.reset_aggregates()


def _count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def _batch(cfg, b=4, s=32, seed=0):
    x = np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, s), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


def test_short_conv_is_the_sum_over_its_taps_and_causal():
    """c[t] = sum_j w[j] z[t - (L - 1) + j] with zeros before the start, by an
    explicit loop over positions and taps; position 0 sees only the last tap;
    changing token t leaves every output before t alone."""
    S, d, L = 9, 5, 3
    z = np.asarray(jax.random.normal(jax.random.key(0), (2, S, d), jnp.float32))
    w = np.asarray(jax.random.normal(jax.random.key(1), (L, d), jnp.float32))
    want = np.zeros_like(z)
    for t in range(S):
        for j in range(L):
            if t - (L - 1) + j >= 0:
                want[:, t] += w[j] * z[:, t - (L - 1) + j]
    got = np.asarray(conv_attn_moe.short_conv(jnp.asarray(z), jnp.asarray(w)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], w[L - 1] * z[:, 0], rtol=1e-6)
    z2 = z.copy()
    z2[:, 4] += 1.0
    got2 = np.asarray(conv_attn_moe.short_conv(jnp.asarray(z2), jnp.asarray(w)))
    np.testing.assert_array_equal(got2[:, :4], got[:, :4])
    assert np.all(got2[:, 4:7] != got[:, 4:7])  # t, t + 1, t + 2 and no further
    np.testing.assert_array_equal(got2[:, 7:], got[:, 7:])


def test_a_token_changes_nothing_before_it_in_the_whole_model():
    cfg = ConvAttnMoeConfig.tiny()
    params = conv_attn_moe.init_params(cfg, jax.random.key(0))
    x, _ = _batch(cfg, b=2, s=16)
    x2 = x.copy()
    x2[:, 9] = (x2[:, 9] + 5) % cfg.vocab_size
    run = jax.jit(lambda t: conv_attn_moe.logits(cfg, params, t)["main"])
    a, b = np.asarray(run(x)), np.asarray(run(x2))
    np.testing.assert_array_equal(a[:, :9], b[:, :9])
    assert np.abs(a[:, 9:] - b[:, 9:]).max() > 1e-3


def test_the_published_layer_list_constructs_and_counts_what_the_widths_give():
    """24 layers, 18 conv and 6 attention, two dense: 8.3 B parameters whole
    with one table (shapes only), and ISSUE 31's arithmetic for the cell."""
    assert PUBLISHED.n_layers == 24 and PUBLISHED.layer_types.count("full_attention") == 6
    assert PUBLISHED.kinds[:3] == (("conv", False), ("conv", False), ("full_attention", True))
    assert [n for _, n in PUBLISHED.runs] == [2, 1, 3, 1, 3, 1, 3, 1, 3, 1, 2, 1, 2]
    assert round(conv_attn_moe.param_count(PUBLISHED) / 1e9, 2) == 8.34
    shapes = jax.eval_shape(lambda: conv_attn_moe.init_params(CELL, jax.random.key(0)))
    one = lambda stack: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stack
    )
    dense_conv, routed_attn, routed_conv = (one(shapes["runs"][i]) for i in (0, 1, 2))
    conv = {k: v for k, v in routed_conv.items() if k.startswith("conv_")}
    assert _count(conv) == 2048 * 6144 + 3 * 2048 + 2048 * 2048 == 16_783_360
    attn = {k: v for k, v in routed_attn.items() if k[0] in "wqk"}
    assert _count(attn) == 2 * 2048**2 + 2 * 2048 * 512 + 128 == 10_485_888
    assert _count({k: routed_conv["moe"][k] for k in ("w_gate", "w_up", "w_down")}) == 8 * 11_010_048
    assert round(_count(routed_conv) / 1e6, 1) == 104.9
    assert round(_count(routed_attn) / 1e6, 1) == 98.6
    assert round(_count(dense_conv) / 1e6, 1) == 60.8
    assert _count(shapes["embed"]) == 16384 * 2048 and "output" not in shapes
    assert [n for _, n in CELL.runs] == [1, 1, 3, 1, 3, 1, 3]
    assert round(conv_attn_moe.param_count(CELL) / 1e6, 1) == 1334.7
    assert round(conv_attn_moe.train_flops_per_token(CELL, 8192) / 1e9, 2) == 2.76


def test_the_published_pattern_runs_a_step_at_toy_widths():
    cfg = ConvAttnMoeConfig.tiny(layer_types=PUBLISHED.layer_types, n_dense_layers=2)
    assert len(cfg.runs) == 13
    params = conv_attn_moe.init_params(cfg, jax.random.key(0))
    x, y = _batch(cfg, b=2, s=16)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(lambda p: conv_attn_moe.lm_loss(cfg, p, x, y), has_aux=True)
    )(params)
    assert np.isfinite(float(loss))
    assert int(metrics["counters"]["moe.assignments"]) == 22 * 32 * cfg.top_k
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree_util.tree_leaves(grads))
    # the table is tied: its gradient has the head's part, a row for every token or not
    assert float(jnp.min(jnp.sum(jnp.abs(grads["embed"]), axis=-1))) > 0


@pytest.mark.parametrize(
    "cfg",
    [ConvAttnMoeConfig.tiny(), ConvAttnMoeConfig.tiny(layer_types=("full_attention", "conv"), n_dense_layers=0),
     ConvAttnMoeConfig.tiny(layer_types=("conv", "full_attention"), n_dense_layers=2)],
    ids=["dense+routed", "routed-only", "dense-only"],
)
def test_specs_mirror_the_parameter_tree(cfg):
    params = jax.eval_shape(lambda: conv_attn_moe.init_params(cfg, jax.random.key(0)))
    specs = conv_attn_moe.param_specs(cfg)
    is_spec = lambda x: isinstance(x, P)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)
    )
    for p, s in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(specs, is_leaf=is_spec)):
        assert len(s) == p.ndim, (p.shape, s)
    for run, ((_, routed), n) in zip(params["runs"], cfg.runs):
        assert run["operator_norm"].shape == (n, cfg.dim)
        if routed:
            assert run["moe"]["router"].dtype == jnp.float32
            assert run["moe"]["router_bias"].shape == (n, 8)
            assert "shared_gate" not in run["moe"]


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        ConvAttnMoeConfig.tiny(layer_types=("conv", "linear_attention"))
    with pytest.raises(ValueError, match="layer_types"):
        ConvAttnMoeConfig.tiny(layer_types=())
    with pytest.raises(ValueError, match="n_dense_layers"):
        ConvAttnMoeConfig.tiny(n_dense_layers=6)
    with pytest.raises(ValueError, match="heads"):
        ConvAttnMoeConfig.tiny(n_kv_heads=3)
    # LFM2's router: epsilon 1e-6, scale 1, no shared expert
    routed = ConvAttnMoeConfig.tiny().routed
    assert (routed.renormalize_eps, routed.scale, routed.shared_dim) == (1e-6, 1.0, 0)
    assert CELL.routed.span == (0, 8) and CELL.routed.buffer_rows(16384) == 65536


def test_fit_trains_and_folds_the_routing_counters_at_the_log_seam():
    cfg = ConvAttnMoeConfig.tiny()
    mesh = build_mesh(MeshSpec.fsdp_parallel(1), jax.devices()[:1])
    trainer = conv_attn_moe.make_trainer(
        cfg, mesh,
        TrainerConfig(strategy="fsdp", optimizer="adamw", learning_rate=1e-2, log_every=2),
    )
    x, y = _batch(cfg)
    state = trainer.init(jax.random.key(0), x)
    bias0 = [np.asarray(r["moe"]["router_bias"]) for r in state.params["runs"][1:]]
    tracing.reset_aggregates()
    state, losses = trainer.fit(state, (Batch(x, y) for _ in range(7)), steps=7)
    assert losses[-1] < losses[0]
    counted = {k: v for k, v in tracing.counters().items() if k.startswith("moe.")}
    assert set(counted) == {
        "moe.assignments", "moe.assignments_held", "moe.rows_run", "moe.slots_read",
        "moe.expert_load_max", "moe.expert_load_mean", "moe.dropped",
    }
    assert all(v["count"] == 7 for v in counted.values())  # the odd last step too
    blocks, tokens = 4, 4 * 32  # the attention layer and three conv layers route
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
    assert counted["moe.expert_load_mean"]["total"] == pytest.approx(
        counted["moe.assignments_held"]["total"] / (blocks * cfg.held_experts[1])
    )
    # The selection bias is a buffer: no gradient, no decay, it stays.
    for before, run in zip(bias0, state.params["runs"][1:]):
        np.testing.assert_array_equal(np.asarray(run["moe"]["router_bias"]), before)
    assert "counters" not in trainer.evaluate(state, [Batch(x, y)])


def test_attention_core_dispatch_and_the_blocks_scopes():
    """Flash on a TPU at and above the crossover, XLA elsewhere; and every
    scope ISSUE 31 names is on the lowered step's operations."""
    from deeplearning_cfn_tpu.models.llama import attention_kind

    assert attention_kind(CELL, None, 8192, backend="tpu") == "flash"
    assert attention_kind(CELL, None, 1024, backend="tpu") == "xla"
    assert attention_kind(CELL, None, 8192, backend="cpu") == "xla"
    assert CELL.head_dim == 64
    cfg = dataclasses.replace(ConvAttnMoeConfig.tiny(), remat=True)
    params = conv_attn_moe.init_params(cfg, jax.random.key(0))
    x, y = _batch(cfg, b=2, s=16)
    lowered = jax.jit(jax.grad(lambda p: conv_attn_moe.lm_loss(cfg, p, x, y)[0])).lower(params)
    # The serialized HLO module holds every operation's `op_name` whole; a scope
    # stands there as `benchmarks/scope_reduce.py` finds it in a trace.
    import re

    from benchmarks.scope_reduce import has_scope

    text = lowered.compiler_ir(dialect="hlo").as_serialized_hlo_module_proto().decode("latin-1")
    ops = set(re.findall(r"[\x20-\x7e]{4,}", text))  # a called computation's names are relative
    for scope in ("embed", "operator_norm", "conv/in", "conv/core", "conv/out", "attn/qkv",
                  "attn/qk_norm", "attn/rope", "attn/core", "attn/out", "ffn_norm", "mlp",
                  "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "final_norm",
                  "head", "xent", "rematted_computation"):
        parts = scope.split("/")
        assert any(all(has_scope(op, p) for p in parts) for op in ops), scope


def test_the_example_runs_from_its_template(tmp_path, monkeypatch):
    """`dlcfn run templates/conv-attn-moe-stage.json` at the tiny size: template
    -> provision -> launch plan -> examples.conv_attn_moe_train -> Trainer.fit."""
    import contextlib
    import io
    import json
    from pathlib import Path

    from deeplearning_cfn_tpu import cli

    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    tracing.reset_aggregates()  # the example reports the process's counters
    template = Path(__file__).resolve().parents[1] / "templates" / "conv-attn-moe-stage.json"
    argv = ["run", str(template)]
    for name, value in (("Size", "tiny"), ("SeqLen", 32), ("Batch", 8), ("Steps", 6)):
        argv += ["-P", f"{name}={value}"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert cli.main(argv) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])["result"]
    assert result["steps"] == 6 and np.isfinite(result["final_loss"])
    assert result["experts_held"] == [0, 4] and result["attention"] == "xla"
    assert result["layers"] == {"conv": 4, "full_attention": 1, "dense": 1}
    assert result["routing"]["moe.dropped"] == 0.0
    assert result["routing"]["moe.assignments"] == 4 * 8 * 32 * 2
