"""models/window_attn_moe.py: YaRN's frequencies and the half-rotated scaled
rotary against numbers written out by hand, the window's edge on a sliding
layer at the published 512 keys, the head-wise gate, the layer lists as data
(the published 40 layers construct, count 33.4 B parameters and the cell's
1,145.7 M), the specs against the tree, the routing statistics through
Trainer.fit, the attention core's dispatch with its scopes, and the example
from its template.  The model against the plain reference (logits, loss,
gradients, an AdamW step) is
tests/benchmark_tests/test_benchmark_window_attn_moe.py."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning_cfn_tpu.models import window_attn_moe
from deeplearning_cfn_tpu.models.window_attn_moe import (
    FULL_ROTARY,
    SLIDING_ROTARY,
    RotaryRule,
    WindowAttnMoeConfig,
)
from deeplearning_cfn_tpu.obs import tracing
from deeplearning_cfn_tpu.ops.attention import partial_rotary_embedding, rotary_embedding, yarn_inv_freq
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.data import Batch
from deeplearning_cfn_tpu.train.trainer import TrainerConfig

PUBLISHED = WindowAttnMoeConfig.published()
# The benchmark's cut of Laguna-XS.2: published layers 0-4, 64 of 256 experts
# and a quarter of the vocabulary held here.
CELL = WindowAttnMoeConfig(
    vocab_size=25088, layer_types=PUBLISHED.layer_types[:5],
    mlp_layer_types=PUBLISHED.mlp_layer_types[:5], heads_per_layer=PUBLISHED.heads_per_layer[:5],
    held_experts=(0, 64),
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


# --- the two rotary rules ---------------------------------------------------------


def test_yarn_frequencies_are_the_formulas_numbers_written_out():
    """ISSUE 33: dim 64, theta 500,000, factor 64, original 4,096, beta 64 / 1.
    ln theta = 13.1224; low = floor(64 ln(4096 / (64 * 2 pi)) / (2 ln theta)) =
    floor(5.66) = 5, high = ceil(64 ln(4096 / (2 pi)) / (2 ln theta)) =
    ceil(15.80) = 16.  i <= 5 keeps f_i, i >= 16 gets f_i / 64, between them
    the ramp (i - 5) / 11."""
    f = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert f.shape == (32,) and f.dtype == np.float32
    plain = lambda i: math.exp(-2 * i / 64 * math.log(500000.0))
    assert f[0] == 1.0
    assert f[5] == pytest.approx(plain(5), rel=1e-6) and plain(5) == pytest.approx(0.128687, rel=1e-5)
    # i = 6: f = 0.0853971, r = 1 / 11: f / 64 * r + f * (1 - r) = 0.00012130 + 0.07763373
    assert plain(6) == pytest.approx(0.0853971, rel=1e-5)
    assert f[6] == pytest.approx(0.0853971 / 64 / 11 + 0.0853971 * 10 / 11, rel=1e-5) == pytest.approx(
        0.0777550, rel=1e-5)
    # i = 15: r = 10 / 11
    assert f[15] == pytest.approx(plain(15) * (10 / 11 / 64 + 1 / 11), rel=1e-5)
    assert f[16] == pytest.approx(plain(16) / 64, rel=1e-6) == pytest.approx(2.20971e-05, rel=1e-5)
    assert f[31] == pytest.approx(plain(31) / 64, rel=1e-6)
    # the rule reads them off the head: half of 128 dimensions rotate
    np.testing.assert_array_equal(FULL_ROTARY.inv_freq(128), f)
    assert FULL_ROTARY.attention_factor == pytest.approx(0.1 * math.log(64.0) + 1.0, rel=1e-12)
    # the sliding layers' rule is plain rotary over the whole head, theta 10,000
    np.testing.assert_allclose(
        SLIDING_ROTARY.inv_freq(128), 10000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6
    )


def test_half_rotated_scaled_rotary_by_hand():
    """Position 3, one head of 8 with the first 4 dimensions rotating (two
    frequencies 1 and 0.5, cos and sin times 1.5): split halves within the
    rotating part, the other four dimensions pass through."""
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 1, 8)
    out = np.asarray(partial_rotary_embedding(
        jnp.tile(x, (1, 4, 1, 1)), jnp.arange(4), np.array([1.0, 0.5], np.float32), 1.5
    ))[0, 3, 0]
    c0, s0, c1, s1 = (1.5 * v for v in (math.cos(3.0), math.sin(3.0), math.cos(1.5), math.sin(1.5)))
    # halves (1, 2) and (3, 4): pairs (1, 3) at frequency 1 and (2, 4) at 0.5
    want = [1 * c0 - 3 * s0, 2 * c1 - 4 * s1, 3 * c0 + 1 * s0, 4 * c1 + 2 * s1, 5, 6, 7, 8]
    np.testing.assert_allclose(out, want, rtol=1e-6)
    # over the whole head at scale 1 it is the other decoders' rotary embedding
    q = jax.random.normal(jax.random.key(0), (2, 9, 3, 16), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(RotaryRule(theta=10000.0).rotate(q, jnp.arange(9))),
        np.asarray(rotary_embedding(q, jnp.arange(9), 10000.0)), rtol=1e-6, atol=1e-6,
    )


# --- the window and the gate ----------------------------------------------------


def _sliding_layer(window):
    cfg = WindowAttnMoeConfig.tiny(sliding_window=window)
    kind = ("sliding_attention", 8, True)
    lp = window_attn_moe._block_params(cfg, jax.random.key(2), kind)
    return cfg, kind, lp


def test_a_sliding_layers_output_at_t_sees_token_t_minus_511_and_none_before():
    """The published window: 512 keys with the token's own.  At t = 600 the
    output is unchanged when every token <= 88 = t - 512 is altered, and
    changes when token 89 = t - 511 is."""
    cfg, kind, lp = _sliding_layer(512)
    h = jax.random.normal(jax.random.key(3), (1, 640, cfg.dim), jnp.float32)
    mix = jax.jit(lambda h: window_attn_moe._attention_mixer(cfg, None, kind, lp, h, jnp.arange(640)))
    base = np.asarray(mix(h))
    behind = np.asarray(mix(h.at[:, :89].add(1.0)))
    np.testing.assert_array_equal(behind[:, 600], base[:, 600])
    assert np.abs(behind[:, 599] - base[:, 599]).max() > 1e-6  # t = 599 still sees token 88
    edge = np.asarray(mix(h.at[:, 89].add(1.0)))
    assert np.abs(edge[:, 600] - base[:, 600]).max() > 1e-6
    np.testing.assert_array_equal(edge[:, 601:], base[:, 601:])  # t = 601 no longer does
    # and a full layer at the same weights sees everything
    full = jax.jit(lambda h: window_attn_moe._attention_mixer(
        cfg, None, ("full_attention", 8, True), lp, h, jnp.arange(640)))
    assert np.abs(np.asarray(full(h.at[:, 0].add(1.0)))[:, 639] - np.asarray(full(h))[:, 639]).max() > 1e-7


def test_the_gate_scales_each_heads_output_before_the_projection():
    """sigmoid(h W_g) a head and token: a gate column driven far negative
    silences that head alone; without `gating` there is no such leaf."""
    cfg, kind, lp = _sliding_layer(6)
    h = jax.random.normal(jax.random.key(4), (2, 16, cfg.dim), jnp.float32)
    mix = lambda lp, cfg=cfg: window_attn_moe._attention_mixer(cfg, None, kind, lp, h, jnp.arange(16))
    plain = dataclasses.replace(cfg, gating=False)
    ungated = {k: v for k, v in lp.items() if k != "wg"}
    assert "wg" not in window_attn_moe._block_params(plain, jax.random.key(2), kind)
    # a zero gate matrix is a gate of one half everywhere
    np.testing.assert_allclose(
        np.asarray(mix({**lp, "wg": jnp.zeros_like(lp["wg"])})),
        0.5 * np.asarray(mix(ungated, plain)), rtol=1e-5, atol=1e-6,
    )
    # head 3 silenced is head 3's rows of the output projection zeroed, times the others' gates
    hd = cfg.head_dim
    silenced = mix({**lp, "wg": jnp.zeros_like(lp["wg"]).at[:, 3].set(-1e4 * jnp.sign(h[0, 0]))})
    # sign(h[0, 0]) only drives token (0, 0)'s gate surely negative
    without = mix({**ungated, "wo": lp["wo"].at[3 * hd : 4 * hd].set(0.0)}, plain)
    np.testing.assert_allclose(np.asarray(silenced)[0, 0], 0.5 * np.asarray(without)[0, 0],
                               rtol=1e-4, atol=1e-6)


def test_a_token_changes_nothing_before_it_in_the_whole_model():
    cfg = WindowAttnMoeConfig.tiny()
    params = window_attn_moe.init_params(cfg, jax.random.key(0))
    x, _ = _batch(cfg, b=2, s=16)
    x2 = x.copy()
    x2[:, 9] = (x2[:, 9] + 5) % cfg.vocab_size
    run = jax.jit(lambda t: window_attn_moe.logits(cfg, params, t)["main"])
    a, b = np.asarray(run(x)), np.asarray(run(x2))
    np.testing.assert_array_equal(a[:, :9], b[:, :9])
    assert np.abs(a[:, 9:] - b[:, 9:]).max() > 1e-3


# --- the layer lists as data ------------------------------------------------------


def test_the_published_lists_construct_and_count_what_the_widths_give():
    """40 layers, every fourth full with 48 heads, the first dense: 33.44 B
    parameters whole (shapes only; the published 33.4 B, which is the ground for
    a gate a head wide), and ISSUE 33's arithmetic for the cell."""
    assert PUBLISHED.n_layers == 40 and PUBLISHED.layer_types.count("full_attention") == 10
    assert PUBLISHED.kinds[:2] == (("full_attention", 48, False), ("sliding_attention", 64, True))
    assert PUBLISHED.kinds[4] == ("full_attention", 48, True)
    assert [n for _, n in PUBLISHED.runs] == [1, 3] + [1, 3] * 9
    assert round(window_attn_moe.param_count(PUBLISHED) / 1e9, 2) == 33.44
    shapes = jax.eval_shape(lambda: window_attn_moe.init_params(CELL, jax.random.key(0)))
    one = lambda stack: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stack
    )
    dense_full, routed_sliding, routed_full = (one(shapes["runs"][i]) for i in (0, 1, 2))
    attention = lambda block: {k: v for k, v in block.items() if k[0] == "w" and len(k) == 2}
    # q and o 2048 x 8192, k and v 2048 x 1024, the gate 2048 x 64
    assert _count(attention(routed_sliding)) == 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64 == 37_879_808
    # 48 heads: 2048 x 6144 twice, the same k and v, the gate 2048 x 48
    assert _count(attention(routed_full)) == 2 * 2048 * 6144 + 4_194_304 + 2048 * 48 == 29_458_432
    moe = routed_sliding["moe"]
    assert _count({k: moe[k] for k in ("w_gate", "w_up", "w_down")}) == 64 * 3 * 2048 * 512 == 201_326_592
    assert _count({k: moe[k] for k in ("shared_gate", "shared_up", "shared_down")}) == 3_145_728
    assert moe["router"].shape == (2048, 256) and moe["router_bias"].shape == (256,)
    assert round(_count(routed_sliding) / 1e6, 1) == 242.9
    assert round(_count(routed_full) / 1e6, 1) == 234.5
    assert round(_count(dense_full) / 1e6, 1) == 79.8
    assert _count(shapes["embed"]) == _count(shapes["output"]) == 25088 * 2048
    assert [n for _, n in CELL.runs] == [1, 3, 1]
    assert round(window_attn_moe.param_count(CELL) / 1e6, 1) == 1145.7
    assert CELL.routed.span == (0, 64) and CELL.routed.buffer_rows(16384) == 131072
    routed = CELL.routed
    assert (routed.renormalize_eps, routed.scale, routed.shared_dim, routed.top_k) == (1e-20, 2.5, 512, 8)


def test_flops_a_token_count_the_band_and_not_the_triangle_for_window_layers():
    """A window layer's scores a head: S W - W (W - 1) / 2 = 8192 x 512 -
    130,816 = 4,063,488, a full layer's S^2 / 2 = 33,554,432."""
    assert window_attn_moe.attended_keys(CELL, "sliding_attention", 8192) == 4_063_488
    assert window_attn_moe.attended_keys(CELL, "full_attention", 8192) == 33_554_432
    assert window_attn_moe.attended_keys(CELL, "sliding_attention", 300) == 300 * 301 / 2  # S < W
    scores = 3 * 2 * 2 * 128 * (2 * 48 * 33_554_432 + 3 * 64 * 4_063_488) / 8192
    weights = (window_attn_moe.train_flops_per_token(CELL, 8192) - scores) / 6
    # the head, five attentions with their gates, the dense SwiGLU, four routed layers: the
    # router, 8 x 64 / 256 = 2 held experts in expectation and the shared one
    assert weights == pytest.approx(
        2048 * 25088 + 3 * 37_879_808 + 2 * 29_458_432 + 3 * 2048 * 8192
        + 4 * (2048 * 256 + 3 * 3_145_728), rel=1e-12)


def test_the_published_pattern_runs_a_step_at_toy_widths():
    cfg = WindowAttnMoeConfig.tiny(
        layer_types=PUBLISHED.layer_types[:9], mlp_layer_types=PUBLISHED.mlp_layer_types[:9],
        heads_per_layer=(6, 8, 8, 8) * 2 + (6,),
    )
    assert len(cfg.runs) == 5
    params = window_attn_moe.init_params(cfg, jax.random.key(0))
    x, y = _batch(cfg, b=2, s=16)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(lambda p: window_attn_moe.lm_loss(cfg, p, x, y), has_aux=True)
    )(params)
    assert np.isfinite(float(loss))
    assert int(metrics["counters"]["moe.assignments"]) == 8 * 32 * cfg.top_k
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree_util.tree_leaves(grads))
    assert float(jnp.max(jnp.abs(grads["output"]))) > 0 and float(jnp.max(jnp.abs(grads["embed"]))) > 0


@pytest.mark.parametrize(
    "cfg",
    [WindowAttnMoeConfig.tiny(), WindowAttnMoeConfig.tiny(gating=False),
     WindowAttnMoeConfig.tiny(layer_types=("sliding_attention", "full_attention"),
                              mlp_layer_types=("dense", "dense"), heads_per_layer=(4, 2))],
    ids=["dense+routed", "ungated", "dense-only"],
)
def test_specs_mirror_the_parameter_tree(cfg):
    params = jax.eval_shape(lambda: window_attn_moe.init_params(cfg, jax.random.key(0)))
    specs = window_attn_moe.param_specs(cfg)
    is_spec = lambda x: isinstance(x, P)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)
    )
    for p, s in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(specs, is_leaf=is_spec)):
        assert len(s) == p.ndim, (p.shape, s)
    for run, ((_, heads, routed), n) in zip(params["runs"], cfg.runs):
        assert run["wq"].shape == (n, cfg.dim, heads * cfg.head_dim)
        assert ("wg" in run) == cfg.gating
        if routed:
            assert run["moe"]["router"].dtype == jnp.float32
            assert run["moe"]["shared_gate"].shape == (n, cfg.dim, cfg.shared_expert_dim)


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="one of"):
        WindowAttnMoeConfig.tiny(layer_types=("full_attention", "conv", "sliding_attention") + ("full_attention",) * 2)
    with pytest.raises(ValueError, match="name every layer"):
        WindowAttnMoeConfig.tiny(heads_per_layer=(6, 8))
    with pytest.raises(ValueError, match="name every layer"):
        WindowAttnMoeConfig.tiny(layer_types=(), mlp_layer_types=(), heads_per_layer=())
    with pytest.raises(ValueError, match="key/value heads"):
        WindowAttnMoeConfig.tiny(heads_per_layer=(6, 8, 8, 7, 6))


def test_fit_trains_and_folds_the_routing_counters_at_the_log_seam():
    cfg = WindowAttnMoeConfig.tiny()
    mesh = build_mesh(MeshSpec.fsdp_parallel(1), jax.devices()[:1])
    trainer = window_attn_moe.make_trainer(
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
    assert all(v["count"] == 7 for v in counted.values())
    blocks, tokens = 4, 4 * 32  # three window layers and the last full layer route
    assert counted["moe.assignments"]["total"] == 7 * blocks * tokens * cfg.top_k
    # the token-major gathers read a slot for each of a token's choices forward, and backward
    # for each that can be held here
    assert counted["moe.slots_read"]["total"] == 7 * blocks * tokens * (
        cfg.top_k + min(cfg.top_k, cfg.held_experts[1]))
    assert 0 < counted["moe.assignments_held"]["total"] < counted["moe.assignments"]["total"]
    assert counted["moe.dropped"]["total"] == 0
    for before, run in zip(bias0, state.params["runs"][1:]):  # the selection bias is a buffer
        np.testing.assert_array_equal(np.asarray(run["moe"]["router_bias"]), before)


def test_attention_core_dispatch_and_the_blocks_scopes():
    """Flash on a TPU at and above the crossover, XLA elsewhere, a window under
    a ring refused; and every scope ISSUE 33 names is on the lowered step's
    operations, a sliding layer's under `attn_window`."""
    from deeplearning_cfn_tpu.models.llama import attend, attention_kind

    assert attention_kind(CELL, None, 8192, backend="tpu") == "flash"
    assert attention_kind(CELL, None, 1024, backend="tpu") == "xla"
    assert attention_kind(CELL, None, 8192, backend="cpu") == "xla"
    q = jnp.zeros((1, 16, 8, 8))
    with pytest.raises(NotImplementedError, match="sliding window"):
        attend("ring", q, q[:, :, :2], q[:, :, :2], None, window=6)
    cfg = dataclasses.replace(WindowAttnMoeConfig.tiny(), remat=True)
    params = window_attn_moe.init_params(cfg, jax.random.key(0))
    x, y = _batch(cfg, b=2, s=16)
    lowered = jax.jit(jax.grad(lambda p: window_attn_moe.lm_loss(cfg, p, x, y)[0])).lower(params)
    import re

    from benchmarks.scope_reduce import has_scope

    text = lowered.compiler_ir(dialect="hlo").as_serialized_hlo_module_proto().decode("latin-1")
    ops = set(re.findall(r"[\x20-\x7e]{4,}", text))
    scopes = ["embed", "attn_norm", "mlp_norm", "mlp", "moe/router", "moe/dispatch", "moe/experts",
              "moe/combine", "moe/shared", "final_norm", "head", "xent", "rematted_computation"]
    scopes += [f"{a}/{part}" for a in ("attn", "attn_window")
               for part in ("qkv", "gate", "rope", "core", "out")]
    for scope in scopes:
        parts = scope.split("/")
        assert any(all(has_scope(op, p) for p in parts) for op in ops), scope


def test_the_flash_path_passes_the_window_and_the_full_layers_none(monkeypatch):
    """On a TPU the sliding layers call the kernel with `window=512`, the full
    layers with none: traced with the kernel replaced by a recorder."""
    from deeplearning_cfn_tpu.ops import pallas_attention

    seen = []

    def recorder(q, k, v, causal=True, mesh=None, interpret=False, window=None):
        seen.append((q.shape[2], k.shape[2], causal, window))
        return q

    monkeypatch.setattr(pallas_attention, "flash_attention", recorder)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = WindowAttnMoeConfig.tiny(sliding_window=512, max_seq_len=4096)
    params = jax.eval_shape(lambda: window_attn_moe.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
    jax.eval_shape(lambda p, t: window_attn_moe.logits(cfg, p, t)["main"], params, tokens)
    assert seen == [(6, 2, True, None), (8, 2, True, 512), (6, 2, True, None)]  # one trace a run


def test_the_example_runs_from_its_template(tmp_path, monkeypatch):
    """`dlcfn run templates/window-attn-moe-stage.json` at the tiny size: template
    -> provision -> launch plan -> examples.window_attn_moe_train -> Trainer.fit."""
    import contextlib
    import io
    import json
    from pathlib import Path

    from deeplearning_cfn_tpu import cli

    monkeypatch.setenv("DLCFN_ROOT", str(tmp_path / "root"))
    tracing.reset_aggregates()  # the example reports the process's counters
    template = Path(__file__).resolve().parents[1] / "templates" / "window-attn-moe-stage.json"
    argv = ["run", str(template)]
    for name, value in (("Size", "tiny"), ("SeqLen", 32), ("Batch", 8), ("Steps", 6)):
        argv += ["-P", f"{name}={value}"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert cli.main(argv) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])["result"]
    assert result["steps"] == 6 and np.isfinite(result["final_loss"])
    assert result["experts_held"] == [0, 4] and result["attention"] == "xla"
    assert result["layers"] == {"full_attention": 2, "sliding_attention": 3, "dense": 1}
    assert result["routing"]["moe.dropped"] == 0.0
    assert result["routing"]["moe.assignments"] == 4 * 8 * 32 * 2


def test_the_stage_size_is_the_benchmarks_cut():
    from deeplearning_cfn_tpu.examples import window_attn_moe_train

    args = window_attn_moe_train.base_parser("").parse_args([])
    for name, value in (("size", "stage"), ("first_layer", 0), ("layers", 5), ("experts_held", 64),
                        ("rank", 0), ("vocab_rows", 25088), ("seq_len", 8192)):
        setattr(args, name, value)
    assert window_attn_moe_train.size_config(args) == CELL
