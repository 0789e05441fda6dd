"""`models/looped_decoder.py` and what it asked of `models/llama.py`: the block
with and without its two post-norms by hand, the tie to `llama.causal_lm_loss`
at one pass, the tie of the loop to an untied stack four times as deep,
`exit_mix` by hand, one pass's logits alive at a time, the counts, and a step
of the trainer with the scopes and counters the per-layer metrics read.  Model
against reference is tests/benchmark_tests/test_benchmark_looped_decoder.py."""

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import llama
from deeplearning_cfn_tpu.models import looped_decoder as model
from deeplearning_cfn_tpu.ops.attention import dot_product_attention, rms_norm, rotary_embedding

HIGHEST = partial(jax.default_matmul_precision, "highest")


def tiny(passes=4, beta=0.05, **kw):
    return model.LoopedDecoderConfig.tiny(passes, beta, dtype=jnp.float32, **kw)


def batch(cfg, rows=2, length=16, seed=1):
    x = jax.random.randint(jax.random.key(seed), (rows, length), 0, cfg.decoder.vocab_size)
    return x, jnp.roll(x, -1, axis=1)


def own_batch(q, k, v):
    return dot_product_attention(q, k, v, causal=True), None


def one_layer(params, i):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


# --- the block ----------------------------------------------------------------------


@pytest.mark.parametrize("sandwich", [True, False], ids=["four-norms", "two-norms"])
def test_the_block_by_hand_with_and_without_the_two_post_norm_leaves(sandwich):
    """The leaves are the switch: a layer that holds `attn_post_norm` and
    `mlp_post_norm` normalises what the mixer and the feed-forward give before
    they join the stream; a layer without them is llama's block."""
    cfg = tiny()
    dec = cfg.decoder
    params = model.init_params(cfg, jax.random.key(0))
    lp = one_layer(params, 1)
    # weights that are not ones, so that a norm left out or put elsewhere shows
    for n, name in enumerate(model.POST_NORMS):
        lp[name] = 1.0 + 0.3 * jax.random.normal(jax.random.key(7 + n), (dec.dim,))
    if not sandwich:
        lp = {k: v for k, v in lp.items() if k not in model.POST_NORMS}
    x = jax.random.normal(jax.random.key(2), (2, 12, dec.dim), jnp.float32)
    positions = jnp.arange(12, dtype=jnp.int32)
    B, S, hd = 2, 12, dec.head_dim
    with HIGHEST():
        got, aux, carried = llama.decoder_block(dec, own_batch, x, lp, positions)
        h = rms_norm(x, lp["attn_norm"], dec.norm_eps)
        q = rotary_embedding((h @ lp["wq"]).reshape(B, S, dec.n_heads, hd), positions, dec.rope_theta)
        k = rotary_embedding((h @ lp["wk"]).reshape(B, S, dec.n_kv_heads, hd), positions, dec.rope_theta)
        v = (h @ lp["wv"]).reshape(B, S, dec.n_kv_heads, hd)
        a = dot_product_attention(q, k, v, causal=True).reshape(B, S, -1) @ lp["wo"]
        if sandwich:
            a = rms_norm(a, lp["attn_post_norm"], dec.norm_eps)
        y = x + a
        h = rms_norm(y, lp["mlp_norm"], dec.norm_eps)
        m = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        if sandwich:
            m = rms_norm(m, lp["mlp_post_norm"], dec.norm_eps)
        want = y + m
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    assert float(aux) == 0.0 and carried is None
    if sandwich:  # what joins the stream has the norm's weight as its scale, whatever `wo` is
        joined = np.asarray(y - x) / np.asarray(lp["attn_post_norm"])
        np.testing.assert_allclose(np.sqrt(np.mean(joined**2, axis=-1)), 1.0, atol=1e-3)


def test_the_tree_holds_the_two_norms_a_layer_and_the_gate_and_llamas_holds_neither():
    cfg = tiny()
    dec = cfg.decoder
    params = model.init_params(cfg, jax.random.key(0))
    plain = llama.init_params(dec, jax.random.key(0))
    assert set(params) - set(plain) == {"exit_gate_w", "exit_gate_b"}
    assert set(params["layers"]) - set(plain["layers"]) == set(model.POST_NORMS)
    for name in model.POST_NORMS:
        assert params["layers"][name].shape == (dec.n_layers, dec.dim)
        assert np.all(np.asarray(params["layers"][name]) == 1.0)
    assert params["exit_gate_w"].shape == (dec.dim,) and params["exit_gate_b"].shape == ()
    assert params["exit_gate_w"].dtype == params["exit_gate_b"].dtype == jnp.float32
    assert float(params["exit_gate_b"]) == 0.0
    specs = model.param_specs(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    ) == jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda _: 0, params))
    with pytest.raises(ValueError, match="dense, untied"):
        model.LoopedDecoderConfig(llama.LlamaConfig.tiny())  # tied
    with pytest.raises(ValueError, match="at least once"):
        tiny(passes=0)


# --- the ties ------------------------------------------------------------------------


def test_one_pass_no_entropy_term_and_no_post_norms_is_llamas_causal_lm_loss():
    """The tie to the code that is there: with `passes` 1, beta 0 and the two
    post-norm leaves taken out of the tree, the loss and the gradient of every
    leaf llama has are `llama.causal_lm_loss`'s on the same weights; the gate,
    which a single pass never reads, gets none."""
    cfg = tiny(passes=1, beta=0.0)
    dec = cfg.decoder
    params = model.init_params(cfg, jax.random.key(3))
    for name in model.POST_NORMS:
        del params["layers"][name]
    x, y = batch(cfg)
    with HIGHEST():
        (got, metrics), g = jax.value_and_grad(
            lambda p: model.lm_loss(cfg, p, x, y), has_aux=True)(params)
        (want, theirs), w = jax.value_and_grad(
            lambda p: llama.causal_lm_loss(dec, p, x, y), has_aux=True)(
            {k: v for k, v in params.items() if not k.startswith("exit_gate")})
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(metrics["perplexity"]) == pytest.approx(float(theirs["perplexity"]), rel=1e-5)
    assert float(jnp.max(jnp.abs(g.pop("exit_gate_w")))) == 0.0 and float(g.pop("exit_gate_b")) == 0.0
    assert jax.tree_util.tree_structure(g) == jax.tree_util.tree_structure(w)
    for path, a in jax.tree_util.tree_leaves_with_path(g):
        b = dict(jax.tree_util.tree_leaves_with_path(w))[path]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-5, err_msg=str(path))
    assert float(metrics["counters"]["loop.exit_mass.1"]) == pytest.approx(1.0)
    assert float(metrics["counters"]["loop.exit_entropy"]) == 0.0


def test_a_shared_layers_gradient_is_the_sum_over_its_four_copies_in_an_untied_stack():
    """The tie of the loop to the model: an untied stack of 4 L layers with the
    final norm, the head and the gate between the quarters, written here layer
    by layer, gives the looped model's loss when the quarters hold the same
    weights, and the gradient of a shared layer is the sum of the gradients of
    its four copies."""
    cfg = tiny(remat=True)
    dec = cfg.decoder
    params = model.init_params(cfg, jax.random.key(4))
    # post-norm weights that are not ones: their gradient sums over the copies too
    for n, name in enumerate(model.POST_NORMS):
        params["layers"][name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(20 + n), (dec.n_layers, dec.dim))
    x, y = batch(cfg)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    top = {k: v for k, v in params.items() if k not in ("embed", "layers")}
    mask = jnp.ones(y.shape, jnp.float32).at[:, -1].set(0.0)

    def untied(copies):  # every leaf [passes, L, ...]
        h = params["embed"][x]
        nll, z = [], []
        for t in range(cfg.passes):
            for i in range(dec.n_layers):
                lp = jax.tree_util.tree_map(lambda a: a[t, i], copies)
                h = llama.decoder_block(dec, own_batch, h, lp, positions)[0]
            h, _, l, g = model._pass_head(dec, top, h, y)
            nll.append(l)
            z.append(g)
        mixed, _, _ = model.exit_mix(jnp.stack(nll), jnp.stack(z), cfg.exit_beta)
        return jnp.sum(mixed * mask) / jnp.sum(mask)

    copies = jax.tree_util.tree_map(lambda a: jnp.stack([a] * cfg.passes), params["layers"])
    with HIGHEST():
        want_loss, by_copy = jax.jit(jax.value_and_grad(untied))(copies)
        got_loss, shared = jax.jit(jax.value_and_grad(lambda p: model.lm_loss(cfg, p, x, y)[0]))(params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for name, g in shared["layers"].items():
        summed = np.asarray(jnp.sum(by_copy[name], axis=0))
        scale = np.max(np.abs(summed))
        np.testing.assert_allclose(np.asarray(g), summed, atol=2e-5 * scale, err_msg=name)
        # and no copy's share is negligible: every pass carries weight
        assert all(np.max(np.abs(np.asarray(by_copy[name][t]))) > 1e-3 * scale for t in range(4)), name


# --- the objective ----------------------------------------------------------------------


def test_exit_mix_by_hand():
    # at z = 0 every gate takes half of what is left: p = (1/2, 1/4, 1/8, 1/8)
    nll = jnp.asarray([4.0, 3.0, 2.0, 1.0])[:, None]
    mixed, p, entropy = model.exit_mix(nll, jnp.zeros((4, 1)), 0.05)
    np.testing.assert_allclose(np.asarray(p[:, 0]), [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    h = 0.5 * math.log(2) + 0.25 * math.log(4) + 0.25 * math.log(8)
    assert float(entropy[0]) == pytest.approx(h, rel=1e-6)
    assert float(mixed[0]) == pytest.approx(2.0 + 0.75 + 0.25 + 0.125 - 0.05 * h, rel=1e-6)
    # any logits: p sums to one a token, the last pass takes what is left, the last logit is unread
    z = 3.0 * jax.random.normal(jax.random.key(0), (4, 5, 7))
    nll = jax.random.uniform(jax.random.key(1), (4, 5, 7), minval=1.0, maxval=6.0)
    mixed, p, entropy = model.exit_mix(nll, z, 0.1)
    lam = jax.nn.sigmoid(z[:3])
    left = jnp.cumprod(1.0 - lam, axis=0)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p[3]), np.asarray(left[2]), rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p[1]), np.asarray(lam[1] * left[0]), rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(mixed), np.asarray(jnp.sum(p * nll, 0) + 0.1 * jnp.sum(p * jnp.log(p), 0)), rtol=1e-4)
    again, _, _ = model.exit_mix(nll, z.at[3].set(1e3), 0.1)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(mixed))
    assert np.all(np.asarray(entropy) > 0) and np.all(np.asarray(entropy) <= math.log(4) + 1e-6)
    # one pass: nothing to mix
    alone, p1, h1 = model.exit_mix(nll[:1], z[:1], 0.1)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(nll[0]))
    assert np.all(np.asarray(p1) == 1.0) and np.all(np.asarray(h1) == 0.0)


@pytest.mark.parametrize("logit", [-1e4, -90.0, 90.0, 1e4], ids=lambda v: f"z={v:g}")
def test_the_entropys_gradient_is_finite_where_a_gate_has_saturated(logit):
    """lambda at 0 or 1 in float32: log p comes from log_sigmoid sums, so p log p
    is 0 and not 0 x inf, and the gradient of the objective is finite."""
    nll = jnp.asarray([4.0, 3.0, 2.0, 1.0])[:, None]

    def objective(z):
        return jnp.sum(model.exit_mix(nll, z, 0.05)[0])

    z = jnp.zeros((4, 1)).at[1].set(logit)
    value, grad = jax.value_and_grad(objective)(z)
    assert np.isfinite(float(value)) and np.all(np.isfinite(np.asarray(grad)))
    assert float(grad[3, 0]) == 0.0  # the last pass's logit is not read
    _, p, entropy = model.exit_mix(nll, z, 0.05)
    assert np.all(np.isfinite(np.asarray(p))) and np.isfinite(float(entropy[0]))
    assert float(jnp.sum(p)) == pytest.approx(1.0, rel=1e-6)


# --- what the step holds --------------------------------------------------------------------


def test_one_passs_logits_are_alive_at_a_time_in_the_lowered_step():
    """The pass scan's outputs are a loss and a gate logit a token; no array of
    every pass's logits [passes, B, S, V] exists in the loss and its gradient,
    while one pass's [B, S, V] does.  The inspection entry point, which asks for
    all of them, shows that the text would say so."""
    cfg = tiny(remat=True)
    params = model.init_params(cfg, jax.random.key(0))
    x, y = batch(cfg)  # 2 x 16 tokens over a vocabulary of 256, four passes
    step = jax.jit(jax.grad(lambda p: model.lm_loss(cfg, p, x, y)[0])).lower(params).as_text()
    assert "tensor<2x16x256xf32>" in step and "tensor<4x2x16xf32>" in step
    assert "4x2x16x256x" not in step
    every = jax.jit(lambda p: model.logits(cfg, p, x)["logits"]).lower(params).as_text()
    assert "tensor<4x2x16x256xf32>" in every
    out = model.logits(cfg, params, x)
    assert out["logits"].shape == (4, 2, 16, 256) and out["gate"].shape == (4, 2, 16)


def test_the_counts_by_hand():
    dec = llama.LlamaConfig(
        vocab_size=49152, dim=2048, n_layers=12, n_heads=16, n_kv_heads=16, mlp_dim=5632,
        tied_embeddings=False)
    cfg = model.LoopedDecoderConfig(dec, passes=4)
    block = 4 * 2048**2 + 3 * 2048 * 5632
    # ISSUE 43: a block 51.39 M with its four norms, 12 of them 616.7 M, table and head 201.3 M
    assert block + 4 * 2048 == 51_388_416
    assert model.param_count(cfg) == 12 * (block + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert model.param_count(cfg) == pytest.approx(818.0e6, rel=1e-3)
    per_token = model.train_flops_per_token(cfg, 8192)
    weights = 12 * block + 2048 * 49152 + 2048
    assert per_token == 4 * 6.0 * (weights + 12 * 2048 * 8192)
    # blocks 1.21e14, attention 4.0e13, heads 2.0e13: 1.81e14 a sequence of 8192
    assert per_token * 8192 == pytest.approx(1.81e14, rel=5e-3)
    once = model.LoopedDecoderConfig(dec, passes=1)
    assert model.train_flops_per_token(once, 8192) * 4 == per_token


# --- the trainer --------------------------------------------------------------------------


def test_three_steps_of_the_trainer_lower_the_loss_and_fold_the_loops_counters():
    from deeplearning_cfn_tpu.obs import tracing
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import Batch
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig, decay_mask

    cfg = tiny(remat=True)
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = model.make_trainer(cfg, mesh, TrainerConfig(
        strategy="fsdp", optimizer="adamw", learning_rate=3e-3, weight_decay=0.1, grad_clip_norm=1.0))
    x = np.random.default_rng(0).integers(0, 256, (len(jax.devices()), 20), dtype=np.int32)
    state = trainer.init(jax.random.key(0), x)
    tracing.reset_aggregates()
    try:
        state, losses = trainer.fit(state, iter([Batch(x, np.roll(x, -1, 1))] * 3), steps=3)
        counted = tracing.counters()
    finally:
        tracing.reset_aggregates()
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    # one observation a step: four passes, each pass's loss, where the gate puts its mass
    assert counted["loop.passes"] == {"count": 3, "total": 12.0}
    for t in (1, 2, 3, 4):
        assert counted[f"loop.loss.{t}"]["count"] == counted[f"loop.exit_mass.{t}"]["count"] == 3
        assert 0.0 < counted[f"loop.exit_mass.{t}"]["total"] / 3 < 1.0
        assert 3.0 < counted[f"loop.loss.{t}"]["total"] / 3 < 7.0  # near log 256 at the start
    assert sum(counted[f"loop.exit_mass.{t}"]["total"] for t in (1, 2, 3, 4)) == pytest.approx(3.0, rel=1e-5)
    assert 0.0 < counted["loop.exit_entropy"]["total"] / 3 <= math.log(4)
    # what AdamW decays: matrices, not the norms (the post-norms among them) nor the gate
    mask = decay_mask(state.params)
    assert mask["layers"]["wq"] and mask["layers"]["w_down"] and mask["embed"] and mask["output"]
    assert not any(mask["layers"][n] for n in ("attn_norm", "mlp_norm", *model.POST_NORMS))
    assert not (mask["final_norm"] or mask["exit_gate_w"] or mask["exit_gate_b"])
    # the scopes the per-layer metrics read, on the compiled step's operations: the HLO's
    # `op_name`s are what a profile's events carry
    tokens = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=trainer.batch_sharding)
    with jax.set_mesh(mesh):
        text = trainer.step_fn.lower(state, tokens, tokens).compile().as_text()
    from benchmarks.scope_reduce import has_scope

    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embed", "loop_pass", "attn_norm", "attn/qkv", "attn/rope", "attn/core", "attn/out",
                  "attn_post_norm", "mlp_norm", "mlp", "mlp_post_norm", "loop_head/final_norm",
                  "loop_head/head", "loop_head/xent", "loop_head/exit_gate", "exit_mix"):
        parts = scope.split("/")
        assert any(all(has_scope(n, part) for part in parts) for n in op_names), scope
    # a pass's layer scan is under `loop_pass`, forward, recomputed and backward; its head unit is not
    under_pass = [n for n in op_names if has_scope(n, "loop_pass")]
    assert any(has_scope(n, "mlp_post_norm") and "rematted_computation" in n for n in under_pass)
    assert any(has_scope(n, "attn_post_norm") and "transpose(" in n for n in under_pass)
    assert not any(has_scope(n, "loop_head") or has_scope(n, "exit_mix") for n in under_pass)
    # no new scope is another reader's after an underscore: `loop_head` is not `head`
    assert not has_scope("jit(train_step)/loss/loop_head/mul", "head")
    assert has_scope("jit(train_step)/loss/loop_head/head/dot_general", "head")
    assert not has_scope("jit(train_step)/loss/attn_post_norm/mul", "attn_norm")
    assert not has_scope("jit(train_step)/loss/while/body/loop_pass/mul", "loop")
