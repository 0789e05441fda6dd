"""Pipeline parallelism (parallel/pipeline.py + Llama pp integration).

Strategy per SURVEY §4: virtual 8-device CPU mesh; assert the pipelined
program is numerically identical to the sequential one (forward AND
gradients), then that a pipelined train step runs and learns.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import llama
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.parallel.pipeline import (
    PipelineError,
    microbatch,
    pipeline_apply,
    stack_stages,
)
from deeplearning_cfn_tpu.train.trainer import TrainerConfig


def _toy(L=8, D=16, seed=0):
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.standard_normal((L, D, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
    return W, x


def _seq_forward(W, x):
    def body(x, w):
        return jnp.tanh(x @ w), None

    out, _ = jax.lax.scan(body, x, W)
    return out


def test_pipeline_matches_sequential_forward_and_grad():
    mesh = build_mesh(MeshSpec(dp=2, pp=4), jax.devices()[:8])
    W, x = _toy()
    Ws = stack_stages(W, 4)

    def stage_fn(lw, act):
        def body(a, w):
            return jnp.tanh(a @ w), None

        out, _ = jax.lax.scan(body, act, lw)
        return out, jnp.zeros((), jnp.float32)

    def pipe(Ws, x):
        out, _ = pipeline_apply(stage_fn, Ws, x, mesh, n_microbatches=4)
        return out

    with jax.set_mesh(mesh):
        ref = jax.jit(_seq_forward)(W, x)
        got = jax.jit(pipe)(Ws, x)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-5)

        g_ref = jax.jit(jax.grad(lambda W, x: _seq_forward(W, x).sum()))(W, x)
        g_pipe = jax.jit(jax.grad(lambda Ws, x: pipe(Ws, x).sum()))(Ws, x)
        np.testing.assert_allclose(
            np.asarray(g_ref),
            np.asarray(g_pipe).reshape(g_ref.shape),
            atol=1e-4,
        )


def test_pipeline_aux_masked_over_bubbles():
    """Aux from warm-up/drain ticks (garbage activations) must not leak in:
    a stage_fn with aux == sum over the activation would differ if bubble
    ticks contributed."""
    mesh = build_mesh(MeshSpec(pp=4, dp=2), jax.devices()[:8])
    W, x = _toy()
    Ws = stack_stages(W, 4)

    def stage_fn(lw, act):
        def body(a, w):
            return jnp.tanh(a @ w), None

        out, _ = jax.lax.scan(body, act, lw)
        return out, jnp.sum(out.astype(jnp.float32))

    with jax.set_mesh(mesh):
        out, aux = jax.jit(
            lambda Ws, x: pipeline_apply(stage_fn, Ws, x, mesh, n_microbatches=4)
        )(Ws, x)

    # Sequential reference: aux = sum of every stage's output over the real
    # microbatches only, averaged over the M=4 microbatches (pipeline_apply
    # keeps per-invocation-mean aux terms at unpipelined scale).
    acts = x
    expect = 0.0
    for s in range(4):
        acts = _seq_forward(W[s * 2 : (s + 1) * 2], acts)
        expect += float(jnp.sum(acts))
    assert np.isclose(float(aux), expect / 4, rtol=1e-4)


def test_microbatch_and_stacking_validation():
    W, x = _toy()
    with pytest.raises(PipelineError):
        microbatch(x, 3)  # 8 % 3 != 0
    with pytest.raises(PipelineError):
        stack_stages(W, 3)  # 8 layers % 3 != 0


def test_llama_pp_matches_single_device():
    """Tiny Llama, pp=2 x dp=2 x tp=2 pipeline vs the sequential stack —
    same weights (stage stacking is a reshape), same logits."""
    # f32: bf16 reduction-order noise across layouts is ~3e-2, which would
    # mask real routing bugs.
    cfg_seq = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, seq_len=16), dtype=jnp.float32
    )
    cfg_pp = dataclasses.replace(cfg_seq, pp_stages=2, pp_microbatches=2)
    mesh = build_mesh(MeshSpec(dp=2, pp=2, tp=2), jax.devices()[:8])

    params_seq = llama.init_params(cfg_seq, jax.random.key(0))
    params_pp = llama.init_params(cfg_pp, jax.random.key(0))
    # Stage stacking must be a pure reshape of the same initialization.
    np.testing.assert_array_equal(
        np.asarray(params_seq["layers"]["wq"]),
        np.asarray(params_pp["layers"]["wq"]).reshape(
            params_seq["layers"]["wq"].shape
        ),
    )

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(4, 16)), jnp.int32
    )
    logits_seq = llama.forward(cfg_seq, params_seq, tokens)
    with jax.set_mesh(mesh):
        logits_pp = jax.jit(
            lambda p, t: llama.forward(cfg_pp, p, t, mesh)
        )(params_pp, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_seq), np.asarray(logits_pp), atol=1e-4
    )


def test_llama_pp_trainer_learns():
    cfg = llama.LlamaConfig.tiny(vocab_size=32, seq_len=8)
    cfg = dataclasses.replace(cfg, pp_stages=2, pp_microbatches=2)
    mesh = build_mesh(MeshSpec(dp=2, pp=2, fsdp=2), jax.devices()[:8])
    trainer = llama.make_trainer(
        cfg, mesh, TrainerConfig(strategy="fsdp", optimizer="adamw", learning_rate=1e-2)
    )
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 32, size=(8, 8), dtype=np.int32)
    x = jax.device_put(jnp.asarray(tokens), trainer.batch_sharding)
    y = jax.device_put(jnp.asarray(np.roll(tokens, -1, 1)), trainer.batch_sharding)
    state = trainer.init(jax.random.key(0), x)
    losses = []
    for _ in range(10):
        state, metrics = trainer.train_step(state, x, y)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_llama_pp_without_pp_mesh_falls_back():
    """Stage-stacked params on a non-pp mesh run sequentially (single-host
    debug path)."""
    cfg = llama.LlamaConfig.tiny(vocab_size=32, seq_len=8)
    cfg = dataclasses.replace(cfg, pp_stages=2)
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, size=(2, 8)), jnp.int32
    )
    logits = llama.forward(cfg, params, tokens)
    assert logits.shape == (2, 8, 32)
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_pp_config_validation():
    with pytest.raises(ValueError):
        llama.LlamaConfig.tiny(pp_stages=3)  # 2 layers % 3
    with pytest.raises(ValueError):
        dataclasses.replace(
            llama.LlamaConfig.tiny(), pp_stages=2, use_ring_attention=True
        )
    with pytest.raises(ValueError):
        llama.LlamaConfig.tiny_moe(n_experts=1)  # default top_k=2 > 1


def test_llama_pp_moe_aux_scale_matches_sequential():
    """Regression: the MoE load-balancing aux must not scale with
    pp_microbatches (it is a per-invocation mean; the pipeline averages)."""
    cfg_seq = dataclasses.replace(
        llama.LlamaConfig.tiny_moe(vocab_size=64, seq_len=16),
        dtype=jnp.float32,
        moe_capacity_factor=4.0,  # generous capacity: no dropped tokens
    )
    cfg_pp = dataclasses.replace(cfg_seq, pp_stages=2, pp_microbatches=4)
    mesh = build_mesh(MeshSpec(dp=2, pp=2, ep=2), jax.devices()[:8])
    params_seq = llama.init_params(cfg_seq, jax.random.key(0))
    params_pp = llama.init_params(cfg_pp, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(8, 16)), jnp.int32
    )
    _, aux_seq = llama.forward_with_aux(cfg_seq, params_seq, tokens)
    with jax.set_mesh(mesh):
        _, aux_pp = jax.jit(
            lambda p, t: llama.forward_with_aux(cfg_pp, p, t, mesh)
        )(params_pp, tokens)
    # Microbatch means over 1/4 of the batch differ slightly from the
    # full-batch mean; scale must match (a sum bug would give ~4x).
    assert float(aux_pp) == pytest.approx(float(aux_seq), rel=0.25)


def test_stage_count_must_match_mesh_pp():
    """Regression: 4 stages on a pp=2 mesh would shard cleanly and then
    silently drop stage blocks 1 and 3."""
    mesh = build_mesh(MeshSpec(dp=4, pp=2), jax.devices()[:8])
    W, x = _toy()
    Ws = stack_stages(W, 4)

    def stage_fn(lw, act):
        return act, jnp.zeros((), jnp.float32)

    with pytest.raises(PipelineError, match="stages"):
        with jax.set_mesh(mesh):
            pipeline_apply(stage_fn, Ws, x, mesh, n_microbatches=4)
