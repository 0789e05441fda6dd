"""`llama.remat_keeps`: a rematerialised block keeps its full-causal flash
call's `out` and `lse` by the names `ops/pallas_attention._core_fwd` gives
them, so the quadratic forward kernel is traced once a block and not again
for the backward pass; a windowed call's pair is named and not kept.  Read
from jaxprs and from the interpreter: nothing here needs a chip, and times
come from the chip alone (PERF.md section 6, PR 39)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # public only as print_saved_residuals

from deeplearning_cfn_tpu.models import llama, window_attn_moe
from deeplearning_cfn_tpu.ops import pallas_attention
from tests.kernel_text import kernel_calls, named

DOTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
TOKENS = jax.ShapeDtypeStruct((2, 2048), jnp.int32)  # the flash crossover's length


@pytest.fixture
def on_a_tpu(monkeypatch):
    """`attention_kind` answers "flash" to a TPU backend alone; tracing needs none."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _llama_gradient(policy: str, mesh=None):
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, seq_len=2048),
        remat=True, remat_policy=policy, use_flash_attention=True,
    )
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    loss = lambda p, t: llama.causal_lm_loss(cfg, p, t, t, mesh)[0]
    return jax.make_jaxpr(jax.grad(loss))(params, TOKENS).jaxpr


def test_a_block_runs_the_full_causal_forward_kernel_once_and_the_windowed_one_twice(on_a_tpu):
    """The window-and-full decoder has both kinds of call in one stack: two
    runs of a full layer and one of sliding layers, each a scan over its
    blocks.  `jax.grad` has applied the policy by the time the jaxpr is made."""
    cfg = window_attn_moe.WindowAttnMoeConfig.tiny(
        sliding_window=512, max_seq_len=4096, remat=True
    )
    assert [kind[0] for kind, _ in cfg.runs] == [
        "full_attention", "sliding_attention", "full_attention"
    ]
    params = jax.eval_shape(lambda: window_attn_moe.init_params(cfg, jax.random.key(0)))
    loss = lambda p, t: window_attn_moe.lm_loss(cfg, p, t, t)[0]
    calls = kernel_calls(jax.make_jaxpr(jax.grad(loss))(params, TOKENS).jaxpr)
    assert calls["_flash_forward"] == 2  # once a run: the parent traced it twice a run
    assert calls["_window_flash_forward_band"] == 2  # forward, and again for the backward
    # a full-causal backward pass is the fused kernel, a windowed one the pair
    assert calls["_flash_backward_fused"] == 2
    assert calls["_flash_backward_dkv"] == calls["_flash_backward_dq"] == 0
    assert calls["_window_flash_backward_dkv"] == calls["_window_flash_backward_dq"] == 1


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_llamas_block_keeps_the_pair_under_both_policies(policy, on_a_tpu):
    calls = kernel_calls(_llama_gradient(policy))
    assert calls == {"_flash_forward": 1, "_flash_backward_fused": 1}


def test_the_pair_survives_shard_map_on_a_dp2_mesh(on_a_tpu):
    """Under a mesh the kernel runs inside `shard_map`; the names are given
    inside it too, and the policy outside still finds them."""
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh, virtual_cpu_devices

    mesh = build_mesh(MeshSpec(dp=2), virtual_cpu_devices(2))
    with jax.set_mesh(mesh):
        jaxpr = _llama_gradient("full", mesh)
    assert len(named(jaxpr, "shard_map")) >= 2  # forward and backward
    assert kernel_calls(jaxpr)["_flash_forward"] == 1


def _block(window, interpret=True):
    """A block of the decoders' shape at toy size: projections, the flash
    call, an output projection and a residual, on x [B, S, d]."""
    heads, head_dim = 2, 16

    def block(x, w):
        b, s, _ = x.shape
        q, k, v = ((x @ w[n]).reshape(b, s, heads, head_dim) for n in ("q", "k", "v"))
        attn = pallas_attention.flash_attention(
            q, k, v, block_q=16, block_k=16, interpret=interpret, window=window
        )
        return x + attn.reshape(b, s, heads * head_dim) @ w["o"]

    key = jax.random.key(0)
    d = heads * head_dim
    x = jax.random.normal(key, (2, 48, d), jnp.bfloat16)
    w = {n: jax.random.normal(jax.random.fold_in(key, i), (d, d), jnp.bfloat16) * d**-0.5
         for i, n in enumerate("qkvo")}
    return block, x, w


def _kept(policy, window):
    """Shapes of what a checkpointed toy block saves beside its arguments."""
    block, x, w = _block(window)
    saved = saved_residuals(jax.checkpoint(block, policy=policy), x, w)
    return sorted(tuple(aval.shape) for aval, why in saved if not why.startswith("from the argument"))


def test_remat_keeps_saves_the_full_causal_pair_and_what_the_other_policy_saves():
    pair = [(2, 2, 48), (2, 48, 2, 16)]  # lse [B, H, S] and out [B, S, H, D]
    assert _kept(llama.remat_keeps(), None) == pair
    # joined with "dots": the q, k and v projections' results beside the pair
    # (the output projection's is needed by no gradient)
    assert _kept(llama.remat_keeps(DOTS), None) == pair + [(2, 48, 32)] * 3
    assert _kept(DOTS, None) == [(2, 48, 32)] * 3
    # a windowed call's pair is named, and not kept
    assert _kept(llama.remat_keeps(), 16) == []
    block, x, w = _block(16)
    grad = jax.grad(lambda x, w: block(x, w).astype(jnp.float32).sum())
    names = {e.params["name"] for e in named(jax.make_jaxpr(grad)(x, w).jaxpr, "name")}
    assert names == set(pallas_attention.WINDOW_FLASH_RESIDUALS)


@pytest.mark.parametrize("window", [None, 16])
def test_gradients_with_and_without_the_policy_are_bit_equal(window, monkeypatch):
    for name in ("BWD_FUSED_BLOCKS", "WINDOW_BWD_DKV_BLOCKS", "WINDOW_BWD_DQ_BLOCKS"):
        monkeypatch.setattr(pallas_attention, name, (16, 16))
    block, x, w = _block(window)

    def grads(policy):
        loss = lambda x, w: jnp.sum(jax.checkpoint(block, policy=policy)(x, w).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)

    kept, recomputed = grads(llama.remat_keeps()), grads(None)
    for a, b in zip(jax.tree_util.tree_leaves(kept), jax.tree_util.tree_leaves(recomputed)):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_outside_a_checkpoint_the_names_lower_to_nothing(monkeypatch):
    """A gradient outside any `jax.checkpoint` lowers to the same text with the
    names as without them; the kernels' own modules are held to the parent's in
    test_pallas_attention.py (`KERNELS_*`)."""

    def traced():
        block, x, w = _block(None, interpret=False)
        grad = jax.grad(lambda x, w: block(x, w).astype(jnp.float32).sum(), argnums=(0, 1))
        names = {e.params["name"] for e in named(jax.make_jaxpr(grad)(x, w).jaxpr, "name")}
        return names, jax.jit(grad).trace(x, w).lower(lowering_platforms=("tpu",)).as_text()

    names, with_names = traced()
    assert names == set(pallas_attention.FLASH_RESIDUALS)
    monkeypatch.setattr(pallas_attention, "checkpoint_name", lambda value, name: value)
    names, without = traced()
    assert not names and without == with_names
