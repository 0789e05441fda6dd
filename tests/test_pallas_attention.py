"""Flash attention (Pallas kernel, interpret mode asked for by name) vs XLA
attention.

Covers: causal/non-causal, GQA, non-divisible sequence lengths (padding +
masking), and gradients through the custom VJP.  The compiled kernel's
numerics are checked on the chip by chip_smoke.py.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops.attention import dot_product_attention
from deeplearning_cfn_tpu.ops import pallas_attention

# The kernel compiles through Mosaic unless told otherwise; on the CPU mesh
# every call here asks for the interpreter.
flash_attention = functools.partial(pallas_attention.flash_attention, interpret=True)


def _qkv(b=2, s=64, hq=4, hkv=2, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_matches_xla_attention(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_gqa_head_mapping():
    q, k, v = _qkv(hq=8, hkv=2)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ragged_seq_len_padding():
    # 50 is not a multiple of any block size → exercises padding + kv mask.
    q, k, v = _qkv(s=50)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_io():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_gradients_match(hq, hkv):
    q, k, v = _qkv(s=48, hq=hq, hkv=hkv)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_block_picker_balances_padding_against_block_size():
    """Effective block selection: keep the big (fast) block for aligned
    sequences, step down for ragged ones instead of paying up to 2.5x in
    padded attention FLOPs (512-block on S=600 would pad to 1024) — but
    never chase the last few percent of padding down to a tiny block:
    round-2 advisor flagged S=600 picking 32 (padded 608) over 128
    (padded 640), trading ~5% padding for a ~40% MXU-efficiency loss."""
    from deeplearning_cfn_tpu.ops.pallas_attention import _clamp_block

    assert _clamp_block(512, 2048) == 512  # aligned: biggest block wins
    assert _clamp_block(1024, 2048) == 1024  # measured-best default
    assert _clamp_block(512, 4096) == 512
    assert _clamp_block(512, 128) == 128  # short seq: clamp to length
    assert _clamp_block(128, 8) == 16  # sublane floor
    # Ragged: 128 pads to 640, within tolerance of the 608 minimum; the
    # tiny 32 block is NOT chosen for its ~5% padding saving.
    assert _clamp_block(512, 600) == 128
    assert _clamp_block(512, 640) == 128  # 640 = 5*128: zero padding
    # Far-from-aligned: 512 pads 600->1024 (+68%), rightly rejected.
    assert _clamp_block(512, 520) == 128  # 128 pads to 640 vs min 528 @16
    # Tolerance respects genuinely large savings: stepping to 16 saves
    # >12.5% only when no bigger block comes close.
    assert _clamp_block(16, 600) == 16
    # Non-power-of-two caller blocks still consider the 128 floor: 384's
    # halving ladder (384, 192, 96...) must not skip over it.
    assert _clamp_block(384, 600) == 128


def test_llama_attention_dispatch_crossover():
    """use_flash_attention means "fastest memory-safe attention": below
    the measured v5e crossover XLA's fused attention wins (3.74 vs
    4.69 ms at S=2048 with round-2 blocks, BENCH_NOTES), so the llama
    path must fall back to XLA there instead of dispatching to the
    Pallas kernel unconditionally."""
    from deeplearning_cfn_tpu.models.llama import LlamaConfig, attention_kind
    from deeplearning_cfn_tpu.ops.pallas_attention import FLASH_CROSSOVER_SEQ

    cfg = LlamaConfig.tiny(vocab_size=64, seq_len=FLASH_CROSSOVER_SEQ)
    cfg = dataclasses.replace(cfg, use_flash_attention=True)
    assert attention_kind(cfg, None, FLASH_CROSSOVER_SEQ, backend="tpu") == "flash"
    assert attention_kind(cfg, None, FLASH_CROSSOVER_SEQ - 1, backend="tpu") == "xla"
    assert attention_kind(cfg, None, FLASH_CROSSOVER_SEQ, backend="cpu") == "xla"
    off = dataclasses.replace(cfg, use_flash_attention=False)
    assert attention_kind(off, None, FLASH_CROSSOVER_SEQ, backend="tpu") == "xla"


def test_compiled_kernel_does_not_fall_back_off_tpu():
    """No backend guessing: without interpret=True the Mosaic kernel is
    what runs, and on a CPU that is an error, not a silent interpreter."""
    q, k, v = _qkv(s=16)
    with pytest.raises(ValueError, match="interpret mode"):
        pallas_attention.flash_attention(q, k, v)


def test_bad_gqa_ratio_raises():
    q, k, v = _qkv(hq=6, hkv=4)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k, v)


def test_mesh_shard_map_path():
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh, virtual_cpu_devices

    mesh = build_mesh(MeshSpec(dp=2, tp=2), virtual_cpu_devices(4))
    q, k, v = _qkv(b=4, s=32, hq=4, hkv=2)
    ref = dot_product_attention(q, k, v, causal=True)

    def loss_mesh(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, mesh=mesh)
        return jnp.sum(out**2), out

    (val, out), grads = jax.value_and_grad(loss_mesh, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("spec_kw", [{"dp": 2, "sp": 2}, {"sp": 4}])
def test_mesh_sp_sharding_rejected(spec_kw):
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh, virtual_cpu_devices

    mesh = build_mesh(MeshSpec(**spec_kw), virtual_cpu_devices(4))
    q, k, v = _qkv(s=32)
    with pytest.raises(ValueError, match="ring_attention"):
        flash_attention(q, k, v, mesh=mesh)


def test_jit_and_value_and_grad():
    q, k, v = _qkv(s=32)

    @jax.jit
    def step(q, k, v):
        def loss(q):
            return jnp.mean(flash_attention(q, k, v, True, None, 16, 16))

        return jax.value_and_grad(loss)(q)

    val, grad = step(q, k, v)
    assert np.isfinite(float(val))
    assert np.isfinite(np.asarray(grad)).all()
