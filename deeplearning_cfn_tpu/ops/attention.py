"""Attention ops.

The reference has no attention anywhere (vision-era workloads); this module
exists because the TPU framework's flagship configs (BERT, Llama-3 —
BASELINE.json) are transformers.  Two paths:

- ``dot_product_attention``: XLA attention.  On TPU, XLA fuses the
  softmax chain and tiles the two matmuls onto the MXU; with the causal
  mask expressed as a static lower-triangular bias the compiler keeps
  everything on-chip for moderate sequence lengths.
- ``flash_attention``: Pallas blockwise-softmax kernel (ops/pallas_attention)
  for long sequences where materializing the [S, S] score matrix would blow
  HBM bandwidth.  It needs a TPU; callers dispatch to
  ``dot_product_attention`` elsewhere (models/llama.attention_kind).

Both are pure functions of [batch, seq, heads, head_dim] tensors, grouped-
query aware (kv heads may be fewer than q heads).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


from deeplearning_cfn_tpu.ops.pallas_attention import flash_attention  # noqa: F401  (public re-export)


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """Expand KV heads for grouped-query attention."""
    num_kv = k.shape[2]
    if num_kv == num_q_heads:
        return k
    assert num_q_heads % num_kv == 0, (num_q_heads, num_kv)
    return jnp.repeat(k, num_q_heads // num_kv, axis=2)


def dot_product_attention(
    q: jax.Array,  # [B, S, Hq, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    causal: bool = True,
    mask: jax.Array | None = None,  # [B, 1, S, S] additive or bool
    softmax_dtype: jnp.dtype = jnp.float32,
    window: int | None = None,
) -> jax.Array:
    """Plain XLA attention with f32 softmax (bf16 softmax loses tail mass).
    ``window``: query t sees the keys ``t - window < j <= t`` (causal only;
    the convention of ops/pallas_attention.py)."""
    if window is not None and not causal:
        raise ValueError("a window is causal")
    *_, seq_q, num_heads, head_dim = q.shape
    k = _repeat_kv(k, num_heads)
    v = _repeat_kv(v, num_heads)
    scale = head_dim**-0.5
    # [B, H, Sq, Sk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(softmax_dtype) * scale
    if causal:
        seq_k = k.shape[1]
        causal_mask = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool))
        if window is not None:
            causal_mask = jnp.logical_and(causal_mask, jnp.triu(causal_mask, k=1 - window))
        scores = jnp.where(causal_mask[None, None], scores, jnp.finfo(softmax_dtype).min)
    if mask is not None:
        if mask.dtype == bool:
            scores = jnp.where(mask, scores, jnp.finfo(softmax_dtype).min)
        else:
            scores = scores + mask.astype(softmax_dtype)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def rotary_embedding(
    x: jax.Array,  # [B, S, H, D]
    positions: jax.Array,  # [B, S] or [S]
    theta: float = 500000.0,  # Llama-3 base
) -> jax.Array:
    """RoPE applied over the last dim (split-halves convention)."""
    head_dim = x.shape[-1]
    if positions.ndim == 1:
        positions = positions[None, :]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> np.ndarray:
    """YaRN's inverse frequencies over ``dim`` rotary dimensions, float32
    [dim / 2], as `transformers`' `_compute_yarn_parameters` computes them:
    ``f_i = theta ** (-2i / dim)``; dimensions that turn more than
    ``beta_fast`` times over the original context keep ``f_i``, those that
    turn fewer than ``beta_slow`` times get ``f_i / factor``, and a linear ramp
    ``r_i = clip((i - low) / (high - low), 0, 1)`` blends between:
    ``(f_i / factor) r_i + f_i (1 - r_i)``."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return ((f / factor) * ramp + f * (1.0 - ramp)).astype(np.float32)


def partial_rotary_embedding(
    x: jax.Array,  # [B, S, H, D]
    positions: jax.Array,  # [S]
    inv_freq: np.ndarray,  # [R / 2]: the first R dimensions of a head rotate
    scale: float = 1.0,  # on cos and sin (YaRN's attention factor)
) -> jax.Array:
    """RoPE over the first ``R = 2 * len(inv_freq)`` dimensions of each head,
    split halves within them; the other ``D - R`` pass through."""
    rot = 2 * len(inv_freq)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    cos = (scale * jnp.cos(angles))[None, :, None, :]
    sin = (scale * jnp.sin(angles))[None, :, None, :]
    turned, kept = x[..., :rot].astype(jnp.float32), x[..., rot:]
    x1, x2 = jnp.split(turned, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return out if rot == x.shape[-1] else jnp.concatenate([out, kept], axis=-1)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with f32 accumulation regardless of compute dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    norm = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (norm * weight.astype(jnp.float32)).astype(dtype)
