"""The depthwise causal convolution of a few taps that the gated short
convolution (models/conv_attn_moe.py) and the Mamba-2 mixer
(models/ssm_attn_moe.py) both run, as shifted multiply-adds XLA fuses with
what surrounds them; `ops/pallas_ssm_stages.py` is the fused form of the
second's, and this its oracle."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def short_conv(z: jax.Array, w: jax.Array) -> jax.Array:
    """The depthwise causal convolution: z [B, S, d], w [L, d] ->
    c[:, t] = sum_j w[j] * z[:, t - (L - 1) + j], zeros before the start."""
    L, S = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j : j + S] for j in range(L))
