"""Flax wrapper for the Pallas fused dense kernel (ops/pallas_fused).

``FusedDense`` is a drop-in for ``nn.Dense`` (+ an optionally fused
activation) with an IDENTICAL parameter tree — same names (``kernel``,
``bias``), same shapes, same initializers — so a model can flip its
``use_pallas_*`` flag on an existing checkpoint and restore cleanly in
either direction.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from deeplearning_cfn_tpu.ops.pallas_fused import fused_dense


class FusedDense(nn.Module):
    """``activation(x @ kernel + bias)`` through one Pallas kernel.

    Differences from ``nn.Dense`` + separate activation are purely in
    lowering, not in parameters: the kernel accumulates in f32 on the
    MXU and applies bias/activation in VMEM before the single HBM
    write.  Leading axes are flattened to 2D around the kernel call
    (the kernel's layout contract is ``x [M, K]``).  The kernel is the
    compiled Mosaic one, so a model with its ``use_pallas_*`` flag on
    needs a TPU; the CPU tests run it under
    ``pltpu.force_tpu_interpret_mode()``.
    """

    features: int
    activation: str | None = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), jnp.float32
        )
        x = x.astype(self.dtype)
        kernel = kernel.astype(self.dtype)
        bias = bias.astype(self.dtype)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        out = fused_dense(x2, kernel, bias, activation=self.activation)
        return out.reshape(*lead, self.features)
