"""ResNet v1.5 family (50/101/152) — the framework's flagship vision models.

Capability analogs from the reference: the Horovod ResNet-50 synthetic
benchmark (README.md:149-163, the BASELINE.json driver metric) and the MXNet
ResNet-152 dist_device_sync example it suggests for ImageNet
(README.md:139 with --model resnet152).  Rebuilt TPU-first:

- NHWC layout + bf16-friendly convs: XLA tiles convolutions onto the MXU;
  channels-last is the native TPU layout.
- BatchNorm in float32 running stats regardless of compute dtype (bf16 BN
  statistics diverge); under GSPMD the batch statistics are global across
  the sharded batch axis — SyncBN semantics with zero runtime machinery
  (the reference had to opt into Horovod SyncBN explicitly, run.sh:60-61).
- zero-init of the last BN gamma in each residual block (the standard
  trick the reference's tensorpack config applied via its own init), which
  buys ~0.5% top-1 and faster early convergence.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class GroupNorm32(nn.Module):
    """GroupNorm-32 with the same construction surface the blocks use for
    BatchNorm (name= / scale_init=); group count capped for thin feature
    maps (tiny test backbones)."""

    dtype: Any = jnp.float32
    scale_init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self, y: jnp.ndarray) -> jnp.ndarray:
        import math

        # gcd, not min: the group count must DIVIDE the channel count,
        # and widths that aren't multiples of 32 exist (thin test
        # backbones, non-standard num_filters).
        return nn.GroupNorm(
            num_groups=math.gcd(32, int(y.shape[-1])),
            epsilon=1e-5,
            dtype=self.dtype,
            scale_init=self.scale_init,
            name="gn",
        )(y)


class _FoldedNorm(nn.Module):
    """Identity stand-in for a normalization that has been folded into
    the preceding convolution's kernel/bias (:func:`fold_batchnorm`).
    Accepts the same construction surface the blocks use (scale_init=)."""

    dtype: Any = jnp.float32
    scale_init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self, y: jnp.ndarray) -> jnp.ndarray:
        return y


class BottleneckBlock(nn.Module):
    filters: int
    strides: tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        y = self.conv(self.filters, (1, 1), name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides, name="conv2")(y)
        y = self.norm(name="bn2")(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1), name="conv3")(y)
        # Zero-init gamma: each block starts as identity.
        y = self.norm(scale_init=nn.initializers.zeros, name="bn3")(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="bn_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32
    # When True, skip the classifier and return the {C2..C5} stage feature
    # maps (stride 4..32) — the backbone interface detection FPNs consume.
    return_features: bool = False
    # "batch" (default, the reference family's normalization) or "group"
    # (GroupNorm-32): the round-3 trace put the ResNet-50 step at an HBM
    # ceiling dominated by BN stats/grads reduces, and named "a different
    # normalization" as an untried byte-reduction lever — this flag makes
    # the lever measurable (BENCH_NOTES r4).  GroupNorm has no running
    # stats (no model_state, no train/eval asymmetry) and normalizes per
    # sample, trading BN's global-batch statistics for a reduce that
    # needs no cross-batch traffic.
    norm: str = "batch"

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        if self.norm == "folded":
            # Inference-only deployment variant: BatchNorm's eval-mode
            # affine is absorbed into the conv kernels/biases
            # (:func:`fold_batchnorm` converts a trained "batch" model's
            # weights).  Training this variant would train WITHOUT
            # normalization — refuse.
            if train:
                raise ValueError(
                    'norm="folded" is inference-only; train with '
                    'norm="batch" and fold the result'
                )
            conv = partial(nn.Conv, use_bias=True, dtype=self.dtype)
            norm = partial(_FoldedNorm, dtype=self.dtype)
        elif self.norm == "group":
            norm = partial(GroupNorm32, dtype=self.dtype)
        elif self.norm != "batch":
            # Silent fallback would train the WRONG experiment.
            raise ValueError(
                f"unknown norm {self.norm!r}; expected batch|group|folded"
            )
        else:
            norm = partial(
                nn.BatchNorm,
                use_running_average=not train,
                momentum=0.9,
                epsilon=1e-5,
                # Outputs in the compute dtype; statistics/params stay f32
                # (flax computes mean/var in >= f32 and param_dtype defaults
                # to f32, so running stats cannot diverge).  f32 BN outputs
                # doubled HBM traffic on every normalization: the round-3
                # trace attributed ~39% of the ResNet-50 step to BN-side
                # elementwise+reduce fusions moving f32 activations
                # (docs/BENCH_NOTES.md).
                dtype=self.dtype,
            )
        # The blocks and the classifier carry their module's name
        # (`stage<n>_block<m>`, `head`) into a profile's op names by
        # themselves; `stem` and `head` name what no module does.
        with jax.named_scope("stem"):
            x = x.astype(self.dtype)
            x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv_init")(x)
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        features = {}
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    name=f"stage{i + 1}_block{j + 1}",
                )(x)
            features[f"C{i + 2}"] = x
        if self.return_features:
            return features
        with jax.named_scope("head"):
            x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


def fold_batchnorm(params: Any, batch_stats: Any, eps: float = 1e-5) -> Any:
    """Fold eval-mode BatchNorm into the preceding convolutions:
    ``W' = W * s`` and ``b' = beta - mean * s`` with
    ``s = gamma / sqrt(var + eps)`` per output channel.  Input: a trained
    ``norm="batch"`` model's ``params`` + ``batch_stats``; output: params
    for the same architecture constructed with ``norm="folded"``
    (bias-carrying convs, no norm modules).

    The pairing is by the family's naming convention (``convX``/``bnX``
    within each scope — conv1/bn1 ... conv_proj/bn_proj, conv_init/
    bn_init), so it holds for every ResNet depth and for the
    ``return_features`` backbone variant.

    Measured at the bench shape (docs/BENCH_NOTES.md r5): XLA already
    fuses the eval-mode BN affine into the conv epilogue, so folding is
    a weight-portability convenience, not a throughput lever.
    """
    from collections.abc import Mapping

    def fold_scope(p: Mapping, bs: Mapping) -> dict:
        out = {}
        for name, sub in p.items():
            if name.startswith("conv"):
                bn = "bn" + name[len("conv"):]
                if bn in p:
                    gamma = jnp.asarray(p[bn]["scale"], jnp.float32)
                    beta = jnp.asarray(p[bn]["bias"], jnp.float32)
                    mean = jnp.asarray(bs[bn]["mean"], jnp.float32)
                    var = jnp.asarray(bs[bn]["var"], jnp.float32)
                    s = gamma / jnp.sqrt(var + eps)
                    kernel = jnp.asarray(sub["kernel"], jnp.float32)
                    out[name] = {
                        "kernel": (kernel * s).astype(sub["kernel"].dtype),
                        "bias": (beta - mean * s).astype(jnp.float32),
                    }
                else:
                    out[name] = dict(sub)
            elif name.startswith("bn"):
                continue  # absorbed
            # Mapping, not dict: flax FrozenDict scopes (frozen trees,
            # checkpoint restores) must fold too, not silently pass
            # through half-converted.
            elif isinstance(sub, Mapping) and any(
                k.startswith("conv") for k in sub
            ):
                out[name] = fold_scope(sub, bs.get(name, {}))
            else:
                out[name] = sub
        return out

    return fold_scope(params, batch_stats)


ResNet50: Callable[..., ResNet] = partial(ResNet, stage_sizes=(3, 4, 6, 3))
ResNet101: Callable[..., ResNet] = partial(ResNet, stage_sizes=(3, 4, 23, 3))
ResNet152: Callable[..., ResNet] = partial(ResNet, stage_sizes=(3, 8, 36, 3))
