"""Seeded random projections of a tensor: sum(x * r), with r = +-1 drawn by
an integer hash from the run's key, the leaf's name and each element's
index, `DRAWS` times over.

A norm hardly feels rounding noise: |g + e| - |g| is about |e|^2 / 2|g| for
noise that does not line up with g, so fp8's noise of a third of a
gradient moves its norm by a few percent, no more than the bias bfloat16
leaves in a few BatchNorm leaves (my chip and CPU runs, PR 23).  A
projection feels it in full: sum((g + e) * r) - sum(g * r) = sum(e * r),
which has the variance |e|^2, so the root mean square of a few draws
estimates |e|.  Both sides compute their own scalars; neither needs the
other's tensor.  The hash is plain uint32 arithmetic on an iota, so it
fuses into the reduction and reads the same on every backend.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

DRAWS = 8


def signs(shape: tuple[int, ...], salt) -> jax.Array:
    """+-1.0 for every element of `shape`, in row-major order (lowbias32)."""
    h = jnp.arange(math.prod(shape), dtype=jnp.uint32) + salt
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return (1.0 - 2.0 * (h >> 31).astype(jnp.float32)).reshape(shape)


def sketch(x: jax.Array, name: str, key: jax.Array) -> jax.Array:
    """[DRAWS] projections of x."""
    data = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    salt = data[0] ^ (data[-1] * jnp.uint32(0x9E3779B1)) ^ jnp.uint32(zlib.crc32(name.encode()))
    x = x.astype(jnp.float32)
    return jnp.stack([
        jnp.sum(x * signs(x.shape, salt + jnp.uint32(0x632BE5AB * (draw + 1) % 2**32)))
        for draw in range(DRAWS)
    ])


def sketches(tree: dict, key: jax.Array) -> dict:
    return {name: sketch(x, name, key) for name, x in tree.items()}
