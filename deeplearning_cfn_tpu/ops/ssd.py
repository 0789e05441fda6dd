"""The state-space scan of Mamba-2 (arXiv:2405.21060, "SSD"), chunked.

The recurrence, a head at a time, with a scalar decay a head and token:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h in [P, N], float32
    y_t = h_t C_t + D x_t

Over a chunk of `chunk` tokens the recurrence is a masked matrix product, so
the sequence costs four matmuls a chunk and one short scan over the chunks'
states instead of S dependent steps:

- within a chunk, ``y = (L o C B^T) X`` with ``L_ij = exp(cs_i - cs_j)`` for
  ``i >= j`` and 0 above the diagonal, ``cs`` the cumulative sum of ``dt A``
  inside the chunk and ``X = dt x``;
- the chunk's own state at its end, ``(B o decay-to-end)^T X``;
- a `lax.scan` over the chunks carries the state across them
  (``h <- exp(cs_last) h + state``) and hands each chunk the state before it;
- what the earlier chunks give a token, ``exp(cs_i) C_i h``.

``C B^T`` is computed once a *group* of heads (B and C are shared by the
``H / G`` heads of a group).  The decays and cumulative sums are float32; the
mask goes *inside* the exponent (``-inf`` above the diagonal): masked after,
the upper triangle's ``exp`` of a positive sum overflows under a strong decay
and the product's gradient is NaN.  The four matmuls take operands in x's type
and accumulate in float32.  Plain `jax.numpy`: JAX differentiates it.

Since PR 42 this module is the fallback and the oracle.  On a TPU a call whose
sequence is whole chunks, whose chunk and state are whole lane tiles (128) and
whose groups' heads together are whole lane tiles too, in float32 or bfloat16,
leaves it for fused kernels of the same arithmetic, which keep a chunk's L,
masked product and states in VMEM, forward and backward: the module beside this
one that `models/ssm_attn_moe._ssm_mixer` imports with it, whose `takes_kernel`
is the rule.  (Its name is not spelled here: tests/test_ssd.py's last test
holds this file free of the kernel language's name.)  Everything else runs
here: the CPU, a toy or ragged shape, another type; and the kernels' tests
hold them to this module's values and gradients.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Chunks whose matrix forms exist at once: 8 chunks of 128 at 128 heads are
# 67 MB of L in float32.
CHUNKS_A_TILE = 8


def _by_tiles(fn, *chunked):
    """``fn(*tiles)`` over tiles of the arrays' chunk axis (axis 1 of
    [b, chunks, ...]), each tile rematerialised in the backward pass; the
    results have the chunks on axis 1 again."""
    nc = chunked[0].shape[1]
    tile = math.gcd(nc, CHUNKS_A_TILE)
    apart = lambda a: jnp.moveaxis(a.reshape(a.shape[0], nc // tile, tile, *a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda tiles: jax.checkpoint(fn)(*tiles), tuple(apart(a) for a in chunked))
    together = lambda a: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], nc, *a.shape[3:])
    return jax.tree_util.tree_map(together, out)



def ssd(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array,
    chunk: int,
) -> jax.Array:
    """x [b, S, H, P], dt [b, S, H] (positive: after its softplus), A [H]
    (negative), B and C [b, S, G, N] with G dividing H, D [H] -> y [b, S, H, P]
    in x's type.  Any S: the tail is padded to a whole chunk with ``dt = 0``,
    which neither decays the state nor adds to it."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    pad = -S % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C))
    nc = (S + pad) // chunk
    # [b, chunks, chunk, groups, heads a group, ...]
    xc = x.reshape(b, nc, chunk, G, H // G, P)
    dtc = dt.astype(f32).reshape(b, nc, chunk, G, H // G)
    Bc, Cc = B.reshape(b, nc, chunk, G, N), C.reshape(b, nc, chunk, G, N)
    A, D = (a.astype(f32).reshape(G, H // G) for a in (A, D))

    def decays(dtc):
        """The cumulative sum of dt A inside each chunk [b, c, l, g, r], <= 0."""
        return jnp.cumsum(dtc * A, axis=2)

    def scaled(xc, dtc):
        return (xc.astype(f32) * dtc[..., None]).astype(x.dtype)  # X = dt x

    def state(xc, dtc, Bc):
        """Each chunk's own state at its end [b, c, g, r, p, n], and its decay
        from start to end [b, c, g, r]."""
        with jax.named_scope("states"):
            cs = decays(dtc)
            to_end = jnp.exp(cs[:, :, -1:] - cs)  # [b, c, l, g, r]
            Xd = (scaled(xc, dtc).astype(f32) * to_end[..., None]).astype(x.dtype)
            states = jnp.einsum("bclgn,bclgrp->bcgrpn", Bc, Xd, preferred_element_type=f32)
            return states, jnp.exp(cs[:, :, -1])

    def output(xc, dtc, Bc, Cc, before):
        """A chunk's tokens' y from the chunk itself and from the state it
        starts from, ``before`` [b, c, g, r, p, n]."""
        cs, X = decays(dtc), scaled(xc, dtc)
        with jax.named_scope("within"):
            scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32)
            span = cs[:, :, :, None] - cs[:, :, None, :]  # [b, c, i, j, g, r]: cs_i - cs_j
            causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
            L = jnp.exp(jnp.where(causal, span, -jnp.inf))
            M = (L * scores.transpose(0, 1, 3, 4, 2)[..., None]).astype(x.dtype)
            y = jnp.einsum("bcijgr,bcjgrp->bcigrp", M, X, preferred_element_type=f32)
        with jax.named_scope("across"):
            y = y + jnp.exp(cs)[..., None] * jnp.einsum(
                "bclgn,bcgrpn->bclgrp", Cc, before.astype(x.dtype), preferred_element_type=f32
            )
        return (y + D[:, :, None] * xc.astype(f32)).astype(x.dtype)

    states, chunk_decay = _by_tiles(state, xc, dtc, Bc)
    with jax.named_scope("carry"):

        def carry(h, step):
            decay, state = step
            return decay[..., None, None] * h + state, h

        _, before = jax.lax.scan(
            carry, jnp.zeros_like(states[:, 0]),
            (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
        )
        before = jnp.moveaxis(before, 0, 1)  # the state each chunk starts from
    y = _by_tiles(output, xc, dtc, Bc, Cc, before)
    return y.reshape(b, nc * chunk, H, P)[:, :S]
