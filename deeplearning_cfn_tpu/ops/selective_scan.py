"""The selective scan of Mamba (arXiv:2312.00752, section 3 and Algorithm 2).

The recurrence, a channel c and a state n at a time, from h_0 = 0:

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

The decay differs for every channel *and* state, so a chunk of it is no matrix
product (Mamba-2's, `ops/ssd.py`, has one scalar a head, which is what makes
its chunk matmuls): it is elementwise work on a [channels, states] float32
state a token.  B and C are shared by all channels.

Here it is its definition in `jax.numpy`: a `lax.scan` over the tokens in
float32, in chunks of `CHUNK` tokens rematerialised in the backward pass, so
that JAX's transpose holds a state a chunk and one chunk's states a token and
not a state a token of the whole sequence.  This is the path off the TPU and
the oracle of the kernels beside it (the module `models/mamba_attn` imports
with this one, whose `takes_kernel` is the rule); x, B and C are read in their
own type and widened, y comes back in x's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens whose states the backward pass holds at once.
CHUNK = 64


def selective_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array
) -> jax.Array:
    """x [b, S, I], dt [b, S, I] (positive), A [I, N] (negative), B and C
    [b, S, N], D [I] -> y [b, S, I] in x's type."""
    b, S, I = x.shape
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)

    def token(h, t):
        xt, dtt, Bt, Ct = (a.astype(f32) for a in t)  # [b, I], [b, I], [b, N], [b, N]
        h = jnp.exp(dtt[..., None] * A) * h + (dtt * xt)[..., None] * Bt[:, None, :]
        return h, jnp.sum(h * Ct[:, None, :], axis=-1) + D * xt

    pad = -S % CHUNK

    def chunks(a):  # [b, S, ...] -> [chunks, CHUNK, b, ...]; a padded dt of 0 keeps the state
        a = jnp.moveaxis(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)), 1, 0)
        return a.reshape(((S + pad) // CHUNK, CHUNK) + a.shape[1:])

    one_chunk = jax.checkpoint(lambda h, c: jax.lax.scan(token, h, c))
    h0 = jnp.zeros((b, I, A.shape[1]), f32)
    _, y = jax.lax.scan(one_chunk, h0, tuple(chunks(a) for a in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape(S + pad, b, I)[:S], 0, 1).astype(x.dtype)
