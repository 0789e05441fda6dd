"""The precisions a plain reference can be computed in: its own (float32
under `highest`), and the nearest one below the configurations' bfloat16,
which is what the control of `correct` is computed in.

The lower precision is fp8 as a training step uses it: every matmul's and
convolution's operands rounded to e4m3 on the way forward, and the
gradient that arrives at its result rounded to e5m2 on the way back, each
with one scale per tensor.  Both roundings are straight-through: autodiff
sees the identity, so no gradient is lost to the derivative of a cast.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def identity(x):
    return x


def _round_to(x, dtype, top: float):
    """Round to an 8-bit float with one scale per tensor, back to float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = top / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_operand(x):
    """An operand as e4m3; its gradient passes unchanged."""
    return _round_to(x, jnp.float8_e4m3fn, E4M3_MAX)


fp8_operand.defvjp(lambda x: (fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def fp8_result(y):
    """A result unchanged; the gradient that arrives at it as e5m2."""
    return y


fp8_result.defvjp(
    lambda y: (y, None), lambda _, g: (_round_to(g, jnp.float8_e5m2, E5M2_MAX),)
)


class Rounding(NamedTuple):
    """What a reference applies around every matmul and convolution:
    `result(op(operand(a), operand(b)))`."""

    operand: Callable = identity
    result: Callable = identity


ROUNDINGS = {"float32": Rounding(), "fp8": Rounding(fp8_operand, fp8_result)}
