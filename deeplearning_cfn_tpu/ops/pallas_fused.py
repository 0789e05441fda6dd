"""Fused dense (matmul + bias + activation) as a Pallas TPU kernel.

The ResNet classifier head and the BERT MLP block lower, under default
XLA, to a dot followed by separate bias/activation elementwise ops; at
the hot-block shapes `cost_analysis` attributes a measurable slice of
``bytes_accessed`` to the materialized intermediate.  This kernel fuses
the whole block: one grid pass over (M, N) output tiles, the FULL
reduction axis per tile, bias and activation applied in VMEM before the
single HBM write.

Design rules (shared with ops/pallas_attention.py):

- The K axis is NOT split.  Each output tile's value is one complete
  ``dot_general`` over K — the same per-element contraction the XLA
  reference computes, with no second reduction tree — so the kernel
  agrees with ``fused_dense_reference`` to within a few ulp of the
  accumulation dtype.  Bit-identity is not promised: the order in which
  a backend sums a contraction is its own business (the CPU tests see
  differences of a few ulp against ``nn.Dense``; the tolerances measured
  on the MXU are in ``chip_smoke.py``).
- f32 accumulation on the MXU via ``preferred_element_type``; inputs
  stay in their storage dtype.
- Forward is the kernel; backward is a ``custom_vjp`` in plain XLA
  (dense backward is two matmuls — XLA fuses those fine).
- The kernel compiles through Mosaic and needs a TPU; it does not guess
  its backend and nothing falls back.  ``interpret=True`` runs the same
  body in the Pallas interpreter for the CPU tests that ask for it by
  name.  ``fused_dense_profitable`` is the dispatch guard — it compiles
  the XLA reference at the call shape and only votes for the kernel
  when the fused analytic HBM traffic undercuts what ``cost_analysis``
  measured for XLA.

``fused_dense_quantized`` is the int8-weights variant: weights cross
HBM→VMEM as int8 + a per-output-channel f32 scale and are dequantized
per TILE in VMEM — the one fusion XLA cannot express, since an XLA
dequantize materializes the full upcast weight matrix in HBM first.

Layout contract: ``x [M, K]``, ``w [K, N]``, ``b [N]`` → ``[M, N]``.
Callers with leading batch/seq axes flatten to 2D around the call
and reshape the result back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Output-tile defaults: 256x256 keeps x/w tiles well inside VMEM at the
# bench shapes (K <= 4096 bf16: 256*4096*2 = 2 MiB per operand tile)
# while giving the MXU full 128-lane tiles.
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256

# Sublane granularity: 16 covers f32 (8) and bf16 (16); the int8 operand
# is the weight, whose sublane axis is K — padded to the 128 lane
# multiple below, which satisfies int8's (32, 128) tile too.
_SUBLANE = 16
_LANE = 128

#: Activations the kernel may fuse.  Values are used both inside the
#: kernel body and by the XLA reference path, so the two can never
#: disagree about what (e.g.) "gelu" means.
_ACTIVATIONS = {
    None: lambda z: z,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
}


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad2(a: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr == 0 and pc == 0:
        return a
    return jnp.pad(a, ((0, pr), (0, pc)))


def _fused_kernel(x_ref, w_ref, b_ref, out_ref, *, activation):
    x = x_ref[...]  # [bm, Kp]
    w = w_ref[...]  # [Kp, bn]
    acc = jax.lax.dot_general(
        x,
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc + b_ref[...].astype(jnp.float32)  # [1, bn] broadcasts
    acc = _ACTIVATIONS[activation](acc)
    out_ref[...] = acc.astype(out_ref.dtype)


def _quant_kernel(x_ref, wq_ref, scale_ref, b_ref, out_ref, *, activation):
    # Dequantize the int8 weight TILE in VMEM: HBM and the HBM->VMEM copy
    # only ever carry int8 + the [1, bn] scale row.
    x = x_ref[...].astype(jnp.float32)
    w = wq_ref[...].astype(jnp.float32) * scale_ref[...].astype(jnp.float32)
    acc = jax.lax.dot_general(
        x,
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc + b_ref[...].astype(jnp.float32)
    acc = _ACTIVATIONS[activation](acc)
    out_ref[...] = acc.astype(out_ref.dtype)


def _clamp(block: int, dim: int, granule: int) -> int:
    """Largest multiple of ``granule`` <= ``block`` that does not
    overshoot the (padded) dimension — small shapes shrink their tile
    instead of paying a mostly-padding grid step."""
    target = min(_round_up(max(dim, 1), granule), _round_up(block, granule))
    return max(target, granule)


@functools.partial(
    jax.jit, static_argnames=("activation", "block_m", "block_n", "interpret")
)
def _fused_forward(x, w, b, activation, block_m, block_n, interpret):
    M, K = x.shape
    _, N = w.shape
    bm = _clamp(block_m, M, _SUBLANE)
    bn = _clamp(block_n, N, _LANE)
    mp, np_, kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, _LANE)
    xp = _pad2(x, mp, kp)
    wp = _pad2(w, kp, np_)
    bp = _pad2(b.reshape(1, N), 1, np_)
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="fused_dense",
    )(xp, wp, bp)
    return out[:M, :N]


def fused_dense_reference(x, w, b, activation=None):
    """The plain-XLA program the kernel is checked against: f32 MXU
    accumulation, f32 bias/activation, cast to the input dtype."""
    acc = jax.lax.dot_general(
        x,
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc + b.astype(jnp.float32)
    acc = _ACTIVATIONS[activation](acc)
    return acc.astype(x.dtype)


def _quant_reference(x, wq, scale, b, activation, out_dtype):
    w = wq.astype(jnp.float32) * scale.reshape(1, -1).astype(jnp.float32)
    acc = jax.lax.dot_general(
        x.astype(jnp.float32),
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc + b.astype(jnp.float32)
    acc = _ACTIVATIONS[activation](acc)
    return acc.astype(out_dtype)


# --- custom-vjp core ------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_core(x, w, b, activation, block_m, block_n, interpret):
    return _fused_forward(x, w, b, activation, block_m, block_n, interpret)


def _core_fwd(x, w, b, activation, block_m, block_n, interpret):
    out = _fused_forward(x, w, b, activation, block_m, block_n, interpret)
    return out, (x, w, b)


def _core_bwd(activation, block_m, block_n, interpret, res, g):
    del block_m, block_n, interpret
    x, w, b = res
    # Recompute the pre-activation in plain XLA (two matmuls dominate the
    # backward anyway; saving z would cost an extra [M, N] residual).
    z = jax.lax.dot_general(
        x,
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b.astype(jnp.float32)
    _, act_vjp = jax.vjp(_ACTIVATIONS[activation], z)
    (dz,) = act_vjp(g.astype(jnp.float32))
    dx = jax.lax.dot_general(
        dz,
        w,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    dw = jax.lax.dot_general(
        x,
        dz,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(w.dtype)
    db = jnp.sum(dz, axis=0).astype(b.dtype)
    return dx, dw, db


_fused_core.defvjp(_core_fwd, _core_bwd)


# --- public entry points --------------------------------------------------


def fused_dense(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    activation: str | None = None,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """``activation(x @ w + b)`` as one Pallas kernel, [M, K] x [K, N].

    Compiled Mosaic kernel unless ``interpret=True`` (the Pallas
    interpreter, for CPU tests).  Differentiable (custom_vjp; the
    backward is plain XLA).
    """
    if activation not in _ACTIVATIONS:
        raise ValueError(
            f"unknown activation {activation!r}; one of {sorted(map(str, _ACTIVATIONS))}"
        )
    if x.ndim != 2 or w.ndim != 2 or b.ndim != 1:
        raise ValueError(
            f"fused_dense wants x[M,K], w[K,N], b[N]; got {x.shape}/{w.shape}/{b.shape}"
        )
    return _fused_core(x, w, b, activation, block_m, block_n, bool(interpret))


@functools.partial(
    jax.jit, static_argnames=("activation", "block_m", "block_n", "interpret")
)
def fused_dense_quantized(
    x: jax.Array,
    wq: jax.Array,
    scale: jax.Array,
    b: jax.Array,
    activation: str | None = None,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Fused dense with int8 weights: ``wq [K, N] int8`` and a
    per-output-channel ``scale [N] f32`` are dequantized tile-by-tile in
    VMEM — the weight matrix never exists in float in HBM.  Forward-only
    (the int8-weights bench/serving path; training updates float
    weights).  Checked against :func:`_quant_reference`."""
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    if wq.dtype != jnp.int8:
        raise ValueError(f"wq must be int8, got {wq.dtype}")
    M, K = x.shape
    _, N = wq.shape
    bm = _clamp(block_m, M, _SUBLANE)
    bn = _clamp(block_n, N, _LANE)
    mp, np_, kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, _LANE)
    xp = _pad2(x, mp, kp)
    wp = _pad2(wq, kp, np_)
    sp = _pad2(scale.reshape(1, N).astype(jnp.float32), 1, np_)
    bp = _pad2(b.reshape(1, N), 1, np_)
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=bool(interpret),
        name="fused_dense_quantized",
    )(xp, wp, sp, bp)
    return out[:M, :N]


# --- profitability --------------------------------------------------------

#: the fused kernel must beat XLA's measured HBM traffic by at least
#: this fraction before the dispatcher prefers it — a tie is not a win
#: once kernel-launch overhead is counted.
PROFIT_MARGIN = 0.10


def fused_dense_bytes(m: int, k: int, n: int, itemsize: int) -> int:
    """Analytic HBM traffic of the fused kernel: read x + w + b once,
    write the output once.  (Tiles re-read x per N-block and w per
    M-block from VMEM, not HBM, at these block sizes.)"""
    return itemsize * (m * k + k * n + n + m * n)


def fused_dense_profitable(
    m: int, k: int, n: int, dtype=jnp.bfloat16, activation: str | None = "gelu"
) -> bool:
    """cost_analysis-based dispatch check: compile the plain-XLA
    dense+bias+activation at this shape and compare its measured
    ``bytes accessed`` against the fused kernel's analytic traffic.
    True only when fusion saves at least :data:`PROFIT_MARGIN` — i.e.
    when XLA really does materialize intermediates it could have kept
    in registers/VMEM.  AOT lower+compile only; nothing executes."""
    x = jax.ShapeDtypeStruct((m, k), dtype)
    w = jax.ShapeDtypeStruct((k, n), dtype)
    b = jax.ShapeDtypeStruct((n,), dtype)
    ref = jax.jit(functools.partial(fused_dense_reference, activation=activation))
    cost = ref.lower(x, w, b).compile().cost_analysis() or {}
    xla_bytes = cost.get("bytes accessed")
    if not xla_bytes:
        return False
    fused = fused_dense_bytes(m, k, n, jnp.dtype(dtype).itemsize)
    return fused < float(xla_bytes) * (1.0 - PROFIT_MARGIN)
