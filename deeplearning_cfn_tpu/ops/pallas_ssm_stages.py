"""The Mamba-2 block's two elementwise stages as Pallas TPU kernels, a forward
and a backward one each: every float32 value lives in VMEM for the length of a
tile, so a pass reads its operands and writes its results once.

`models/ssm_attn_moe.py`'s `_conv_silu` and `_gate_norm` are the mathematics,
the fallback and these kernels' oracle; the entry points here have their
signatures.

- **`conv_silu`**: ``silu(depthwise causal conv(xBC) + bias)``.  A grid step is
  a tile of rows by a lane-multiple of channels; the taps reach ``taps - 1``
  rows back, which a second block of the same array brings (the `_HALO` rows
  before the tile, zeros before the sequence's start).  A row shift is a
  sublane rotation of the tile with the halo on top.  Backward
  (`_conv_silu_backward`): the pre-activation recomputed from xBC,
  ``g silu'(pre)`` for the tile's rows and the `taps - 1` after them (a halo
  the other way, of xBC and of g, zeros after the sequence's end), dx, and the
  sums over rows to dw and dbias kept in a float32 block that stays in VMEM
  while the grid walks the sequence.
- **`gate_norm`**: ``RMSNorm_group(y silu(z)) w``.  A column block is one
  group, so the mean square is a reduction along lanes inside the tile.
  Backward (`_gate_norm_backward`): the gated value and its rsqrt recomputed,
  dy and dz, and the norm weight's gradient summed like the filter's.

Operand and accumulator types are the jnp functions': operands in the
activations' type, taps, SiLU, mean square and sums in float32, a result rounded
once.  The float32 sums over the sequence are taken in another order.

A `jax.custom_vjp` each: the residuals are the inputs alone, what
`jax.checkpoint` of the jnp stage holds.  `takes_conv_kernel` and
`takes_gate_norm_kernel` say which calls leave the jnp stages for this module.
``interpret=True`` runs the same bodies in the Pallas interpreter, for the CPU
tests.
"""

from __future__ import annotations

import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Rows of the neighbouring tile a halo block brings: a bfloat16 sublane tile.
_HALO = 16
# (rows, channels) of a grid step's tile and the rows its body works on at a
# time: from scripts/chip_ssm_stages_sweep.py, PERF.md section 6.  The body is
# unrolled a chunk at a time: the convolution's at 32 rows runs 3.3 ms a step
# faster in the Nemotron cell and costs 1.8 s of tracing and lowering a run.
CONV_TILE = (1024, 512)
CONV_CHUNK = 128
GATE_NORM_ROWS = 512
GATE_NORM_CHUNK = 64
# A group wider than this is the jnp stage's: its tile would not fit in VMEM.
_MAX_GROUP = 4096
_VMEM_LIMIT = 64 * 1024 * 1024

_f32 = jnp.float32
_DTYPES = (jnp.float32, jnp.bfloat16)


def _on_tpu(backend: str | None) -> bool:
    return (backend or jax.default_backend()) == "tpu"


def takes_conv_kernel(xBC: jax.Array, w: jax.Array, backend: str | None = None) -> bool:
    """Whether `conv_silu` on xBC [b, S, C] with taps w [L, C] runs the
    kernels: a TPU backend, float32 or bfloat16, channels whole lane tiles,
    whole row tiles, the taps within a halo.  Everything else is `_conv_silu`."""
    _, S, C = xBC.shape
    return (
        _on_tpu(backend) and xBC.dtype in _DTYPES and C % _LANES == 0
        and S % CONV_TILE[0] == 0 and w.shape[0] <= _HALO
    )


def takes_gate_norm_kernel(
    y: jax.Array, z: jax.Array, groups: int, backend: str | None = None
) -> bool:
    """Whether `gate_norm` on y and z [b, S, inner] runs the kernels: a TPU
    backend, float32 or bfloat16, a group whole lane tiles (and one tile of it
    within VMEM), whole row tiles.  Everything else is `_gate_norm`."""
    _, S, inner = y.shape
    return (
        _on_tpu(backend) and y.dtype in _DTYPES and z.dtype == y.dtype
        and inner % groups == 0 and (inner // groups) % _LANES == 0
        and inner // groups <= _MAX_GROUP and S % GATE_NORM_ROWS == 0
    )


def _row_sums(a):
    """[rows, C] float32 -> [8, C]: the rows summed a sublane tile at a time,
    so that the sum is vector adds; the caller adds the eight."""
    return a.reshape(a.shape[0] // 8, 8, a.shape[1]).sum(axis=0)


def _silu_and_slope(x):
    """silu(x) and silu'(x) in float32, from one sigmoid."""
    sig = jax.nn.sigmoid(x)
    return x * sig, sig * (1.0 + x * (1.0 - sig))


def _down(a, k: int):
    """`a`'s rows moved down by k: row t holds row t - k (the first k wrap)."""
    return a if k % a.shape[0] == 0 else pltpu.roll(a, k % a.shape[0], 0)


def _rows(ref, lo: int, hi: int, before=None, after=None):
    """Rows lo .. hi of a tile in float32, the _HALO rows before them too where
    `before` is given and the _HALO after them where `after` is: those two
    [_HALO, C] stand in for the rows outside the tile, which its first and last
    chunk reach."""
    start = lo - _HALO if before is not None and lo else lo
    stop = hi + _HALO if after is not None and hi < ref.shape[0] else hi
    parts = [ref[start:stop].astype(_f32)]
    if before is not None and lo == 0:
        parts.insert(0, before)
    if after is not None and hi == ref.shape[0]:
        parts.append(after)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _chunks(rows: int, chunk: int) -> list[tuple[int, int]]:
    """The (first, past the last) rows of a tile's chunks: `chunk` rows each
    where that parts the tile evenly, else the tile whole."""
    chunk = chunk if rows % chunk == 0 else rows
    return [(lo, lo + chunk) for lo in range(0, rows, chunk)]


def _conv_forward_kernel(x_ref, before_ref, w_ref, bias_ref, o_ref, *, chunk: int):
    w, bias = w_ref[...].astype(_f32), bias_ref[...]
    taps = w.shape[0]
    # Zeros before the sequence's start; elsewhere the last rows of the tile before.
    before = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[...].astype(_f32))
    for lo, hi in _chunks(x_ref.shape[0], chunk):
        strip = _rows(x_ref, lo, hi, before)
        pre = bias + sum(w[taps - 1 - k : taps - k] * _down(strip, k) for k in range(taps))
        o_ref[lo:hi] = jax.nn.silu(pre[_HALO:]).astype(o_ref.dtype)


def _conv_backward_kernel(
    x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref, bias_ref, dx_ref, sums_ref,
    *, chunk: int,
):
    w, bias = w_ref[...].astype(_f32), bias_ref[...]
    taps = w.shape[0]
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    before = jnp.where(step == 0, 0.0, before_ref[...].astype(_f32))
    after = after_ref[...].astype(_f32)
    # No row after the sequence's end feeds a row inside it.
    g_after = jnp.where(step == pl.num_programs(2) - 1, 0.0, g_after_ref[...].astype(_f32))
    for lo, hi in _chunks(x_ref.shape[0], chunk):
        n = hi - lo
        strip = _rows(x_ref, lo, hi, before, after)
        xs = [_down(strip, k) for k in range(taps)]  # xs[k][t] = x[t - k]
        pre = bias + sum(w[taps - 1 - k : taps - k] * xs[k] for k in range(taps))
        # g silu'(pre) on the chunk's rows and the _HALO after them.
        gp = _rows(g_ref, lo, hi, after=g_after) * _silu_and_slope(pre[_HALO:])[1]
        # dx[t] = sum_k w[taps - 1 - k] gp[t + k]
        dx = sum(w[taps - 1 - k : taps - k] * _down(gp, -k) for k in range(taps))
        dx_ref[lo:hi] = dx[:n].astype(dx_ref.dtype)
        own = gp[:n]
        for k in range(taps):
            sums_ref[taps - 1 - k] += _row_sums(own * xs[k][_HALO : _HALO + n])
        sums_ref[taps] += _row_sums(own)


def _gate_norm_forward_kernel(y_ref, z_ref, w_ref, o_ref, *, eps: float, chunk: int):
    w = w_ref[...]
    for lo, hi in _chunks(y_ref.shape[0], chunk):
        gated = y_ref[lo:hi].astype(_f32) * jax.nn.silu(z_ref[lo:hi].astype(_f32))
        gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
        o_ref[lo:hi] = (gated * w).astype(o_ref.dtype)


def _gate_norm_backward_kernel(
    y_ref, z_ref, w_ref, g_ref, dy_ref, dz_ref, sums_ref, *, eps: float, chunk: int
):
    w = w_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    for lo, hi in _chunks(y_ref.shape[0], chunk):
        y, z, g = (ref[lo:hi].astype(_f32) for ref in (y_ref, z_ref, g_ref))
        silu, slope = _silu_and_slope(z)
        gated = y * silu
        scale = jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
        sums_ref[...] += _row_sums(g * (gated * scale))
        dn = g * w
        # d(gated scale): scale dn - gated scale^3 mean(dn gated)
        pull = scale * scale * scale * jnp.mean(dn * gated, axis=-1, keepdims=True)
        dgated = scale * dn - gated * pull
        dy_ref[lo:hi] = (dgated * silu).astype(dy_ref.dtype)
        dz_ref[lo:hi] = (dgated * y * slope).astype(dz_ref.dtype)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
)


def _conv_specs(xBC, tile):
    """The grid (sequence, column block, row tile: the last sequential) and the
    block specs of a tile, of the _HALO rows before and after it (the first
    tile's before and the last's after are clamped onto the array; the kernels
    put zeros there) and of a [n, C] row array's column block."""
    b, S, C = xBC.shape
    rows, cols = tile[0], math.gcd(C, tile[1])
    halos = rows // _HALO
    halo = lambda at: pl.BlockSpec((None, _HALO, cols), lambda i, c, s: (i, at(s), c))
    return SimpleNamespace(
        grid=(b, C // cols, S // rows), cols=cols,
        tile=pl.BlockSpec((None, rows, cols), lambda i, c, s: (i, s, c)),
        before=halo(lambda s: jnp.maximum(s * halos - 1, 0)),
        after=halo(lambda s: jnp.minimum((s + 1) * halos, S // _HALO - 1)),
        row=lambda n: pl.BlockSpec((n, cols), lambda i, c, s: (0, c)),
    )


@functools.partial(jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _conv_forward(xBC, w, bias, *, tile, chunk, interpret):
    at = _conv_specs(xBC, tile)
    return pl.pallas_call(
        functools.partial(_conv_forward_kernel, chunk=chunk),
        grid=at.grid,
        in_specs=[at.tile, at.before, at.row(w.shape[0]), at.row(1)],
        out_specs=at.tile,
        out_shape=jax.ShapeDtypeStruct(xBC.shape, xBC.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_conv_silu_forward",
    )(xBC, xBC, w, bias.astype(_f32)[None])


@functools.partial(jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _conv_backward(xBC, w, bias, g, *, tile, chunk, interpret):
    """The cotangents of xBC, w and bias, in their shapes and types."""
    b, _, C = xBC.shape
    at, taps = _conv_specs(xBC, tile), w.shape[0]
    dx, sums = pl.pallas_call(
        functools.partial(_conv_backward_kernel, chunk=chunk),
        grid=at.grid,
        in_specs=[at.tile, at.before, at.after, at.tile, at.after, at.row(taps), at.row(1)],
        out_specs=[
            at.tile, pl.BlockSpec((None, taps + 1, 8, at.cols), lambda i, c, s: (i, 0, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xBC.shape, xBC.dtype),
            jax.ShapeDtypeStruct((b, taps + 1, 8, C), _f32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_conv_silu_backward",
    )(xBC, xBC, xBC, g, g, w, bias.astype(_f32)[None])
    sums = sums.sum(axis=(0, 2))  # over the sequences and a sublane tile's eight partial sums
    return dx, sums[:taps].astype(w.dtype), sums[taps].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_core(xBC, w, bias, tile, chunk, interpret):
    return _conv_forward(xBC, w, bias, tile=tile, chunk=chunk, interpret=interpret)


def _conv_core_fwd(xBC, w, bias, tile, chunk, interpret):
    return _conv_core(xBC, w, bias, tile, chunk, interpret), (xBC, w, bias)


def _conv_core_bwd(tile, chunk, interpret, res, g):
    return _conv_backward(*res, g, tile=tile, chunk=chunk, interpret=interpret)


_conv_core.defvjp(_conv_core_fwd, _conv_core_bwd)


def conv_silu(xBC: jax.Array, w: jax.Array, bias: jax.Array, interpret: bool = False) -> jax.Array:
    """`_conv_silu` through the kernels: xBC [b, S, C], taps w [L, C], bias [C]
    -> silu(depthwise causal conv(xBC) + bias) in xBC's type.  For the shapes
    `takes_conv_kernel` names."""
    return _conv_core(xBC, w, bias, CONV_TILE, CONV_CHUNK, bool(interpret))


def _gate_norm_specs(y, groups, rows):
    b, S, inner = y.shape
    width = inner // groups
    block = pl.BlockSpec((None, rows, width), lambda i, c, s: (i, s, c))
    return (b, groups, S // rows), block, pl.BlockSpec((1, width), lambda i, c, s: (0, c))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows", "chunk", "interpret"))
def _gate_norm_forward(y, z, w, *, groups, eps, rows, chunk, interpret):
    grid, block, weight = _gate_norm_specs(y, groups, rows)
    return pl.pallas_call(
        functools.partial(_gate_norm_forward_kernel, eps=eps, chunk=chunk),
        grid=grid,
        in_specs=[block, block, weight],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_gate_norm_forward",
    )(y, z, w.astype(_f32)[None])


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows", "chunk", "interpret"))
def _gate_norm_backward(y, z, w, g, *, groups, eps, rows, chunk, interpret):
    """The cotangents of y, z and w, in their shapes and types."""
    b, _, inner = y.shape
    grid, block, weight = _gate_norm_specs(y, groups, rows)
    dy, dz, sums = pl.pallas_call(
        functools.partial(_gate_norm_backward_kernel, eps=eps, chunk=chunk),
        grid=grid,
        in_specs=[block, block, weight, block],
        out_specs=[block, block, pl.BlockSpec((None, 8, inner // groups), lambda i, c, s: (i, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct(y.shape, y.dtype), jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((b, 8, inner), _f32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_gate_norm_backward",
    )(y, z, w.astype(_f32)[None], g)
    return dy, dz, sums.sum(axis=(0, 1)).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _gate_norm_core(y, z, w, groups, eps, rows, chunk, interpret):
    return _gate_norm_forward(
        y, z, w, groups=groups, eps=eps, rows=rows, chunk=chunk, interpret=interpret
    )


def _gate_norm_core_fwd(y, z, w, *static):
    return _gate_norm_core(y, z, w, *static), (y, z, w)


def _gate_norm_core_bwd(groups, eps, rows, chunk, interpret, res, g):
    return _gate_norm_backward(
        *res, g, groups=groups, eps=eps, rows=rows, chunk=chunk, interpret=interpret
    )


_gate_norm_core.defvjp(_gate_norm_core_fwd, _gate_norm_core_bwd)


def gate_norm(
    y: jax.Array, z: jax.Array, w: jax.Array, groups: int, eps: float, interpret: bool = False
) -> jax.Array:
    """`_gate_norm` through the kernels: y and z [b, S, inner], w [inner] ->
    RMSNorm_group(y silu(z)) w in y's type.  For the shapes
    `takes_gate_norm_kernel` names."""
    return _gate_norm_core(
        y, z, w, int(groups), float(eps), GATE_NORM_ROWS, GATE_NORM_CHUNK, bool(interpret)
    )
