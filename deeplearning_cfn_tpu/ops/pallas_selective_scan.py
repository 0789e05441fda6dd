"""The selective scan of `ops/selective_scan.py` as two Pallas TPU kernels,
forward and backward, with no state a token in HBM.

`ops/selective_scan.py` is the definition, the path off the TPU and these
kernels' oracle: read its docstring first.  No chunk of this recurrence is a
matrix product (the decay differs a channel and a state), so the kernels are
elementwise work for the VPU and the EUP around a state that stays in VMEM:

- **The layout.**  The state of a step's `CHANNELS_A_STEP` channels is
  ``[N, W]`` float32: the states down the sublanes, the channels along the
  lanes.  A token's x, dt and dy are rows ``[1, W]`` that every sublane reads;
  its B and C are columns ``[N, 1]`` that every lane reads.  A column read from
  ``[S, N]`` would be a transpose a token, so the wrapper hands the kernels B
  and C already spread over one lane tile, ``[S, N, 128]`` in their own type (a
  token's is one packed tile; 33 MB a sequence of 8192 in bfloat16, read once
  a time chunk because the channel tiles are the grid's innermost axis).
- **forward** (`_selective_scan_forward`): grid (batch, time chunks, channel
  tiles).  Every channel tile's state is carried along the chunks in one VMEM
  scratch ``[tiles, N, W]``.  A chunk's tokens go `GROUP` at a time (one packed
  bfloat16 tile of rows) through a `fori_loop` whose body is unrolled: ``a =
  exp(dt A)``, ``h = a h + B (dt x)``, ``y = sum_n C h``; ``D x`` is added to
  the whole chunk at once.  It writes y and, when a backward pass will follow,
  the state each chunk starts from.
- **backward** (`_selective_scan_backward`): the same grid with the chunks
  reversed.  From a chunk's boundary state it runs the tokens forward into a
  VMEM history ``[chunk + 1, N, W]``, then backward with
  ``g_t = C_t (x) dy_t + a_{t+1} g_{t+1}`` carried like the state:
  ``dC_t = sum_c dy_t h_t`` and ``dB_t = sum_c g_t dt_t x_t`` leave as sums over
  the step's lane *tiles* (``[S, N, 128]`` float32, accumulated over the channel
  tiles in the revisited output block; the wrapper sums the 128 lanes),
  ``w = g_t h_{t-1} a_t`` gives ``ddt_t = sum_n w A + x_t du_t`` and
  ``dA += w dt_t``, ``du_t = sum_n g_t B_t`` gives ``dx_t = dt_t du_t + D dy_t``;
  dA and dD are summed over time in VMEM and over the batch by the wrapper.

What stays in XLA, differentiated by JAX: dt's softplus and ``A = -exp(A_log)``
(the caller's), the spreading of B and C and the lane sums of their gradients.
All arithmetic is float32; x, B, C and dy are read in their own type and
widened, y and dx go back in x's.

`takes_kernel` says which calls leave `ops/selective_scan.py` for this module;
`selective_scan` has its signature.  ``interpret=True`` runs the same bodies in
the Pallas interpreter, for the CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Tokens a loop iteration (a packed bfloat16 tile of rows), tokens a grid step
# (the spacing of the kept boundary states) and channels a grid step: from
# scripts/chip_selective_scan_sweep.py, PERF.md section 6 (PR 47).
GROUP = 16
CHUNK = 128
CHANNELS_A_STEP = 640
_VMEM_LIMIT = 100 * 1024 * 1024

_f32 = jnp.float32


def takes_kernel(x: jax.Array, A: jax.Array, backend: str | None = None) -> bool:
    """Whether `selective_scan` on x [b, S, I] and A [I, N] runs the kernels: a
    TPU backend, whole time chunks, channels whole lane tiles, states whole
    packed sublane tiles, float32 or bfloat16 operands.  Everything else is
    `ops/selective_scan.selective_scan`'s."""
    _, S, I = x.shape
    return (
        (backend or jax.default_backend()) == "tpu"
        and S % CHUNK == 0 and I % _LANES == 0 and A.shape[1] % 16 == 0
        and x.dtype in (jnp.float32, jnp.bfloat16)
    )


def _width(channels: int, channels_a_step: int) -> int:
    """The widest whole number of lane tiles that divides `channels` and is no
    wider than `channels_a_step`."""
    tiles = channels // _LANES
    fits = max(channels_a_step // _LANES, 1)
    return _LANES * max(k for k in range(1, fits + 1) if tiles % k == 0)


def _across(column, k: int):
    """A token's B or C, [N, 128] with every lane alike, over k lane tiles."""
    column = column.astype(_f32)
    return column if k == 1 else jnp.concatenate([column] * k, axis=1)


def _fold(a, k: int):
    """[N, k 128] -> [N, 128]: the sum over the lane tiles."""
    return sum(a[:, m * _LANES : (m + 1) * _LANES] for m in range(k))


def _group(x_ref, dt_ref, g, group: int):
    """A group's first token and its dt and dt x, [group, W] float32."""
    t0 = pl.multiple_of(g * group, group)
    dtg = dt_ref[pl.ds(t0, group), :]
    return t0, dtg, dtg * x_ref[pl.ds(t0, group), :].astype(_f32)


def _forward_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest, group, save_states):
    before_ref = rest[0] if save_states else None
    state, ybuf = rest[-2:]
    chunk, W = x_ref.shape
    k, j = W // _LANES, pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], _f32)

    h0 = state[j]
    if save_states:
        before_ref[...] = h0
    A = a_ref[...]

    def tokens(g, h):
        t0, dtg, ug = _group(x_ref, dt_ref, g, group)
        for i in range(group):
            h = jnp.exp(dtg[i : i + 1] * A) * h + _across(b_ref[t0 + i], k) * ug[i : i + 1]
            ybuf[pl.ds(t0 + i, 1), :] = jnp.sum(_across(c_ref[t0 + i], k) * h, axis=0, keepdims=True)
        return h

    state[j] = jax.lax.fori_loop(0, chunk // group, tokens, h0)
    y_ref[...] = (ybuf[...] + d_ref[...] * x_ref[...].astype(_f32)).astype(y_ref.dtype)


def _backward_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, before_ref, dy_ref,
    dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
    dstate, da_sum, dd_sum, hist, wbuf, dubuf,
    *, group,
):
    chunk, W = x_ref.shape
    k, j = W // _LANES, pl.program_id(2)
    groups = chunk // group

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[j] = jnp.zeros(dstate.shape[1:], _f32)
        da_sum[j] = jnp.zeros(da_sum.shape[1:], _f32)
        dd_sum[j] = jnp.zeros(dd_sum.shape[1:], _f32)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    A = a_ref[...]
    hist[0] = before_ref[...]

    def forward(g, h):
        t0, dtg, ug = _group(x_ref, dt_ref, g, group)
        for i in range(group):
            h = jnp.exp(dtg[i : i + 1] * A) * h + _across(b_ref[t0 + i], k) * ug[i : i + 1]
            hist[t0 + i + 1] = h
        return h

    jax.lax.fori_loop(0, groups, forward, before_ref[...])

    def backward(r, carry):
        K, dA = carry  # a_{t+1} g_{t+1}; this chunk's sum of w dt
        t0, dtg, ug = _group(x_ref, dt_ref, groups - 1 - r, group)
        dyg = dy_ref[pl.ds(t0, group), :].astype(_f32)
        for i in reversed(range(group)):
            t = t0 + i
            Bt, dt, dy = _across(b_ref[t], k), dtg[i : i + 1], dyg[i : i + 1]
            a = jnp.exp(dt * A)
            G = _across(c_ref[t], k) * dy + K
            dc_ref[t] += _fold(dy * hist[t + 1], k)
            db_ref[t] += _fold(G * ug[i : i + 1], k)
            w = G * hist[t] * a
            wbuf[pl.ds(t, 1), :] = jnp.sum(w * A, axis=0, keepdims=True)
            dubuf[pl.ds(t, 1), :] = jnp.sum(G * Bt, axis=0, keepdims=True)
            dA = dA + w * dt
            K = a * G
        return K, dA

    K, dA = jax.lax.fori_loop(0, groups, backward, (dstate[j], jnp.zeros(A.shape, _f32)))
    dstate[j] = K
    da_sum[j] += dA
    da_ref[...] = da_sum[j]
    xs, dys, du = x_ref[...].astype(_f32), dy_ref[...].astype(_f32), dubuf[...]
    dd_sum[j] += (dys * xs).reshape(chunk // 8, 8, W).sum(axis=0)
    dd_ref[...] = dd_sum[j]
    dx_ref[...] = (du * dt_ref[...] + d_ref[...] * dys).astype(dx_ref.dtype)
    ddt_ref[...] = wbuf[...] + du * xs


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
)


def _specs(x, At, chunk: int, channels_a_step: int, reverse: bool = False):
    """(grid, W, block specs by name) for x [b, S, I] and At [N, I]."""
    b, S, I = x.shape
    N = At.shape[0]
    W, nc = _width(I, channels_a_step), S // chunk
    order = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    specs = dict(
        wide=pl.BlockSpec((None, chunk, W), lambda i, c, j: (i, order(c), j)),
        states=pl.BlockSpec((N, W), lambda i, c, j: (0, j)),
        columns=pl.BlockSpec((None, chunk, N, _LANES), lambda i, c, j: (i, order(c), 0, 0)),
        row=pl.BlockSpec((1, W), lambda i, c, j: (0, j)),
        before=pl.BlockSpec((None, None, N, W), lambda i, c, j: (i, order(c), 0, j)),
    )
    return (b, nc, I // W), W, specs


def _spread(a):
    """B or C [b, S, N] -> [b, S, N, 128], every lane alike."""
    return jnp.broadcast_to(a[..., None], a.shape + (_LANES,))


@functools.partial(
    jax.jit, static_argnames=("interpret", "save_states", "chunk", "group", "channels_a_step")
)
def _forward(x, dt, At, B, C, D, *, interpret, save_states, chunk, group, channels_a_step):
    """y [b, S, I] and, with `save_states`, the state each chunk starts from
    [b, chunks, N, I] float32 (else None)."""
    grid, W, s = _specs(x, At, chunk, channels_a_step)
    b, nc, tiles = grid
    N = At.shape[0]
    out_shape, out_specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)], [s["wide"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, N, x.shape[-1]), _f32))
        out_specs.append(s["before"])
    out = pl.pallas_call(
        functools.partial(_forward_kernel, group=group, save_states=save_states),
        grid=grid,
        in_specs=[s["wide"], s["wide"], s["states"], s["columns"], s["columns"], s["row"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tiles, N, W), _f32), pltpu.VMEM((chunk, W), _f32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_selective_scan_forward",
    )(x, dt, At, _spread(B), _spread(C), D)
    return (out[0], out[1]) if save_states else (out[0], None)


@functools.partial(jax.jit, static_argnames=("interpret", "chunk", "group", "channels_a_step"))
def _backward(x, dt, At, B, C, D, before, dy, *, interpret, chunk, group, channels_a_step):
    """The cotangents of x, dt, At, B, C and D, in their shapes and types."""
    grid, W, s = _specs(x, At, chunk, channels_a_step, reverse=True)
    b, _, tiles = grid
    S, I = x.shape[1:]
    N = At.shape[0]
    summed = lambda rows: pl.BlockSpec((None, rows, W), lambda i, c, j: (i, 0, j))
    a_column = jax.ShapeDtypeStruct((b, S, N, _LANES), _f32)
    scratch = lambda *shape: pltpu.VMEM(shape, _f32)
    dx, ddt, dA, dB, dC, dD = pl.pallas_call(
        functools.partial(_backward_kernel, group=group),
        grid=grid,
        in_specs=[
            s["wide"], s["wide"], s["states"], s["columns"], s["columns"], s["row"],
            s["before"], s["wide"],
        ],
        out_specs=[s["wide"], s["wide"], summed(N), s["columns"], s["columns"], summed(8)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(x.shape, _f32),
            jax.ShapeDtypeStruct((b, N, I), _f32), a_column, a_column,
            jax.ShapeDtypeStruct((b, 8, I), _f32),
        ],
        scratch_shapes=[
            scratch(tiles, N, W), scratch(tiles, N, W), scratch(tiles, 8, W),
            scratch(chunk + 1, N, W), scratch(chunk, W), scratch(chunk, W),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_selective_scan_backward",
    )(x, dt, At, _spread(B), _spread(C), D, before, dy)
    return (
        dx, ddt, dA.sum(axis=0), dB.sum(axis=-1).astype(B.dtype), dC.sum(axis=-1).astype(C.dtype),
        dD.sum(axis=(0, 1))[None],
    )


def _tiles() -> dict:
    """The module's constants as they stand when a call is traced."""
    return dict(chunk=CHUNK, group=GROUP, channels_a_step=CHANNELS_A_STEP)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _core(x, dt, At, B, C, D, interpret):
    return _forward(x, dt, At, B, C, D, interpret=interpret, save_states=False, **_tiles())[0]


def _core_fwd(x, dt, At, B, C, D, interpret):
    y, before = _forward(x, dt, At, B, C, D, interpret=interpret, save_states=True, **_tiles())
    return y, (x, dt, At, B, C, D, before)


def _core_bwd(interpret, res, dy):
    return _backward(*res, dy, interpret=interpret, **_tiles())


_core.defvjp(_core_fwd, _core_bwd)


def selective_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """`ops/selective_scan.selective_scan` through the kernels: x [b, S, I],
    dt [b, S, I] (positive), A [I, N] (negative), B and C [b, S, N], D [I]
    -> y [b, S, I] in x's type.  For the shapes `takes_kernel` names."""
    return _core(
        x, dt.astype(_f32), A.astype(_f32).T, B, C, D.astype(_f32)[None], bool(interpret)
    )
