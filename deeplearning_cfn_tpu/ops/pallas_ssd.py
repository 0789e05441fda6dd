"""The state-space scan of `ops/ssd.py` as two Pallas TPU kernels, forward and
backward, with nothing of a chunk's matrix form in HBM.

`ops/ssd.py` is the algorithm, the fallback and these kernels' oracle: read its
docstring first.  Here a grid step holds one chunk of one group of heads (or of
a part of a group, `HEADS_A_STEP`) and walks the chunks in order:

- **forward** (`_ssd_forward`): ``C B^T`` once a step; a head at a time L with
  the mask inside the exponent and the masked product in x's type; the three
  matmuls (within, across from the carried state, the chunk's own state) with
  float32 accumulation; ``D x``.  The state is carried in a VMEM scratch,
  float32, as ``[N, heads P]``: transposed, so that the across product
  ``C @ state`` and the chunk's own state ``B^T @ Xd`` are one matmul each over
  all the step's heads at the MXU's full width, whatever the head size.  It
  writes y and, when a backward pass will follow, the state each chunk starts
  from.
- **backward** (`_ssd_backward`): the same grid with the chunks reversed, the
  state's cotangent carried in VMEM, L and the products recomputed.  It writes
  dx, dB and dC (summed over the step's heads), and per (token, head) the
  cotangents of dt and of the decays' cumulative sum cs, which reach ``dt``
  and ``A`` through the XLA around the kernel.  ``d(cs_i - cs_j)`` is formed a
  head at a time in VMEM, float32, and cs takes its row sums less its column
  sums: the *same* matrix both ways, because over a chunk the two cancel and
  what is left is the gradient (two separately rounded matmuls in its place
  lost a quarter of ``dA`` in bfloat16).  The chunk's last token's cs also
  takes the decays to the chunk's end and the chunk's total decay.

The per-(token, head) scalars come and go as ``[b, H, S]``, the tokens along
the lanes (dense tiles in HBM; a ``[S, 16]`` block would be lane-padded
eightfold), and are transposed a chunk at a time inside the kernels.

Heads narrower than a lane tile (P = 64) go two to a tile of 128 lanes: a
head's matmuls take the tile with the other head's lanes zeroed, so nothing is
sliced below a lane tile.  Sums over a head's P lanes are matmuls against a
0/1 matrix, in two bfloat16 terms (16 bits of mantissa) under bfloat16
operands.

What stays in XLA, differentiated by JAX: dt's softplus (the caller's),
``dt A``, its cumulative sum inside a chunk, and the sums to ``dA`` and ``dD``.  Operand and accumulator types are `ops/ssd.py`'s:
decays and sums float32, matmul operands in x's type, float32 accumulation, the
carried state float32 and cast only as the across matmul's operand.

`takes_kernel` says which calls leave `ops/ssd.py` for this module; `ssd`
is the entry point with `ops/ssd.ssd`'s signature.  ``interpret=True`` runs the
same bodies in the Pallas interpreter, for the CPU tests.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Heads a grid step holds (a whole group when the group has no more) and
# chunks a grid step walks: from scripts/chip_ssd_sweep.py, PERF.md section 6.
HEADS_A_STEP = 16
CHUNKS_A_STEP = 1
_VMEM_LIMIT = 64 * 1024 * 1024

_f32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def takes_kernel(x: jax.Array, B: jax.Array, chunk: int, backend: str | None = None) -> bool:
    """Whether `ssd` on x [b, S, H, P] and B [b, S, G, N] runs the kernels: a
    TPU backend, whole chunks, chunk and state whole lane tiles, a group's
    heads whole lane tiles too (a head a divisor or a multiple of one), float32
    or bfloat16 operands.  Everything else is `ops/ssd.ssd`'s."""
    _, S, H, P = x.shape
    G, N = B.shape[2:]
    return (
        (backend or jax.default_backend()) == "tpu"
        and S % chunk == 0 and chunk % _LANES == 0 and N % _LANES == 0
        and H % G == 0 and (H // G * P) % _LANES == 0 and (P % _LANES == 0 or _LANES % P == 0)
        and x.dtype in (jnp.float32, jnp.bfloat16) and B.dtype == x.dtype
    )


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_f32)


def _tiling(heads: int, P: int) -> tuple[int, int]:
    """(heads a lane tile, the tile's width) for `heads` heads of P lanes."""
    k = _LANES // P if P < _LANES and _LANES % P == 0 and heads % (_LANES // P) == 0 else 1
    return k, k * P


def _spread(cols: list, lane, P: int):
    """Columns [l, 1], one a head of a tile -> [l, width]: head m's over lanes
    m P .. (m + 1) P."""
    out = jnp.broadcast_to(cols[0], lane.shape)
    for m, col in enumerate(cols[1:], 1):
        out = jnp.where(lane >= m * P, col, out)
    return out


def _only(a, lane, m: int, k: int, P: int):
    """`a` with every head's lanes but head m's zeroed."""
    if k == 1:
        return a
    return jnp.where((lane >= m * P) & (lane < (m + 1) * P), a, jnp.zeros_like(a))


def _segment_sums(a, ones):
    """Sums of `a` [l, heads P] float32 over each head's P lanes, as a matmul
    against `ones` [heads P, lanes]: exact to 16 bits under bfloat16."""
    if ones.dtype == _f32:
        return jax.lax.dot_general(
            a, ones, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=_f32,
        )
    high = a.astype(ones.dtype)
    low = (a - high.astype(_f32)).astype(ones.dtype)
    return _dot(high, ones) + _dot(low, ones)


def _masked_product(cs_col, cs_row, scores, causal, dtype):
    """(L, (L o C B^T) in x's type) of one head: the mask inside the exponent."""
    L = jnp.exp(jnp.where(causal, cs_col - cs_row, -jnp.inf))
    return L, (L * scores).astype(dtype)


def _columns(rows):
    """A step's per-(head, token) scalars [heads, l] with the tokens down the
    sublanes [l, lanes]: head h's in lane h, the lanes padded to whole tiles."""
    pad = -rows.shape[0] % _LANES
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)], axis=0)
    return rows.T


def _causal(Q: int):
    return jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (Q, Q), 1
    )


def _chunk_scalars(cs_ref, dt_ref, rows):
    """A chunk's cs and dt with the tokens down the sublanes [l, lanes], the
    decay from each token to the chunk's end and the decay from its start to
    each token."""
    cs, dts = _columns(cs_ref[:, rows]), _columns(dt_ref[:, rows])
    return cs, dts, jnp.exp(cs[-1:] - cs), jnp.exp(cs)


def _tile_terms(x_ref, rows, cols, hs, scalars, lane, P):
    """A lane tile of a chunk: x in float32, dt, the decay to the chunk's end
    and the decay from its start spread over the tile's heads' lanes, and
    X = dt x in x's type."""
    _, dts, to_end, grown = scalars
    xs = x_ref[rows, cols].astype(_f32)
    dt, end, grow = (_spread([a[:, h : h + 1] for h in hs], lane, P) for a in (dts, to_end, grown))
    return xs, dt, end, grow, (xs * dt).astype(x_ref.dtype)


def _forward_kernel(
    x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, y_ref, *rest,
    chunk: int, head_dim: int, save_states: bool,
):
    before_ref = rest[0] if save_states else None
    state, xd, decay = rest[-3:]
    Q, P = chunk, head_dim
    heads = x_ref.shape[1] // P
    k, width = _tiling(heads, P)
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    causal = _causal(Q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1)
    for j in range(x_ref.shape[0] // Q):
        rows = slice(j * Q, (j + 1) * Q)
        Bm, Cm = b_ref[rows], c_ref[rows]
        scores = _dot(Cm, Bm, _NT)
        before = state[...]
        if save_states:
            before_ref[j] = before
        across = _dot(Cm, before.astype(dtype))  # [l, heads P]
        scalars = _chunk_scalars(cs_ref, dt_ref, rows)
        cs = scalars[0]
        for t in range(heads // k):
            cols = slice(t * width, (t + 1) * width)
            hs = range(t * k, (t + 1) * k)
            xs, _, end, grow, X = _tile_terms(x_ref, rows, cols, hs, scalars, lane, P)
            xd[:, cols] = (X.astype(_f32) * end).astype(dtype)
            decay[:, cols] = grow[Q - 1 :]  # the chunk's total decay
            y = grow * across[:, cols]
            for m, h in enumerate(hs):
                _, M = _masked_product(
                    cs[:, h : h + 1], cs_ref[h : h + 1, rows], scores, causal, dtype
                )
                y = y + _dot(M, _only(X, lane, m, k, P))
            y_ref[rows, cols] = (y + d_ref[:, cols] * xs).astype(dtype)
        state[...] = decay[...] * before + _dot(Bm, xd[...], _TN)


def _backward_kernel(
    x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, ones_ref, before_ref, dy_ref,
    dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref, dd_ref,
    dstate, xd, w, decay, v, q,
    *, chunk: int, head_dim: int,
):
    Q, P = chunk, head_dim
    heads = x_ref.shape[1] // P
    k, width = _tiling(heads, P)
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    causal = _causal(Q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (Q, ones_ref.shape[1]), 1)
    for j in reversed(range(x_ref.shape[0] // Q)):
        rows = slice(j * Q, (j + 1) * Q)
        Bm, Cm = b_ref[rows], c_ref[rows]
        scores = _dot(Cm, Bm, _NT)
        before = before_ref[j]
        before_low = before.astype(dtype)
        dafter = dstate[...]
        dafter_low = dafter.astype(dtype)
        across = _dot(Cm, before_low)  # [l, heads P]
        dxd = _dot(Bm, dafter_low)  # the cotangent of X decayed to the chunk's end
        # What the chunk's total decay of the state it starts from gives cs.
        chain = jnp.sum(dafter * before, axis=0, keepdims=True)
        scalars = _chunk_scalars(cs_ref, dt_ref, rows)
        cs = scalars[0]
        dscores = jnp.zeros((Q, Q), _f32)
        # The row sums of a head's dL o L o C B^T, head h's in lane h.
        dcs = jnp.zeros(head_lane.shape, _f32)
        for t in range(heads // k):
            cols = slice(t * width, (t + 1) * width)
            hs = range(t * k, (t + 1) * k)
            xs, dt, end, grow, X = _tile_terms(x_ref, rows, cols, hs, scalars, lane, P)
            dy_low = dy_ref[rows, cols]
            dy = dy_low.astype(_f32)
            xd[:, cols] = (X.astype(_f32) * end).astype(dtype)
            w[:, cols] = (grow * dy).astype(dtype)
            decay[:, cols] = grow[Q - 1 :]
            dX = end * dxd[:, cols]
            dend = dX * X.astype(_f32)  # summed over a head's lanes: d(cs_last - cs)
            for m, h in enumerate(hs):
                L, M = _masked_product(
                    cs[:, h : h + 1], cs_ref[h : h + 1, rows], scores, causal, dtype
                )
                dy_m = _only(dy_low, lane, m, k, P)
                dL = _dot(dy_m, X, _NT) * L
                dscores = dscores + dL
                # d(cs_i - cs_j) = dL L C B^T: + its row sums, - its column sums.
                dspan = dL * scores
                dcs = jnp.where(head_lane == h, jnp.sum(dspan, axis=1, keepdims=True), dcs)
                dcs_ref[h : h + 1, rows] = -jnp.sum(dspan, axis=0, keepdims=True)
                dX = dX + _dot(M, dy_m, _TN)
            dx_ref[rows, cols] = (d_ref[:, cols] * dy + dX * dt).astype(dtype)
            q[:, cols] = dX * xs
            v[:, cols] = dy * grow * across[:, cols] - dend
            # The chunk's last token's cs has the decays to it and the chunk's own.
            v[Q - 1 :, cols] += jnp.sum(dend, axis=0, keepdims=True) + grow[Q - 1 :] * chain[:, cols]
            dd_ref[:, cols] += (dy * xs).reshape(Q // 8, 8, width).sum(axis=0)
        dscores_low = dscores.astype(dtype)
        db_ref[rows] = (_dot(xd[...], dafter_low, _NT) + _dot(dscores_low, Cm, _TN)).astype(dtype)
        dc_ref[rows] = (_dot(w[...], before_low, _NT) + _dot(dscores_low, Bm)).astype(dtype)
        dstate[...] = decay[...] * dafter + _dot(Cm, w[...], _TN)
        ddt_ref[:, rows] = _segment_sums(q[...], ones_ref[...]).T[:heads]
        dcs_ref[:, rows] += (dcs + _segment_sums(v[...], ones_ref[...])).T[:heads]


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
)


def _layout(x, dt, cs, B, C, D, chunk, heads_a_step, chunks_a_step, reverse=False):
    """The kernels' six arrays from the caller's (x [b, S, H P], dt and cs
    [b, H, S] float32, B and C [b, S, G, N], D [H] float32), their sizes, and
    the block specs of those arrays and of the outputs shaped like them.  A
    grid step holds `heads_a_step` heads (a whole group, or an even part of
    one) and `chunks_a_step` chunks, the last chunks first under `reverse`."""
    b, S, HP = x.shape
    H = dt.shape[1]
    G, N = B.shape[2:]
    hs = min(heads_a_step, H // G)
    parts, P = H // G // hs, HP // H  # the steps a group takes; a head's width
    steps, nc = G * parts, S // chunk
    a_step = chunks_a_step if nc % chunks_a_step == 0 else 1
    blocks = nc // a_step
    order = (lambda c: blocks - 1 - c) if reverse else (lambda c: c)
    by_step = lambda a: a.reshape(b, steps, hs, S)
    arrays = (
        x, by_step(dt), by_step(cs), B.reshape(b, S, G * N), C.reshape(b, S, G * N),
        jnp.repeat(D, P)[None],
    )
    rows = chunk * a_step
    wide = pl.BlockSpec((None, rows, hs * P), lambda i, g, c: (i, order(c), g))
    scalars = pl.BlockSpec((None, None, hs, rows), lambda i, g, c: (i, g, 0, order(c)))
    group = pl.BlockSpec((None, rows, N), lambda i, g, c: (i, order(c), g // parts))
    specs = dict(
        inputs=[wide, scalars, scalars, group, group, pl.BlockSpec((1, hs * P), lambda i, g, c: (0, g))],
        wide=wide, scalars=scalars,
        part=pl.BlockSpec((None, rows, N), lambda i, g, c: (i, order(c), g)),
        states=pl.BlockSpec((None, a_step, N, hs * P), lambda i, g, c: (i, order(c), 0, g)),
    )
    dims = SimpleNamespace(
        b=b, S=S, H=H, P=P, N=N, G=G, hs=hs, parts=parts, steps=steps, nc=nc, blocks=blocks
    )
    return arrays, dims, specs


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "save_states", "heads_a_step", "chunks_a_step")
)
def _forward(
    x, dt, cs, B, C, D, *, chunk, interpret, save_states,
    heads_a_step=HEADS_A_STEP, chunks_a_step=CHUNKS_A_STEP,
):
    """y [b, S, H P] and, with `save_states`, the state each chunk starts from
    [b, chunks, N, H P] float32 (else None)."""
    arrays, d, specs = _layout(x, dt, cs, B, C, D, chunk, heads_a_step, chunks_a_step)
    out_shape, out_specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)], [specs["wide"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((d.b, d.nc, d.N, x.shape[-1]), _f32))
        out_specs.append(specs["states"])
    out = pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk, head_dim=d.P, save_states=save_states),
        grid=(d.b, d.steps, d.blocks),
        in_specs=specs["inputs"],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((d.N, d.hs * d.P), _f32), pltpu.VMEM((chunk, d.hs * d.P), x.dtype),
            pltpu.VMEM((1, d.hs * d.P), _f32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_ssd_forward",
    )(*arrays)
    return (out[0], out[1]) if save_states else (out[0], None)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "heads_a_step", "chunks_a_step")
)
def _backward(
    x, dt, cs, B, C, D, before, dy, *, chunk, interpret,
    heads_a_step=HEADS_A_STEP, chunks_a_step=CHUNKS_A_STEP,
):
    """The cotangents of x, dt, cs, B, C and D, in their shapes and types."""
    arrays, d, specs = _layout(x, dt, cs, B, C, D, chunk, heads_a_step, chunks_a_step, reverse=True)
    b, S, H, hs, P = d.b, d.S, d.H, d.hs, d.P
    lanes = -(-hs // _LANES) * _LANES
    ones = (jnp.arange(hs * P)[:, None] // P == jnp.arange(lanes)[None]).astype(x.dtype)
    a_part = jax.ShapeDtypeStruct((b, S, d.steps * d.N), x.dtype)
    a_scalar = jax.ShapeDtypeStruct((b, d.steps, hs, S), _f32)
    wide = lambda dtype, rows=chunk: pltpu.VMEM((rows, hs * P), dtype)
    dx, dB, dC, ddt, dcs, dD = pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, head_dim=P),
        grid=(b, d.steps, d.blocks),
        in_specs=specs["inputs"] + [
            pl.BlockSpec(ones.shape, lambda i, g, c: (0, 0)), specs["states"], specs["wide"],
        ],
        out_specs=[
            specs["wide"], specs["part"], specs["part"], specs["scalars"], specs["scalars"],
            pl.BlockSpec((None, 8, hs * P), lambda i, g, c: (i, 0, g)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype), a_part, a_part, a_scalar, a_scalar,
            jax.ShapeDtypeStruct((b, 8, H * P), _f32),
        ],
        scratch_shapes=[
            wide(_f32, d.N), wide(x.dtype), wide(x.dtype), wide(_f32, 1), wide(_f32), wide(_f32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="_ssd_backward",
    )(*arrays, ones, before, dy)
    # A group in several steps: each step's share of dB and dC, summed here.
    of_group = lambda a: a.reshape(b, S, d.G, d.parts, d.N).astype(_f32).sum(axis=3).astype(B.dtype)
    dB, dC = (a.reshape(B.shape) if d.parts == 1 else of_group(a) for a in (dB, dC))
    return (
        dx, ddt.reshape(b, H, S), dcs.reshape(b, H, S), dB, dC,
        dD.reshape(b, 8, H, P).sum(axis=(0, 1, 3)),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _core(x, dt, cs, B, C, D, chunk, interpret):
    return _forward(x, dt, cs, B, C, D, chunk=chunk, interpret=interpret, save_states=False)[0]


def _core_fwd(x, dt, cs, B, C, D, chunk, interpret):
    y, before = _forward(x, dt, cs, B, C, D, chunk=chunk, interpret=interpret, save_states=True)
    return y, (x, dt, cs, B, C, D, before)


def _core_bwd(chunk, interpret, res, dy):
    return _backward(*res, dy, chunk=chunk, interpret=interpret)


_core.defvjp(_core_fwd, _core_bwd)


def ssd(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array,
    chunk: int, interpret: bool = False,
) -> jax.Array:
    """`ops/ssd.ssd` through the kernels: x [b, S, H, P], dt [b, S, H]
    (positive), A [H] (negative), B and C [b, S, G, N], D [H] -> y [b, S, H, P]
    in x's type.  For the shapes `takes_kernel` names."""
    b, S, H, P = x.shape
    # The per-(token, head) scalars with the tokens along the lanes, [b, H, S].
    dt = dt.astype(_f32).transpose(0, 2, 1)
    cs = jnp.cumsum((dt * A.astype(_f32)[:, None]).reshape(b, H, S // chunk, chunk), axis=-1)
    y = _core(
        x.reshape(b, S, H * P), dt, cs.reshape(b, H, S), B, C, D.astype(_f32), chunk,
        bool(interpret),
    )
    return y.reshape(b, S, H, P)
