"""Flash attention as a Pallas TPU kernel.

The reference has no attention at all (vision-era stack; SURVEY §5
"long-context: absent"), so this kernel exists for the framework's own
transformer flagships (BERT, Llama-3).  Design is TPU-first:

- Forward is a blockwise online-softmax kernel: grid
  ``(batch, heads, q_blocks, kv_blocks)``; the kv axis is the innermost
  (sequential) grid dimension, so the running max/denominator/accumulator
  live in VMEM scratch across kv steps and the [S, S] score matrix is never
  materialized in HBM.  Scores/softmax in f32 on the MXU via
  ``preferred_element_type``; inputs stay bf16.
- Causal blocks that are entirely masked are skipped with ``@pl.when``
  (compute is predicated off, the MXU never sees them).
- Grouped-query attention is handled in the BlockSpec index maps (a kv head
  is fetched for ``group = Hq // Hkv`` query heads) — no materialized
  ``repeat`` anywhere, forward or backward.
- Backward: ``custom_vjp`` whose backward pass is a blockwise ``lax.scan``
  recomputation from the saved log-sum-exp — O(S) activation memory,
  standard flash-attention-2 residual strategy.  It is plain XLA (fuses
  fine on TPU); the forward hot path is the Pallas kernel.
- Mesh-aware: pass ``mesh=`` and the kernel runs under ``shard_map`` with
  batch sharded over (dp, fsdp) and heads over tp — attention is
  independent per (batch, head), so each shard computes locally with no
  collectives.  Sequence sharding (sp > 1) is NOT this kernel's job; that
  is ring attention (parallel/ring_attention.py).
- The kernel compiles through Mosaic and so needs a TPU; it does not guess
  its backend.  Callers choose: ``models/llama.attention_kind`` only picks
  this kernel on a ``tpu`` backend and takes ops.attention
  .dot_product_attention everywhere else.  ``interpret=True`` runs the same
  kernel body in the Pallas interpreter — grid-sequential and slow, for the
  CPU tests that ask for it by name, never a fallback.

Layout contract matches ops/attention.py: [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Measured on v5e (S=2048, H=8, D=64, bf16): 512x512 blocks run the
# forward ~40% faster than 128x128 (4.7 ms vs 6.5 ms), and 1024x512 is
# the measured best (3.78 ms — docs/BENCH_NOTES.md block sweep), so it is
# the default.  Small-S inputs clamp down to the sequence length, so
# large defaults cost nothing for short sequences.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 512

# Below this sequence length XLA's fused attention wins on v5e (measured:
# 3.74 ms XLA vs 4.69 ms flash at S=2048 with 512 blocks; flash pulls
# ahead from S=2048 with 1024x512 blocks and is 2x faster by S=4096).
# Dispatchers (models/llama.py) fall back to XLA attention under this.
FLASH_CROSSOVER_SEQ = 2048

# Sublane tile granularity: 16 covers both f32 (8) and bf16 (16) tiles, so
# clamped block sizes always satisfy Mosaic's (sublane, lane) constraints.
_SUBLANE = 16


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _attn_kernel(
    q_ref,  # [1, 1, Bq, D]
    k_ref,  # [1, 1, Bk, D]
    v_ref,  # [1, 1, Bk, D]
    out_ref,  # [1, 1, Bq, D]
    lse_ref,  # [1, 1, Bq, 128] (lane-replicated; TPU min tile is (8, 128))
    acc_ref,  # VMEM [Bq, D] f32
    m_ref,  # VMEM [Bq, 128] f32 (running max; lane-replicated)
    l_ref,  # VMEM [Bq, 128] f32 (running denominator)
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    kv_len: int,
    need_lse: bool,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    if causal:
        # Entire block above the diagonal → skip all compute.
        run = k_start <= q_start + block_q - 1
    else:
        run = qi >= 0  # always true, but traced so @pl.when is uniform

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]  # [Bq, D]
        k = k_ref[0, 0]  # [Bk, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Bq, Bk] f32
        s = s * sm_scale
        # Mask: causal and kv padding.
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [Bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [Bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows with no valid key yet keep m = -inf; exp(NEG_INF - NEG_INF)
        # would be exp(0) = 1, so clamp the shift for fully-masked rows.
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)  # [Bq, Bk]
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(
            m_prev <= NEG_INF / 2, jnp.zeros_like(m_prev), jnp.exp(m_prev - shift)
        )
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        denom = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_ref[:] / denom).astype(out_ref.dtype)
        if need_lse:
            lse = jnp.where(
                l == 0.0, jnp.full_like(m, NEG_INF), m + jnp.log(denom)
            )
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _pad_seq(x: jax.Array, block: int) -> jax.Array:
    s = x.shape[1]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "block_q", "block_k", "interpret", "need_lse"
    ),
)
def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    need_lse: bool = True,
):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv

    qt = jnp.swapaxes(_pad_seq(q, block_q), 1, 2)  # [B, Hq, Sq', D]
    kt = jnp.swapaxes(_pad_seq(k, block_k), 1, 2)  # [B, Hkv, Sk', D]
    vt = jnp.swapaxes(_pad_seq(v, block_k), 1, 2)
    sq_p, sk_p = qt.shape[2], kt.shape[2]
    nq, nk = sq_p // block_q, sk_p // block_k

    grid = (B, Hq, nq, nk)
    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        kv_len=Sk,
        need_lse=need_lse,
    )
    if need_lse:
        # Lane-replicated LSE ([..., 128] f32) — the TPU min-tile layout for
        # per-row stats (same shape jax's own TPU flash kernel uses for l/m).
        lse_spec = pl.BlockSpec((1, 1, block_q, 128), lambda b, h, i, j: (b, h, i, 0))
        lse_shape = jax.ShapeDtypeStruct((B, Hq, sq_p, 128), jnp.float32)
    else:
        # Inference: XLA cannot DCE a pallas output, so shrink it to one
        # dummy tile that every grid step aliases and nothing writes.
        lse_spec = pl.BlockSpec((1, 1, 8, 128), lambda b, h, i, j: (0, 0, 0, 0))
        lse_shape = jax.ShapeDtypeStruct((1, 1, 8, 128), jnp.float32)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, sq_p, D), q.dtype),
            lse_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # batch/head/q blocks are independent (megacore-splittable); only
            # the kv axis is sequential — it carries the VMEM accumulator.
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # The kernel's name in the compiled program and in a profile
        # (`_flash_forward.<n>`), said here so that it no longer hangs on
        # the name of the jitted function around it.  The benchmark's
        # attention_roofline_share finds the kernel by this name.
        name="_flash_forward",
    )(qt, kt, vt)
    out = jnp.swapaxes(out, 1, 2)[:, :Sq]  # [B, Sq, Hq, D]
    if not need_lse:
        return out, None
    return out, lse[:, :, :Sq, 0]  # [B, Hq, Sq]


# --- memory-efficient backward (blockwise scan, plain XLA) ---------------


def _blockwise_backward(res, g, *, causal: bool, sm_scale: float, block_k: int):
    """Recompute p blockwise from the saved LSE and accumulate dq/dk/dv with
    a scan over kv blocks — never materializes [Sq, Sk] and never expands
    the kv heads: the GQA group lives as an explicit einsum axis."""
    q, k, v, out, lse = res
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv

    # [B, Sq, Hkv, group, D] views; contractions below run in f32 on the MXU
    # via preferred_element_type without materializing f32 copies.
    qg = q.reshape(B, Sq, Hkv, group, D)
    gg = g.reshape(B, Sq, Hkv, group, D)
    # delta_i = sum_d out_i * dout_i  (FA2 trick: dp_ij - delta_i term)
    delta = jnp.einsum(
        "bqhgd,bqhgd->bqhg",
        out.reshape(B, Sq, Hkv, group, D),
        gg,
        preferred_element_type=jnp.float32,
    )
    lse_g = lse.reshape(B, Hkv, group, Sq).transpose(0, 3, 1, 2)  # [B,Sq,Hkv,g]

    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    nk = kp.shape[1] // block_k
    kb = jnp.moveaxis(kp.reshape(B, nk, block_k, Hkv, D), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, block_k, Hkv, D), 1, 0)

    q_pos = jnp.arange(Sq)
    f32 = jnp.float32

    def kv_block(dq_acc, blk):
        k_blk, v_blk, j = blk  # [B, Bk, Hkv, D], kv-block index
        k_pos = j * block_k + jnp.arange(block_k)
        s = (
            jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_blk, preferred_element_type=f32)
            * sm_scale
        )
        mask = k_pos[None, :] < Sk
        if causal:
            mask = jnp.logical_and(mask, k_pos[None, :] <= q_pos[:, None])
        mask = mask[None, :, None, None, :]  # [1, Sq, 1, 1, Bk]
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.where(mask, jnp.exp(s - lse_g[..., None]), 0.0)  # [B,Sq,Hkv,g,Bk]
        dv_blk = jnp.einsum("bqhgk,bqhgd->bkhd", p, gg, preferred_element_type=f32)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", gg, v_blk, preferred_element_type=f32)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq_acc = dq_acc + jnp.einsum(
            "bqhgk,bkhd->bqhgd", ds, k_blk, preferred_element_type=f32
        )
        dk_blk = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qg, preferred_element_type=f32)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Sq, Hkv, group, D), f32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_block, dq0, (kb, vb, jnp.arange(nk))
    )
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(B, nk * block_k, Hkv, D)[:, :Sk]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(B, nk * block_k, Hkv, D)[:, :Sk]
    return (
        dq.reshape(B, Sq, Hq, D).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


# --- custom-vjp core (arrays only; mesh handled by the public wrapper) ---


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    # Primal-only path (no grad being taken): skip the LSE output entirely.
    bq = _clamp_block(block_q, q.shape[1])
    bk = _clamp_block(block_k, k.shape[1])
    out, _ = _flash_forward(
        q, k, v, causal, sm_scale, bq, bk, interpret, need_lse=False
    )
    return out


# A larger block is kept over a smaller one unless the smaller block's
# padded length saves more than this fraction — the MXU-efficiency gap
# between block sizes (40% from 128 to 512, BENCH_NOTES) dwarfs
# single-digit padding savings.
_PAD_TOLERANCE = 0.125
# Blocks below 128 underutilize the MXU (128x128 systolic array); never
# step below it for padding reasons when the sequence allows 128.
_MIN_MXU_BLOCK = 128


def _clamp_block(block: int, seq: int) -> int:
    """Effective block size: the largest candidate <= ``block`` whose
    padded sequence length ``round_up(seq, b)`` is within
    ``_PAD_TOLERANCE`` of the minimum, with candidates floored at the MXU
    tile (128) whenever the sequence reaches it.

    Large blocks run fastest on the MXU (docs/BENCH_NOTES.md: 512x512 is
    ~40% faster than 128x128 at S=2048), but padding cost grows with the
    block: a ragged S=600 under a 512 block pads to 1024 (~2.5x the
    attention FLOPs of a 128 block's 640).  Strictly minimizing padding
    overshoots the other way — S=600 would pick a 32 block (padded 608)
    over 128 (padded 640), trading ~5% padding for a far larger MXU
    efficiency loss — hence the floor and the tolerance."""
    seq_t = _round_up(max(seq, _SUBLANE), _SUBLANE)
    floor = min(_MIN_MXU_BLOCK, seq_t)
    candidates = []
    b = _round_up(block, _SUBLANE)
    while b >= floor:
        candidates.append((b, _round_up(seq_t, b)))
        if b > floor and b // 2 < floor:
            b = floor  # non-power-of-two ladders must still consider the floor
        else:
            b //= 2
    if not candidates:  # block < floor: honor the caller's small block
        return min(_round_up(block, _SUBLANE), seq_t)
    min_padded = min(p for _, p in candidates)
    # Largest (descending order) candidate within tolerance of the best
    # padding; the min_padded candidate itself always qualifies.
    best = next(
        b
        for b, padded in candidates
        if padded <= min_padded * (1.0 + _PAD_TOLERANCE)
    )
    return min(best, seq_t)


def _core_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    bq = _clamp_block(block_q, q.shape[1])
    bk = _clamp_block(block_k, k.shape[1])
    out, lse = _flash_forward(q, k, v, causal, sm_scale, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _core_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    del block_q, interpret
    bk = _clamp_block(block_k, res[1].shape[1])
    # The backward is plain XLA: the scope is what tells its fusions from
    # the rest of the step's in a profile.
    with jax.named_scope("attn_bwd"):
        return _blockwise_backward(
            res, g, causal=causal, sm_scale=sm_scale, block_k=bk
        )


_flash_core.defvjp(_core_fwd, _core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Flash attention, [B, S, H, D] in/out, GQA-aware (Hkv must divide Hq).

    Compiled Mosaic kernel unless ``interpret=True`` (the Pallas
    interpreter: slow, for CPU tests — see module docstring).  Off a TPU
    the compiled form raises; nothing here falls back.

    ``mesh``: when given and any of dp/fsdp/tp is > 1, the kernel runs under
    ``shard_map`` with batch sharded over (dp, fsdp) and heads over tp; the
    sequence axis must be unsharded (use ring attention for sp > 1).
    """
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hkv == 0 or Hq % Hkv != 0:
        raise ValueError(f"q heads ({Hq}) must be a multiple of kv heads ({Hkv})")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    sm_scale = float(sm_scale)
    interpret = bool(interpret)

    def core(q, k, v):
        # nondiff argnums must be positional for custom_vjp
        return _flash_core(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        raise ValueError(
            "flash_attention does not shard the sequence axis; use "
            "parallel.ring_attention for sp > 1"
        )
    if mesh is not None and any(mesh.shape.get(a, 1) > 1 for a in ("dp", "fsdp", "tp")):
        # tp shards the head axis of q AND kv alike, so the per-shard GQA
        # group mapping is preserved whenever tp divides Hkv.
        tp = mesh.shape.get("tp", 1)
        if Hkv % tp != 0:
            raise ValueError(f"tp={tp} must divide kv heads ({Hkv})")
        spec = P(("dp", "fsdp"), None, "tp", None)
        return jax.shard_map(
            core,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    return core(q, k, v)
