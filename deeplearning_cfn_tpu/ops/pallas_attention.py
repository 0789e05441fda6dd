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
- The causal triangle, the same in every kernel (forward, dk/dv or fused, dq):
  a (q block, kv block) pair wholly above the diagonal is neither computed
  (``_run_pair``) nor fetched (its BlockSpec index names the block of the
  nearest step that runs, so the pipeline has nothing to copy); a pair
  wholly inside the triangle, with no padding in it, runs without a mask:
  no iota, compare or select over the tile; only a pair on the diagonal or
  over padding builds the mask (``_pair_mask``).
- A sliding window (``window=W``, causal only): query t sees the keys j with
  ``t - W < j <= t``, W keys with its own (the mask ``sliding_window`` gives
  in `transformers`).  The window is a lower edge in the same rule: a pair
  wholly behind it is neither computed nor fetched, a pair its edge crosses
  builds the mask.  Windowed calls also walk a shorter grid: the kv axis has
  as many steps as the widest q block's band holds kv blocks, counted from
  the band's first block (the dk/dv kernel's q axis likewise), so that a
  sequence of 8192 under a window of 512 does not pay a grid step for each of
  its skipped pairs.  Their kernels carry names of their own
  (``_window_flash_forward``, ``_window_flash_backward_dkv``,
  ``_window_flash_backward_dq``): the benchmark's readers find the
  full-causal kernels by the prefixes ``_flash_forward`` / ``_flash_backward``
  and divide by a full-causal cost.
- The band step (``_window_flash_forward_band``): a windowed forward call
  whose window is whole lane tiles (128..1024) and shorter than the sequence,
  from a caller that names no tiles, takes a q block of ``window`` rows with
  its whole band, the two aligned kv blocks, in one grid step.  The kv axis
  and the online softmax go: one maximum, one exponential and one sum over
  scores that are all inside the band, rows in chunks that skip the band's
  empty sub-tiles, the log-sum-exp written as a compact row.  The shapes
  choose (`_takes_band_step`), no argument does; every other windowed call
  keeps the grid above, and the backward kernels are the same for both.
- Grouped-query attention is handled in the BlockSpec index maps (a kv head
  is fetched for ``group = Hq // Hkv`` query heads) — no materialized
  ``repeat`` anywhere, forward or backward.
- Backward: ``custom_vjp`` with the flash-attention-2 residuals (q, k, v,
  out and the log-sum-exp: O(S) activation memory) and Pallas kernels that
  recompute the probabilities blockwise from the log-sum-exp, so the
  score-sized tensors s, p, dp, ds live in VMEM only.  Without a window, one
  fused kernel (``_flash_backward_fused``), grid ``(batch, kv_heads,
  kv_blocks, group * q_blocks)``: it folds the ``group`` query heads of a kv
  head into its sequential axis, writes dk and dv once per kv head, and adds
  every pair's dq into a float32 block of the whole group that stays in VMEM
  while the grid walks the kv head: five block matmuls a pair.  Under a
  window, or where that block does not fit (`_takes_fused_backward`: the
  shapes choose, no argument does), the pair it grew from: the same dk/dv
  kernel without the third result, and a dq kernel, grid ``(batch, heads,
  q_blocks, kv_blocks)``, seven matmuls a pair between them.  Only ``delta =
  rowsum(out * dout)``, dq's scale and cast and the layout changes around the
  kernels are XLA.
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
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# The forward kernel's (q block, kv block), from a sweep on v5e at both decoder
# cells' shapes (scripts/chip_grouped_matmul_sweep.py attention, PR 29; B 2,
# bf16, causal; ms a call, kv block 512 / 1024 / 2048):
#   S 4096, 32/8 heads of 128     S 8192, 20/20 heads of 256
#   q  512: 5.39  3.49  3.75      14.64  12.17  13.12
#   q 1024: 5.15  3.00  3.68      12.39  11.00  12.44
#   q 2048: 5.44  3.52  (VMEM)    12.65  11.59  (VMEM)
# Both shapes want 1024x1024 (the next best is 16% and 5% behind), so the head
# size is not an input of the choice.  A kv block of 512 is slower here than it
# was under the kernel that masked every pair (4.65 and 14.00 at 1024x512).
# Small-S inputs clamp down to the sequence length (`_clamp_block`), so large
# defaults cost nothing for short sequences.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Float32 [1024, 1024] score tiles do not fit the default scoped limit
# (16 MiB) at heads of 256; 32 MiB holds them up to heads of 512.  Not the
# backward's 64 MiB: under it the compiler schedules this kernel 5% slower at
# heads of 128 (3.17 against 3.00 ms a call at S 4096; 24 and 32 MiB read the
# same, 48 and 100 MiB 3.08), and no differently at heads of 256.
_FWD_VMEM_LIMIT = 32 * 1024 * 1024
# The backward kernels' (q block, kv block), from a sweep on v5e at the
# decoder cell's shape (B 2, S 4096, 32/8 heads of 128, bf16;
# scripts/chip_attention_backward_sweep.py, PR 25): 1024x1024 runs the dk/dv
# kernel in 3.98 ms and the dq kernel in 3.47 ms a call, 512x512 in 4.34 and
# 3.85, 256x256 in 8.95 and 6.74.  At S 2048 / D 64 (16/4 heads) 512x512 is
# the best by a tenth (0.27 + 0.21 ms against 0.29 + 0.23).
BWD_DKV_BLOCKS = (1024, 1024)
BWD_DQ_BLOCKS = (1024, 1024)
# Four float32 [1024, 1024] tiles (s, p, dp, ds) are 16 MB, the default
# scoped VMEM limit by themselves; a v5e core has 128 MiB.
_BWD_VMEM_LIMIT = 64 * 1024 * 1024
# The fused backward kernel's (q block, kv block), from a sweep on v5e at the
# decoder cells' full-causal shapes (scripts/chip_attention_backward_sweep.py,
# PR 44; bf16, causal; ms a call by the device trace; the pair is the dk/dv and
# the dq kernel at their own 1024 x 1024, the fused kernel at q block x kv
# block 512x512 / 512x1024 / 1024x512 / 1024x1024):
#   B, S, heads q/kv of D          pair                 fused
#   2, 4096, 32/8 of 128   (Mistral)   3.98 + 3.47    5.33   5.27   5.30   4.96
#   1, 8192, 16/16 of 128  (Ouro)      3.54 + 2.93    4.98   4.67   4.68   4.35
#   2, 8192, 20/20 of 256  (GLM)      16.87 + 13.87  22.25  21.90  21.92  20.98
#   2, 8192, 32/8 of 64    (LFM2)     13.96 + 11.73  20.37  18.94  18.97  17.68
#   2, 8192, 48/8 of 128   (Laguna)   20.93 + 17.48  30.30  28.31  28.37  26.54
#   1, 8192, 32/2 of 128   (Nemotron)  7.00 + 5.96   132-140 MB of VMEM's 128
#   1, 2048, 16/4 of 64                0.30 + 0.23    0.33   0.37   0.38   0.36
# 0.67-0.69 of the pair at every shape that fits, under the 5/7 of the matmul
# count: the exponential, the mask and ds are made once a pair too.  At Ouro's
# shape the five matmuls of its 576 pairs run at 178 TFLOP/s, 90% of the MXU's
# peak; the transposed-left matmul's pass over ds^T hides under them.  The
# whole backward on the host's clock at 1024 x 1024, pair -> fused: 9.20 ->
# 6.41, 7.81 -> 5.50, 31.92 -> 22.41, 29.06 -> 20.74, 42.73 -> 30.52.  On the
# chip the three gradients equal the pair's bit for bit at every row.
BWD_FUSED_BLOCKS = (1024, 1024)
# What the fused kernel's resident dq (`_fused_dq_bytes`: a kv head's group,
# float32, both of Pallas's buffers) may take of VMEM beside `_BWD_VMEM_LIMIT`;
# the call's limit is the sum, 120 MiB of a v5e core's 128 at most.  Laguna's
# groups of 6 take 48 MiB and fit; Nemotron's groups of 16 would take 128 and
# run the pair, as does any call past S 57,344 at groups of 1 and heads of 128.
_BWD_FUSED_DQ_BUDGET = 56 * 1024 * 1024
# The tiles of calls with a window, (q block, kv block) of the forward, the
# dk/dv and the dq kernel, from a sweep on v5e at the window layers' shape of
# `laguna-xs.2.train-s8192` (scripts/chip_grouped_matmul_sweep.py window, PR 36;
# B 2, S 8192, 64/8 heads of 128, window 512, bf16; ms a call, kv block 256 /
# 512 / 1024):
#             forward               dk/dv                 dq
#   q  256: 16.23  11.67  11.65    10.34   9.59  11.18    9.45   7.99  11.97
#   q  512: 18.64  10.27   9.82     9.24   8.02  10.79    9.16   6.49  10.80
#   q 1024: 24.22  11.51  10.84    14.31  11.94  13.48   10.32   8.82  11.28
#   band:    2.91 (rows in chunks of 256; 3.21 the whole block, 3.44 chunks of 128)
# A call's band holds 0.27 TFLOP forward (1.35 ms at the chip's peak).  The
# tiled forward is not bound by its matmuls: the grid steps that run a pair
# take 1.36 / 1.47 / 1.98 us at a q block of 256, 2.35 / 2.59 / 3.34 at 512 and
# 4.11 / 3.91 / 5.65 at 1024, of which both tile matmuls at the peak are 12-48%
# (41% at 512 x 1024); the rest, the softmax's passes over the tile, the running
# statistics and the accumulator's round trip, follows the q block's rows and
# is paid on every kv step.  The band step (`_band_forward`: what a call runs
# that `_takes_band_step`, this shape among them) pays it once a q block: 2,048
# steps of 1.42 us, 70% of them its matmuls at the peak, 46% of the band's
# roofline where 512 x 1024 reaches 14%.  `WINDOW_FWD_BLOCKS` is what the other
# windowed calls run.  The backward pair reaches 23%: every pair it runs at
# 512 x 512 is a masked one with half of its scores inside the band.  Beside
# them the full-causal kernels at the cell's 48/8 heads: 15.13 ms forward,
# 20.93 + 17.48 backward.
WINDOW_FWD_BLOCKS = (512, 1024)
WINDOW_BWD_DKV_BLOCKS = (512, 512)
WINDOW_BWD_DQ_BLOCKS = (512, 512)

# Below this sequence length XLA's fused attention wins on v5e (measured:
# 3.74 ms XLA vs 4.69 ms flash at S=2048 with 512 blocks; flash pulls
# ahead from S=2048 with 1024x512 blocks and is 2x faster by S=4096).
# Dispatchers (models/llama.py) fall back to XLA attention under this.
# Measured against the forward kernel as it was before PR 29 (at S 2048 the
# new one takes 0.49 ms where that took 0.70, 32/8 heads of 128); no cell
# runs below the crossover, so it stays until one can measure a new value.
FLASH_CROSSOVER_SEQ = 2048

# Sublane tile granularity: 16 covers both f32 (8) and bf16 (16) tiles, so
# clamped block sizes always satisfy Mosaic's (sublane, lane) constraints.
_SUBLANE = 16


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --- what the three kernels share: which pairs run, which are masked -------
#
# Every kernel walks (q block, kv block) pairs.  A pair wholly above the
# causal diagonal is not computed (`_run_pair`) and not fetched
# (`_kv_index_map`, `_backward_dkv`'s `q_index`); a pair wholly inside the
# triangle, with no padding in it, runs without a mask; only a pair on the
# diagonal or over padding builds one (`_pair_mask`).


def _pair_mask(rows, cols, q_axis: int, q_start, k_start, *, causal, q_len, kv_len, window=None):
    """Validity of a [rows, cols] tile of scores whose axis ``q_axis`` runs
    over query positions and the other over key positions: the key is real
    (not kv padding), the query is real (not q padding: a padded row's lse
    is 0 and its scores are, so nothing overflows, but it attends nothing),
    the key is not after the query, and under a window not ``window`` or
    more positions before it."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1 - q_axis)
    mask = jnp.logical_and(k_pos < kv_len, q_pos < q_len)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    return mask


def _run_pair(pair, q_start, k_start, *, causal, block_q, block_k, q_len, kv_len, window=None):
    """Run ``pair(masked)`` for one (q block, kv block): not at all where the
    pair lies wholly above the causal diagonal or wholly behind the window,
    and with the mask only where a score of it is masked, because it holds
    padding, (causal) its last key lies after its first query or (window) its
    first key lies ``window`` or more before its last query.  Pairs wholly
    inside the band skip the iotas, compares and selects.  A windowed grid
    counts its blocks from the band's edge and may step past the last block:
    such a step is not live either."""
    masked = jnp.logical_or(k_start + block_k > kv_len, q_start + block_q > q_len)
    live = True
    if causal:
        masked = jnp.logical_or(masked, k_start + block_k - 1 > q_start)
        live = k_start <= q_start + block_q - 1
    if window is not None:
        masked = jnp.logical_or(masked, k_start <= q_start + block_q - 1 - window)
        behind = k_start + block_k - 1 <= q_start - window
        outside = jnp.logical_or(k_start >= kv_len, q_start >= q_len)
        live = jnp.logical_and(live, jnp.logical_not(jnp.logical_or(behind, outside)))
    pl.when(jnp.logical_and(live, masked))(lambda: pair(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(masked)))(lambda: pair(False))


def _first_kv_block(q_start, *, window: int, block_k: int):
    """The kv block that holds the first key a q block starting at ``q_start``
    sees under the window: where a windowed grid's kv axis starts."""
    return jnp.maximum(q_start - window + 1, 0) // block_k


def _first_q_block(k_start, *, block_q: int):
    """The first q block that sees a key of the kv block starting at
    ``k_start``: where a windowed dk/dv grid's q axis starts."""
    return k_start // block_q


def _kv_steps(nq: int, nk: int, block_q: int, block_k: int, window: int) -> int:
    """Steps of a windowed grid's kv axis: the most kv blocks any q block's
    band covers, from its first visible key to its last query (static)."""
    return max(
        min(((i + 1) * block_q - 1) // block_k, nk - 1)
        - max(i * block_q - window + 1, 0) // block_k + 1
        for i in range(nq)
    )


def _q_steps(nq: int, nk: int, block_q: int, block_k: int, window: int) -> int:
    """Steps a query head takes on a windowed dk/dv grid's q axis: the most q
    blocks that see any one kv block, from its first key's query to the last
    query that still sees its last key (static)."""
    return max(
        min((j * block_k + block_k + window - 2) // block_q, nq - 1) - (j * block_k) // block_q + 1
        for j in range(nk)
    )


def _kv_index_map(
    *, causal: bool, group: int, block_q: int, block_k: int, nk: int, window: int | None = None
):
    """Index map of a k or v block [1, 1, Bk, D] on a grid (batch, query head,
    q block, kv block), the kv head being the query head's group.  Under
    causal a step above the diagonal names the block of the last step that
    runs, so that nothing is fetched for it.  Under a window the grid's kv
    axis counts from the first block of the q block's band."""

    def kv_index(b, h, i, j):
        if window is not None:
            j = j + _first_kv_block(i * block_q, window=window, block_k=block_k)
        if causal:
            j = jnp.minimum(j, jnp.minimum(((i + 1) * block_q - 1) // block_k, nk - 1))
        return b, h // group, j, 0

    return kv_index


def _compiler_params(vmem_limit: int, carried_axes: int = 1) -> pltpu.CompilerParams:
    # batch, head and the held block are independent (megacore-splittable);
    # the last grid axis walks the other operand's blocks and carries the
    # VMEM accumulators (the last two where the fused backward's dq is carried
    # over the held blocks too).
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (4 - carried_axes) + ("arbitrary",) * carried_axes,
        vmem_limit_bytes=vmem_limit,
    )


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
    q_len: int,
    kv_len: int,
    need_lse: bool,
    window: int | None = None,
):
    ki = pl.program_id(3)
    q_start = pl.program_id(2) * block_q
    if window is None:
        k_start = ki * block_k
    else:
        k_start = (ki + _first_kv_block(q_start, window=window, block_k=block_k)) * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def pair(masked: bool):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Bq, Bk] f32
        s = s * sm_scale
        if masked:
            mask = _pair_mask(
                block_q, block_k, 0, q_start, k_start,
                causal=causal, q_len=q_len, kv_len=kv_len, window=window,
            )
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [Bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if masked:
            # Rows with no valid key yet keep m = -inf; exp(NEG_INF - NEG_INF)
            # would be exp(0) = 1, so clamp the shift for fully-masked rows.
            shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            p = jnp.where(mask, jnp.exp(s - shift), 0.0)  # [Bq, Bk]
            alpha = jnp.where(
                m_prev <= NEG_INF / 2, jnp.zeros_like(m_prev), jnp.exp(m_prev - shift)
            )
        else:
            # Every score is a real one, so m_new is, and the guards above
            # would select what is computed here: exp(NEG_INF - m_new) is 0.
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _run_pair(
        pair, q_start, k_start, causal=causal, block_q=block_q, block_k=block_k,
        q_len=q_len, kv_len=kv_len, window=window,
    )

    @pl.when(ki == pl.num_programs(3) - 1)
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
        "causal", "sm_scale", "block_q", "block_k", "interpret", "need_lse", "window"
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
    window: int | None = None,
):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv

    qt = jnp.swapaxes(_pad_seq(q, block_q), 1, 2)  # [B, Hq, Sq', D]
    kt = jnp.swapaxes(_pad_seq(k, block_k), 1, 2)  # [B, Hkv, Sk', D]
    vt = jnp.swapaxes(_pad_seq(v, block_k), 1, 2)
    sq_p, sk_p = qt.shape[2], kt.shape[2]
    nq, nk = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        q_len=Sq,
        kv_len=Sk,
        need_lse=need_lse,
        window=window,
    )
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D),
        _kv_index_map(
            causal=causal, group=group, block_q=block_q, block_k=block_k, nk=nk, window=window
        ),
    )
    kv_steps = nk if window is None else _kv_steps(nq, nk, block_q, block_k, window)
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
        grid=(B, Hq, nq, kv_steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, sq_p, D), q.dtype),
            lse_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(_FWD_VMEM_LIMIT),
        interpret=interpret,
        # The kernel's name in the compiled program and in a profile
        # (`_flash_forward.<n>`), said here so that it no longer hangs on
        # the name of the jitted function around it.  The benchmark's
        # attention_roofline_share finds the kernel by this name, and divides
        # by a full-causal call's cost: a windowed call has a name of its own.
        name="_flash_forward" if window is None else "_window_flash_forward",
    )(qt, kt, vt)
    out = jnp.swapaxes(out, 1, 2)[:, :Sq]  # [B, Sq, Hq, D]
    if not need_lse:
        return out, None
    return out, lse[:, :, :Sq, 0]  # [B, Hq, Sq]


# --- the band step: a windowed forward call whose q block is the window -----
#
# With ``block_q == window == W`` (S padded to whole blocks) q block i sees keys
# of two aligned kv blocks only, i - 1 and i, in complementary triangles: at row
# r and column c of the block, key c of block i - 1 where ``c > r`` and key c of
# block i where ``c <= r``.  Both blocks are resident, so one grid step holds the
# q block's whole band: one maximum, one exponential and one sum over scores that
# are all real ones, no running statistics, no accumulator, no second visit.
# Padding needs no mask: only padded rows see padded keys, and they are cut off.


def _takes_band_step(window: int | None, block_q, block_k, seq_q: int, seq_k: int) -> bool:
    """Whether a forward call runs the band step, from what the call can see:
    a window that is whole lane tiles and whose float32 [W, W] score tile fits
    `_FWD_VMEM_LIMIT`, shorter than the sequence it attends within, and a caller
    that named no tiles.  Every other windowed call walks `_attn_kernel`'s grid."""
    return (
        window is not None and block_q is None and block_k is None
        and window % 128 == 0 and 128 <= window <= 1024
        and seq_q == seq_k and seq_q > window
    )


# Rows of a q block the band step takes at a time, where that divides the
# window (else the whole block): sub-tiles of the resident band.  A chunk of
# rows [a, a + C) sees the own block's columns before a whole, the block
# before's columns from a + C on whole, and the two triangles in the C columns
# between, so it runs (W + C) / 2W of the matmuls of a whole-block step and
# builds the triangle's select on [C, C] alone.  On v5e at the Laguna cell's
# shape (W 512; scripts/chip_grouped_matmul_sweep.py window, PR 36) 256 runs a
# call in 2.91 ms (1.42 us a step, 70% of it the matmuls at the peak), the
# whole block in 3.21 (84%: the MXU binds) and 128 in 3.44 (49%: matmuls of
# 128 rows run far from the peak).  Other windows were compiled, not timed.
_BAND_ROW_CHUNK = 256


def _band_kernel(
    q_ref,  # [1, 1, W, D]
    k_prev_ref,  # [1, 1, W, D]: kv block i - 1 (block 0 again where i is 0)
    k_own_ref,  # [1, 1, W, D]: kv block i
    v_prev_ref,
    v_own_ref,
    out_ref,  # [1, 1, W, D]
    lse_ref,  # [1, 1, 1, W]: a row along the lanes, as the dk/dv kernel reads it
    *,
    sm_scale: float,
    window: int,
    need_lse: bool,
):
    chunk = _BAND_ROW_CHUNK if window % _BAND_ROW_CHUNK == 0 else window
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    before = col > row  # where the block before is seen; elsewhere the q block's own

    def scores(q, k_rows):  # [chunk, n] float32, scaled
        nt = (((1,), (1,)), ((), ()))
        return jax.lax.dot_general(q, k_rows, nt, preferred_element_type=jnp.float32) * sm_scale

    def weighted(p, v_rows):  # [chunk, D] float32
        nn = (((1,), (0,)), ((), ()))
        return jax.lax.dot_general(
            p.astype(v_rows.dtype), v_rows, nn, preferred_element_type=jnp.float32
        )

    def step(first: bool):
        """``first``: the q block with no block before it, whose band is the
        causal triangle of its own block, op for op what `_attn_kernel`
        computes for a diagonal pair from fresh statistics."""
        for a in range(0, window, chunk):
            rows = slice(a, a + chunk)
            # the columns the chunk sees whole: the own block's before its rows,
            # the block before's past them
            whole = [(k_own_ref, v_own_ref, slice(0, a))] if a > 0 else []
            if not first and a + chunk < window:
                whole.append((k_prev_ref, v_prev_ref, slice(a + chunk, window)))
            q = q_ref[0, 0, rows, :]
            own = scores(q, k_own_ref[0, 0, rows, :])
            if first:
                tiles = [jnp.where(before, NEG_INF, own)]
            else:
                tiles = [jnp.where(before, scores(q, k_prev_ref[0, 0, rows, :]), own)]
            tiles += [scores(q, k_ref[0, 0, cols, :]) for k_ref, _, cols in whole]
            m = functools.reduce(jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in tiles])
            # exp(NEG_INF - m) is 0: every row holds its own key
            tiles = [jnp.exp(s - m) for s in tiles]
            l = functools.reduce(jnp.add, [jnp.sum(p, axis=1, keepdims=True) for p in tiles])
            if first:
                acc = weighted(tiles[0], v_own_ref[0, 0, rows, :])
            else:
                acc = weighted(jnp.where(before, 0.0, tiles[0]), v_own_ref[0, 0, rows, :])
                acc += weighted(jnp.where(before, tiles[0], 0.0), v_prev_ref[0, 0, rows, :])
            for p, (_, v_ref, cols) in zip(tiles[1:], whole):
                acc += weighted(p, v_ref[0, 0, cols, :])
            out_ref[0, 0, rows, :] = (acc / l).astype(out_ref.dtype)
            if need_lse:
                lse = jnp.broadcast_to(m + jnp.log(l), (chunk, 128))
                lse_ref[0, 0, :, rows] = jnp.transpose(lse)[:1]  # [chunk, 1] -> [1, chunk]

    block = pl.program_id(2)
    pl.when(block == 0)(lambda: step(True))
    pl.when(block > 0)(lambda: step(False))


@functools.partial(jax.jit, static_argnames=("sm_scale", "window", "interpret", "need_lse"))
def _band_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    sm_scale: float,
    window: int,
    interpret: bool,
    need_lse: bool = True,
):
    """`_flash_forward`'s results (out [B, S, Hq, D] and lse [B, Hq, S]) for a
    causal call under ``window``, by the band step: grid (batch, kv head, q
    block, query head of the group), the group innermost so that its heads
    find the kv head's two blocks where the head before them left them."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qt, kt, vt = (jnp.swapaxes(_pad_seq(x, window), 1, 2) for x in (q, k, v))
    s_p = qt.shape[2]

    q_spec = pl.BlockSpec((1, 1, window, D), lambda b, h, i, g: (b, h * group + g, i, 0))
    prev_spec = pl.BlockSpec((1, 1, window, D), lambda b, h, i, g: (b, h, jnp.maximum(i - 1, 0), 0))
    own_spec = pl.BlockSpec((1, 1, window, D), lambda b, h, i, g: (b, h, i, 0))
    if need_lse:
        # One float32 a row, 4 MB a call at the Laguna cell's shape where the
        # lane-replicated form is 537 MB written and sliced again by XLA.
        lse_spec = pl.BlockSpec((1, 1, 1, window), lambda b, h, i, g: (b, h * group + g, 0, i))
        lse_shape = jax.ShapeDtypeStruct((B, Hq, 1, s_p), jnp.float32)
    else:  # as `_flash_forward`: one dummy tile that nothing writes
        lse_spec = pl.BlockSpec((1, 1, 8, 128), lambda b, h, i, g: (0, 0, 0, 0))
        lse_shape = jax.ShapeDtypeStruct((1, 1, 8, 128), jnp.float32)
    out, lse = pl.pallas_call(
        functools.partial(_band_kernel, sm_scale=sm_scale, window=window, need_lse=need_lse),
        grid=(B, Hkv, s_p // window, group),
        in_specs=[q_spec, prev_spec, own_spec, prev_spec, own_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, s_p, D), q.dtype), lse_shape],
        compiler_params=_compiler_params(_FWD_VMEM_LIMIT),
        interpret=interpret,
        # Begins `_window_flash_forward`, so the window's readers find it and
        # the full-causal ones do not; the suffix says which step shape ran.
        name="_window_flash_forward_band",
    )(qt, kt, kt, vt, vt)
    out = jnp.swapaxes(out, 1, 2)[:, :S]
    if not need_lse:
        return out, None
    return out, lse[:, :, 0, :S]


# --- backward: Pallas kernels (flash-attention-2) --------------------------
#
# They recompute the probabilities of one (q block, kv block) pair from the
# saved log-sum-exp, in VMEM: ``p = exp(q k^T * scale - lse)``,
# ``ds = p * (dout v^T - delta)`` with ``delta = rowsum(out * dout)``.  The
# dk/dv kernel holds a kv block and walks the q blocks of every query head
# of its group (dk, dv are written once per kv head); the dq kernel holds a
# q block and walks the kv blocks: 7 block matmuls a pair between the two.
# The fused form is the dk/dv kernel with dq as a third result that does not
# leave VMEM until the kv head is done: 5 matmuls, no accumulation through
# HBM, and no dq kernel.  ``sm_scale`` on ds is applied once, to the float32
# sums: by the kernels as they write, by XLA on the fused form's dq.


def _dkv_kernel(
    q_ref,  # [1, 1, Bq, D]
    k_ref,  # [1, 1, Bk, D]
    v_ref,  # [1, 1, Bk, D]
    do_ref,  # [1, 1, Bq, D]
    lse_ref,  # [1, 1, 1, Bq] f32: row statistics lie along the lanes
    delta_ref,  # [1, 1, 1, Bq] f32
    dk_ref,  # [1, 1, Bk, D]
    dv_ref,  # [1, 1, Bk, D]
    *rest,  # the fused form's dq_ref [1, group, S', D] f32, then the scratch:
    # dk_acc, dv_acc: VMEM [Bk, D] f32
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    nq: int,  # steps of the q axis a query head takes: every q block, or the window's
    window: int | None = None,
):
    """Works on the transposed tile s^T = k q^T [Bk, Bq]: the per-query
    statistics then broadcast along sublanes from a compact [1, Bq] row,
    and both accumulations (p^T dout, ds^T q) are plain [Bk, Bq] x [Bq, D]
    matmuls with nothing to transpose.

    The fused form (`_flash_backward_fused`) hands it a third result, the
    float32 dq of the kv head's whole group, resident in VMEM while the grid
    walks the head's kv blocks: the pair's ds^T feeds it too, by the one
    matmul that contracts the first axis of both operands (ds^T)^T k, so a
    pair is five block matmuls and its scores, exponential, mask, dp and ds
    are made once.  A q block's rows are summed over the kv blocks in the dq
    kernel's order, from zeros, and leave unscaled."""
    *dq_ref, dk_acc, dv_acc = rest
    ki = pl.program_id(2)
    t = pl.program_id(3)  # (query head of the group, q block), q block fastest
    qi = t % nq
    if window is None:
        q_start = qi * block_q
        k_start = ki * block_k
    else:
        k_start = ki * block_k
        q_start = (qi + _first_q_block(k_start, block_q=block_q)) * block_q

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    dq_ref = dq_ref[0] if dq_ref else None
    if dq_ref is not None:
        # the q block's rows of its query head
        dq_rows = (0, t // nq, pl.ds(pl.multiple_of(q_start, block_q), block_q), slice(None))

        @pl.when(ki == 0)  # every (query head, q block) passes the first kv block
        def _init_dq():
            dq_ref[dq_rows] = jnp.zeros((block_q, dq_ref.shape[-1]), dq_ref.dtype)

    def pair(masked: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        nt = (((1,), (1,)), ((), ()))  # contract the head dim of both
        nn = (((1,), (0,)), ((), ()))
        tn = (((0,), (0,)), ((), ()))  # contract the key axis of both
        st = jax.lax.dot_general(k, q, nt, preferred_element_type=jnp.float32)
        pt = jnp.exp(st * sm_scale - lse_ref[0, 0])  # [Bk, Bq]
        if masked:
            # Select after the exponential: a masked score may be anything
            # (exp may even overflow), the select never multiplies it.
            mask = _pair_mask(
                block_k, block_q, 1, q_start, k_start,
                causal=causal, q_len=q_len, kv_len=kv_len, window=window,
            )
            pt = jnp.where(mask, pt, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, nn, preferred_element_type=jnp.float32
        )
        dpt = jax.lax.dot_general(v, do, nt, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_acc[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, nn, preferred_element_type=jnp.float32
        )
        if dq_ref is not None:
            dq_ref[dq_rows] += jax.lax.dot_general(
                dst.astype(k.dtype), k, tn, preferred_element_type=jnp.float32
            )

    _run_pair(
        pair, q_start, k_start, causal=causal, block_q=block_q, block_k=block_k,
        q_len=q_len, kv_len=kv_len, window=window,
    )

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref,  # [1, 1, Bq, D]
    k_ref,  # [1, 1, Bk, D]
    v_ref,  # [1, 1, Bk, D]
    do_ref,  # [1, 1, Bq, D]
    lse_ref,  # [1, 1, Bq, 128] f32, lane-replicated as the forward writes it
    delta_ref,  # [1, 1, Bq, 128] f32
    dq_ref,  # [1, 1, Bq, D]
    dq_acc,  # VMEM [Bq, D] f32
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    window: int | None = None,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * block_q
    if window is None:
        k_start = ki * block_k
    else:
        k_start = (ki + _first_kv_block(q_start, window=window, block_k=block_k)) * block_k

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def pair(masked: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        nt = (((1,), (1,)), ((), ()))
        nn = (((1,), (0,)), ((), ()))
        s = jax.lax.dot_general(q, k, nt, preferred_element_type=jnp.float32)
        p = jnp.exp(s * sm_scale - lse_ref[0, 0][:, :1])  # [Bq, Bk]
        if masked:
            mask = _pair_mask(
                block_q, block_k, 0, q_start, k_start,
                causal=causal, q_len=q_len, kv_len=kv_len, window=window,
            )
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, nt, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, nn, preferred_element_type=jnp.float32
        )

    _run_pair(
        pair, q_start, k_start, causal=causal, block_q=block_q, block_k=block_k,
        q_len=q_len, kv_len=kv_len, window=window,
    )

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _heads_major(x: jax.Array, block: int) -> jax.Array:
    """[B, S, H, D] -> [B, H, S', D], S padded with zeros to whole blocks."""
    return jnp.swapaxes(_pad_seq(x, block), 1, 2)


def _pad_rows(x: jax.Array, block: int) -> jax.Array:
    """A per-row statistic [B, Hq, Sq] padded with zeros to whole q blocks:
    a padded row then reads p = exp(0 - 0), masked to 0, never
    exp(NEG_INF - NEG_INF)."""
    return jnp.pad(x, ((0, 0), (0, 0), (0, (-x.shape[2]) % block)))


def _backward_dkv(
    q, k, v, dout, lse, delta, *, causal, sm_scale, blocks, interpret, window=None, fused=False
):
    """dk, dv [B, Sk, Hkv, D]: grid (B, Hkv, kv blocks, group * q blocks);
    under a window, group * the q blocks the widest kv block's band holds.
    ``fused`` (no window): dq [B, Sq, Hq, D] too, from the same pairs: a third
    result [B, Hq, S', D] float32 in blocks of a kv head's group, whose index
    does not move while the grid walks that head's kv and q blocks, so that it
    stays in VMEM and is written once."""
    bq, bk = blocks
    (B, Sq, Hq, D), (_, Sk, Hkv, _) = q.shape, k.shape
    group = Hq // Hkv
    qt, dot = _heads_major(q, bq), _heads_major(dout, bq)
    kt, vt = _heads_major(k, bk), _heads_major(v, bk)
    nq, nk = qt.shape[2] // bq, kt.shape[2] // bk
    q_steps = nq if window is None else _q_steps(nq, nk, bq, bk, window)

    def q_index(b, h, j, t):
        i = t % q_steps
        if window is not None:
            # The q axis counts from the first block that sees this kv block;
            # a step past the last one that does names that last one.
            i = i + _first_q_block(j * bk, block_q=bq)
            i = jnp.minimum(i, jnp.minimum((j * bk + bk + window - 2) // bq, nq - 1))
        elif causal:
            # A skipped step names the block of the next step that runs, so
            # that nothing is fetched for it.
            i = jnp.minimum(jnp.maximum(i, (j * bk) // bq), nq - 1)
        return b, h * group + t // q_steps, i

    def row_index(*ids):
        b, h, i = q_index(*ids)
        return b, h, 0, i

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda *ids: (*q_index(*ids), 0))
    row_spec = pl.BlockSpec((1, 1, 1, bq), row_index)
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, j, t: (b, h, j, 0))
    out_specs = [kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct(kt.shape, k.dtype), jax.ShapeDtypeStruct(vt.shape, v.dtype)]
    if fused:
        out_specs.append(pl.BlockSpec((1, group, qt.shape[2], D), lambda b, h, j, t: (b, h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(qt.shape, jnp.float32))
        name = "_flash_backward_fused"
        params = _compiler_params(
            _BWD_VMEM_LIMIT + _fused_dq_bytes(group, qt.shape[2], D), carried_axes=2
        )
    else:
        name = "_flash_backward_dkv" if window is None else "_window_flash_backward_dkv"
        params = _compiler_params(_BWD_VMEM_LIMIT)
    dk, dv, *dq = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=bq, block_k=bk, q_len=Sq, kv_len=Sk, nq=q_steps, window=window,
        ),
        grid=(B, Hkv, nk, group * q_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
        # Not `_flash_forward...`: the benchmark's attention_roofline_share
        # finds the forward kernel by that prefix, these by `_flash_backward`.
        name=name,
    )(
        qt, kt, vt, dot,
        # the statistics as one row along the lanes: [B, Hq, 1, S']
        _pad_rows(lse, bq)[:, :, None, :], _pad_rows(delta, bq)[:, :, None, :],
    )
    dk, dv = jnp.swapaxes(dk, 1, 2)[:, :Sk], jnp.swapaxes(dv, 1, 2)[:, :Sk]
    if not fused:
        return dk, dv
    # The scale, the cast and the way back to [B, Sq, Hq, D] are one XLA pass
    # over the float32 sums: what `_dq_kernel` does as it writes.
    return jnp.swapaxes(dq[0] * sm_scale, 1, 2)[:, :Sq].astype(q.dtype), dk, dv


def _backward_dq(q, k, v, dout, lse, delta, *, causal, sm_scale, blocks, interpret, window=None):
    """dq [B, Sq, Hq, D]: grid (B, Hq, q blocks, kv blocks); under a window,
    the kv blocks the widest q block's band holds."""
    bq, bk = blocks
    (B, Sq, Hq, D), (_, Sk, Hkv, _) = q.shape, k.shape
    group = Hq // Hkv
    qt, dot = _heads_major(q, bq), _heads_major(dout, bq)
    kt, vt = _heads_major(k, bk), _heads_major(v, bk)
    nq, nk = qt.shape[2] // bq, kt.shape[2] // bk

    def columns(x):  # lane-replicated [B, Hq, S', 128], the forward's layout
        x = _pad_rows(x, bq)
        return jnp.broadcast_to(x[..., None], (*x.shape, 128))

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    col_spec = pl.BlockSpec((1, 1, bq, 128), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, D),
        _kv_index_map(causal=causal, group=group, block_q=bq, block_k=bk, nk=nk, window=window),
    )
    kv_steps = nk if window is None else _kv_steps(nq, nk, bq, bk, window)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=bq, block_k=bk, q_len=Sq, kv_len=Sk, window=window,
        ),
        grid=(B, Hq, nq, kv_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, col_spec, col_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(_BWD_VMEM_LIMIT),
        interpret=interpret,
        name="_flash_backward_dq" if window is None else "_window_flash_backward_dq",
    )(qt, kt, vt, dot, columns(lse), columns(delta))
    return jnp.swapaxes(dq, 1, 2)[:, :Sq]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "dkv_blocks", "dq_blocks", "interpret", "window"),
)
def _flash_backward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,  # [B, Hq, Sq] f32
    dout: jax.Array,
    causal: bool,
    sm_scale: float,
    dkv_blocks: tuple[int, int],  # (q block, kv block) of the dk/dv kernel
    dq_blocks: tuple[int, int],
    interpret: bool,
    window: int | None = None,
):
    # delta_i = sum_d out_i * dout_i, the softmax backward's row term.
    delta = jnp.einsum("bqhd,bqhd->bhq", out, dout, preferred_element_type=jnp.float32)
    kw = dict(causal=causal, sm_scale=sm_scale, interpret=interpret, window=window)
    dk, dv = _backward_dkv(q, k, v, dout, lse, delta, blocks=dkv_blocks, **kw)
    dq = _backward_dq(q, k, v, dout, lse, delta, blocks=dq_blocks, **kw)
    return dq, dk, dv


def _fused_dq_bytes(group: int, rows: int, head_dim: int) -> int:
    """VMEM the fused backward's resident dq takes: float32 [group, rows, D] in
    lane tiles, twice (Pallas keeps two buffers of an output block)."""
    return 2 * group * rows * _round_up(head_dim, 128) * 4


def _takes_fused_backward(window: int | None, group: int, seq_q: int, head_dim: int) -> bool:
    """Whether a backward pass runs the fused kernel, from what the call can
    see: no window, and the dq of a kv head's group, resident at the fused
    tiles, within `_BWD_FUSED_DQ_BUDGET`.  Every other call runs the pair."""
    rows = _round_up(seq_q, _clamp_block(BWD_FUSED_BLOCKS[0], seq_q))
    return window is None and _fused_dq_bytes(group, rows, head_dim) <= _BWD_FUSED_DQ_BUDGET


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "blocks", "interpret")
)
def _flash_backward_fused(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,  # [B, Hq, Sq] f32
    dout: jax.Array,
    causal: bool,
    sm_scale: float,
    blocks: tuple[int, int],  # (q block, kv block)
    interpret: bool,
):
    """`_flash_backward`'s three gradients from one kernel (no window)."""
    delta = jnp.einsum("bqhd,bqhd->bhq", out, dout, preferred_element_type=jnp.float32)
    return _backward_dkv(
        q, k, v, dout, lse, delta, causal=causal, sm_scale=sm_scale,
        blocks=blocks, interpret=interpret, fused=True,
    )


# --- custom-vjp core (arrays only; mesh handled by the public wrapper) ---


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, sm_scale, block_q, block_k, interpret, window):
    # Primal-only path (no grad being taken): skip the LSE output entirely.
    out, _ = _forward(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, need_lse=False)
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


def _forward(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, need_lse):
    """The forward call the shapes choose: the band step where the window and
    the sequence allow it and the caller named no tiles, else the tiled kernel
    at the caller's tiles or the swept ones of the kind of call, clamped."""
    if _takes_band_step(window, block_q, block_k, q.shape[1], k.shape[1]):
        return _band_forward(q, k, v, sm_scale, window, interpret, need_lse=need_lse)
    default_q, default_k = (
        (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K) if window is None else WINDOW_FWD_BLOCKS
    )
    bq = _clamp_block(default_q if block_q is None else block_q, q.shape[1])
    bk = _clamp_block(default_k if block_k is None else block_k, k.shape[1])
    return _flash_forward(
        q, k, v, causal, sm_scale, bq, bk, interpret, need_lse=need_lse, window=window
    )


# What `jax.ad_checkpoint.checkpoint_name` calls a differentiated call's `out`
# and its compact `lse` [B, Hq, Sq]: a full-causal call's pair, and a windowed
# call's.  Outside a `jax.checkpoint` whose policy saves a name it is the
# identity and lowers to nothing; inside one that does, the kernel that made
# the pair is not run again for the backward pass.  Which names are worth
# their bytes is the caller's judgement (models/llama.remat_keeps).
FLASH_RESIDUALS = ("flash_out", "flash_lse")
WINDOW_FLASH_RESIDUALS = ("window_flash_out", "window_flash_lse")


def _core_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window):
    out, lse = _forward(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, need_lse=True)
    # Named before they part into the result and the residuals: a name on the
    # result alone would leave the residual another variable, recomputed.
    out_name, lse_name = FLASH_RESIDUALS if window is None else WINDOW_FLASH_RESIDUALS
    out, lse = checkpoint_name(out, out_name), checkpoint_name(lse, lse_name)
    return out, (q, k, v, out, lse)


def _core_bwd(causal, sm_scale, block_q, block_k, interpret, window, res, g):
    del block_q, block_k  # the forward's tiles; the backward has its own
    q, k, v, out, lse = res
    clamp = lambda blocks: (
        _clamp_block(blocks[0], q.shape[1]), _clamp_block(blocks[1], k.shape[1])
    )
    dkv_blocks, dq_blocks = (
        (BWD_DKV_BLOCKS, BWD_DQ_BLOCKS) if window is None
        else (WINDOW_BWD_DKV_BLOCKS, WINDOW_BWD_DQ_BLOCKS)
    )
    # The scope is what tells the backward's kernels and the few XLA
    # operations around them from the rest of the step's in a profile.
    with jax.named_scope("attn_bwd"):
        if _takes_fused_backward(window, q.shape[2] // k.shape[2], q.shape[1], q.shape[3]):
            return _flash_backward_fused(
                q, k, v, out, lse, g, causal, sm_scale, clamp(BWD_FUSED_BLOCKS), interpret
            )
        return _flash_backward(
            q, k, v, out, lse, g, causal, sm_scale,
            clamp(dkv_blocks), clamp(dq_blocks), interpret, window=window,
        )


_flash_core.defvjp(_core_fwd, _core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    mesh: Mesh | None = None,
    window: int | None = None,
) -> jax.Array:
    """Flash attention, [B, S, H, D] in/out, GQA-aware (Hkv must divide Hq).

    ``window``: a sliding window of that many keys, the query's own among
    them (``t - window < j <= t``); causal only.  ``block_q`` / ``block_k``
    left out are the swept forward tiles of the kind of call
    (``DEFAULT_BLOCK_*``, or ``WINDOW_FWD_BLOCKS`` under a window); with both
    left out a window the band step takes (`_takes_band_step`) runs that.

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
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError(f"a window ({window}) is a positive number of keys, and causal")

    def core(q, k, v):
        # nondiff argnums must be positional for custom_vjp
        return _flash_core(q, k, v, causal, sm_scale, block_q, block_k, interpret, window)
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
