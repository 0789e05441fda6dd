"""Ring attention: causal attention over a sequence-sharded mesh axis.

Long-context scaling has no reference analog (SURVEY §5: "long-context /
sequence parallelism: absent"); it is a first-class requirement of the TPU
build.  Design (Liu et al., Ring Attention; blockwise online softmax):

- The sequence axis is sharded over the ``sp`` mesh axis: each device holds
  a [B, S/sp, H, D] slice of Q, K, V.
- sp steps of computation: each device computes blockwise attention of its
  Q block against the K/V block it currently holds, accumulating the online
  softmax state (running max, running denominator, weighted values), then
  rotates K/V to the next ring neighbor with ``jax.lax.ppermute`` over ICI.
- Causality across blocks is decided by block index: a K/V block strictly
  in the future is skipped entirely; the diagonal block applies the
  per-element causal mask; past blocks are unmasked.  Skipped blocks still
  participate in the ppermute (the ring must keep moving), so wall-clock is
  sp ring steps regardless, but no score matrix larger than
  [S/sp, S/sp] ever materializes — HBM stays O(S/sp * S/sp) per device
  instead of O(S^2).

Exposed as ``ring_attention(q, k, v, mesh, axis="sp")`` with the same
[B, S, H, D] contract as ops.attention.dot_product_attention; a test
asserts numerical equality against the dense path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning_cfn_tpu.ops.attention import _repeat_kv


def _block_attend(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, H, D]
    v: jax.Array,
    m: jax.Array,  # [B, H, Sq] running max
    l: jax.Array,  # [B, H, Sq] running denominator
    acc: jax.Array,  # [B, Sq, H, D] running numerator
    mask: jax.Array | None,  # [Sq, Sk] bool or None
):
    """One online-softmax accumulation step."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    block_max = jnp.max(scores, axis=-1)  # [B, H, Sq]
    new_m = jnp.maximum(m, block_max)
    # Rescale previous accumulation; exp(-inf - finite) == 0 handles the
    # first step (m starts at -inf).
    correction = jnp.exp(m - new_m)
    probs = jnp.exp(scores - new_m[..., None])  # [B, H, Sq, Sk]
    # Fully-masked blocks produce probs of exp(-inf)=0; no NaNs.
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    new_l = l * correction + jnp.sum(probs, axis=-1)
    weighted = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    new_acc = acc * correction.transpose(0, 2, 1)[..., None].astype(acc.dtype) + weighted
    return new_m, new_l, new_acc


def ring_attention(
    q: jax.Array,  # [B, S, H, D] — S sharded over `axis`
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    window: int | None = None,
) -> jax.Array:
    """Causal ring attention over the ``axis`` mesh dimension.

    Batch is assumed sharded over (dp, fsdp) and heads over tp as usual;
    this function only manages the sequence axis.  A sliding ``window`` is
    refused by name: the ring passes every block to every device and masks
    by the causal triangle alone, so a window would be silently ignored.
    """
    if window is not None:
        raise NotImplementedError(
            f"ring_attention has no sliding window (window={window}): its blocks are masked "
            "by the causal triangle alone; use flash_attention or dot_product_attention "
            "with the sequence unsharded"
        )
    num_heads = q.shape[2]
    num_kv_heads = k.shape[2]
    sp = mesh.shape[axis]
    tp = mesh.shape.get("tp", 1)
    # GQA: keep K/V compact through the ring whenever the tp sharding of the
    # kv-head axis preserves the q->kv group mapping (tp divides kv heads:
    # shard t's q heads [t*H/tp,(t+1)*H/tp) map exactly onto its kv heads).
    # Compact K/V means the ppermute moves n_kv/n_heads as many bytes —
    # 4x less ring traffic for the Llama-3 8B 32q/8kv shape.  Only when tp
    # does not divide the kv heads do we pre-expand.
    compact_kv = num_kv_heads % tp == 0
    if not compact_kv:
        k = _repeat_kv(k, num_heads)
        v = _repeat_kv(v, num_heads)

    def local(q_blk, k_blk, v_blk):
        # Shapes inside shard_map: [B', S/sp, H', D]
        B, Sq, H, D = q_blk.shape
        my_idx = jax.lax.axis_index(axis)

        m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, Sq), jnp.float32)
        acc0 = jnp.zeros((B, Sq, H, D), v_blk.dtype)

        seq_pos = jnp.arange(Sq)

        def ring_step(step, carry):
            m, l, acc, k_cur, v_cur = carry
            # Which device's block do we currently hold?  K/V rotate
            # "backwards" so after t steps we hold block (my_idx - t) mod sp.
            src_idx = (my_idx - step) % sp
            if causal:
                # Future block: fully masked.  Diagonal: per-element mask.
                def masked_update():
                    # Diagonal block: both blocks share local offsets, so
                    # the local lower-triangular mask IS the global one.
                    mask = seq_pos[:, None] >= seq_pos[None, :]
                    return _block_attend(
                        q_blk, _repeat_kv(k_cur, H), _repeat_kv(v_cur, H), m, l, acc, mask
                    )

                def full_update():
                    return _block_attend(
                        q_blk, _repeat_kv(k_cur, H), _repeat_kv(v_cur, H), m, l, acc, None
                    )

                def skip():
                    return m, l, acc

                m, l, acc = jax.lax.cond(
                    src_idx == my_idx,
                    masked_update,
                    lambda: jax.lax.cond(src_idx < my_idx, full_update, skip),
                )
            else:
                m, l, acc = _block_attend(
                    q_blk, _repeat_kv(k_cur, H), _repeat_kv(v_cur, H), m, l, acc, None
                )
            # Rotate K/V around the ring (neighbor exchange over ICI).
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            k_next = jax.lax.ppermute(k_cur, axis, perm)
            v_next = jax.lax.ppermute(v_cur, axis, perm)
            return m, l, acc, k_next, v_next

        m, l, acc, _, _ = jax.lax.fori_loop(
            0, sp, ring_step, (m0, l0, acc0, k_blk, v_blk)
        )
        # Normalize; l==0 can only happen for fully-masked rows, which do
        # not occur in causal attention (every position sees itself).
        out = acc / l.transpose(0, 2, 1)[..., None].astype(acc.dtype)
        return out

    spec = P(("dp", "fsdp"), axis, "tp", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)  # compact K/V: the head axis still tp-shards (kv heads/tp per device)
