"""Bucketed, latency-hiding gradient synchronization — the comms-overlap engine.

The monolithic dp/fsdp step lets GSPMD place gradient collectives
wherever its scheduler likes, which in practice is one fused bundle at
the end of the backward pass, fully serialized against compute.  This
module makes gradient sync an *explicitly scheduled* program, the
discipline behind the MLPerf-scale wins of arxiv 1909.09756 and
2010.10458:

- :func:`plan_buckets` partitions the parameter tree into size-targeted
  buckets, deterministically: leaves are visited in ``keystr`` path
  order (never hash/set order — the DLC6xx determinism pass lints this
  file), sharded leaves become their own reduce-scatter buckets, and
  replicated leaves greedily fill fused all-reduce buckets up to the
  byte target.
- :func:`build_overlap_grad_fn` lowers loss/grad/sync inside ONE
  ``shard_map`` so every bucket's collective is an explicit instruction
  the scheduler can hoist.  With gradient accumulation, microbatch k's
  bucket sync is issued inside the ``lax.scan`` body that computes
  microbatch k+1's gradients — bucket k's collective overlaps the next
  microbatch's backward pass.
- Bit-parity is part of the contract, not a hope: for replicated (dp)
  parameters the bucketed program performs the same float additions in
  the same order as the monolithic GSPMD step (per-microbatch psum of
  bitwise-identical gradients, accumulated in the same sequence;
  power-of-two loss scalings are exact), so same-seed losses and final
  states are ``assert_array_equal``-equal on the 8-device virtual mesh
  (tests/test_overlap.py pins this).  fsdp-sharded leaves use
  gather-compute-scatter, which matches the monolithic path numerically
  but not bitwise — GSPMD picks a column-parallel backward there
  (docs/PERFORMANCE.md, "Hiding the collectives").
- ``compress=True`` rides the PR 13 int8 plumbing (ops/quant.py): each
  fused bucket is symmetric-int8 quantized with a per-device
  error-feedback residual carried in the optimizer state
  (:class:`ErrorFeedbackState`), cutting the dp sync's wire bytes ~4x
  at the cost of quantization noise the residual re-injects next step.

The proof instrument lives in analysis/comms_audit.py: the audit
machine-reads the optimized HLO *schedule* into a per-program
``overlap_score`` committed to scripts/comms_budget.json and ratcheted
(DLC512).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning_cfn_tpu.ops.quant import dequantize_flat, quantize_flat

# Fused-bucket size target.  Large enough that per-collective latency
# amortizes, small enough that the first bucket closes (and its sync
# issues) well before the backward pass finishes — the trade the
# reference tuned through HOROVOD_FUSION_THRESHOLD (run.sh:70-79), made
# explicit and deterministic here.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

# Gradient sync runs over the batch axes.  Every other mesh axis must be
# trivial (size 1) for the manual program to be correct — no tp/pp
# replica groups are threaded through the bucket collectives.
SYNC_AXES = ("dp", "fsdp")


@dataclass(frozen=True)
class Bucket:
    """One sync unit of the plan.

    ``fused`` buckets hold replicated leaves, concatenated flat and
    synced with a single ``psum`` (or the int8 two-phase exchange);
    ``sharded`` buckets hold exactly one fsdp-sharded leaf, synced with
    ``psum_scatter`` along its sharded dimension.  ``indices`` are
    positions in the canonical ``tree_flatten`` leaf order of the
    parameter tree; bucket ORDER is path-sorted.
    """

    kind: str  # "fused" | "sharded"
    indices: tuple[int, ...]
    paths: tuple[str, ...]
    nbytes: int
    numel: int
    shard_dim: int | None = None
    shard_axes: Any = None  # mesh axis (str) or axes (tuple) of shard_dim

    def to_dict(self) -> dict:
        return {
            "kind": self.kind,
            "paths": list(self.paths),
            "nbytes": self.nbytes,
            "numel": self.numel,
            "shard_dim": self.shard_dim,
        }


@dataclass(frozen=True)
class BucketPlan:
    """Deterministic bucketization of one parameter tree."""

    buckets: tuple[Bucket, ...]
    total_bytes: int
    target_bytes: int

    @property
    def fused(self) -> tuple[Bucket, ...]:
        return tuple(b for b in self.buckets if b.kind == "fused")

    @property
    def sharded(self) -> tuple[Bucket, ...]:
        return tuple(b for b in self.buckets if b.kind == "sharded")

    def to_dict(self) -> dict:
        return {
            "target_bytes": self.target_bytes,
            "total_bytes": self.total_bytes,
            "buckets": [b.to_dict() for b in self.buckets],
        }


def _spec_sharded_dims(spec: P, ndim: int) -> list[tuple[int, Any]]:
    """``(dim, mesh_axes)`` for every sharded dimension of a leaf."""
    out: list[tuple[int, Any]] = []
    for d, axes in enumerate(tuple(spec)[:ndim]):
        if axes is not None:
            out.append((d, axes))
    return out


def plan_buckets(
    abstract_params: Any,
    param_specs: Any,
    target_bytes: int = DEFAULT_BUCKET_BYTES,
) -> BucketPlan:
    """Partition a parameter tree into size-targeted sync buckets.

    Deterministic by construction: leaves are visited in sorted
    ``keystr`` path order (a pure function of the tree's structure —
    no ``hash()``/set-order folds, which the DLC6xx pass would flag),
    so the same tree always yields the same plan and the compiled
    schedule — and therefore the committed ``overlap_score`` — is
    reproducible.  ``abstract_params`` may be shapes, tracers, or real
    arrays; only ``.shape``/``.dtype`` are read.
    """
    if target_bytes <= 0:
        raise ValueError(f"target_bytes must be positive, got {target_bytes}")
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    spec_leaves = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda s: isinstance(s, P)
    )
    if len(spec_leaves) != len(leaves_with_path):
        raise ValueError(
            f"param_specs has {len(spec_leaves)} leaves for "
            f"{len(leaves_with_path)} parameters"
        )
    order = sorted(
        range(len(leaves_with_path)),
        key=lambda i: jax.tree_util.keystr(leaves_with_path[i][0]),
    )
    buckets: list[Bucket] = []
    cur_idx: list[int] = []
    cur_paths: list[str] = []
    cur_bytes = 0
    cur_numel = 0

    def close_fused() -> None:
        nonlocal cur_idx, cur_paths, cur_bytes, cur_numel
        if cur_idx:
            buckets.append(
                Bucket(
                    kind="fused",
                    indices=tuple(cur_idx),
                    paths=tuple(cur_paths),
                    nbytes=cur_bytes,
                    numel=cur_numel,
                )
            )
            cur_idx, cur_paths, cur_bytes, cur_numel = [], [], 0, 0

    for i in order:
        path, leaf = leaves_with_path[i]
        spec = spec_leaves[i]
        ndim = len(getattr(leaf, "shape", ()))
        sharded = _spec_sharded_dims(spec, ndim)
        pathstr = jax.tree_util.keystr(path)
        if len(sharded) > 1:
            raise ValueError(
                f"comms_overlap supports at most one sharded dimension per "
                f"parameter; {pathstr} has spec {spec}"
            )
        numel = int(math.prod(leaf.shape)) if leaf.shape else 1
        nbytes = numel * jnp.dtype(leaf.dtype).itemsize
        if sharded:
            # A sharded leaf is its own reduce-scatter bucket; close the
            # in-flight fused bucket first so bucket order stays the
            # path order (the order syncs are issued in).
            close_fused()
            dim, axes = sharded[0]
            buckets.append(
                Bucket(
                    kind="sharded",
                    indices=(i,),
                    paths=(pathstr,),
                    nbytes=nbytes,
                    numel=numel,
                    shard_dim=dim,
                    shard_axes=axes,
                )
            )
            continue
        cur_idx.append(i)
        cur_paths.append(pathstr)
        cur_bytes += nbytes
        cur_numel += numel
        if cur_bytes >= target_bytes:
            close_fused()
    close_fused()
    return BucketPlan(
        buckets=tuple(buckets),
        total_bytes=sum(b.nbytes for b in buckets),
        target_bytes=target_bytes,
    )


# --- int8 error feedback -----------------------------------------------------


class ErrorFeedbackState(NamedTuple):
    """Optimizer-state wrapper for compressed sync.

    ``residual`` holds one ``[nd, padded_len]`` f32 array per FUSED
    bucket (sharded ``P(sync_axes)`` on dim 0, so each device carries
    only its own ``[1, padded_len]`` error row) — the quantization error
    ``v - dequant(quant(v))`` re-injected into the next step's bucket
    before quantizing, which is what keeps int8 sync convergent.
    ``inner`` is the wrapped (real) optax state.  The wrapper exists
    only when ``TrainerConfig.overlap_compress`` is on; the default
    opt-state structure is untouched otherwise.
    """

    residual: tuple
    inner: Any


def _padded_len(numel: int, nd: int) -> int:
    return numel + (-numel) % nd


def init_error_feedback(
    plan: BucketPlan, nd: int, inner: Any, dtype: Any = jnp.float32
) -> ErrorFeedbackState:
    """Zero residuals for every fused bucket, wrapped around ``inner``."""
    residual = tuple(
        jnp.zeros((nd, _padded_len(b.numel, nd)), dtype) for b in plan.fused
    )
    return ErrorFeedbackState(residual=residual, inner=inner)


def error_feedback_shardings(
    plan: BucketPlan, mesh: Mesh, sync_axes: tuple[str, ...] = SYNC_AXES
) -> tuple[NamedSharding, ...]:
    """Residuals shard their leading (per-device) axis over the sync axes."""
    return tuple(
        NamedSharding(mesh, P(tuple(sync_axes))) for _ in plan.fused
    )


# --- per-bucket sync primitives (shard_map-local views) ----------------------


def _sync_fused_int8(
    flat: jax.Array, residual: jax.Array, sync_axes: tuple[str, ...], nd: int
) -> tuple[jax.Array, jax.Array]:
    """Two-phase int8 all-reduce of one fused bucket with error feedback.

    Phase 1: add this device's residual, quantize the whole padded
    bucket with one symmetric scale, then ``all_to_all`` the int8
    chunks so device j holds every peer's chunk j (plus an all-gather
    of the nd scalar scales).  Phase 2: dequantize-sum the segment in
    f32, requantize it, and ``all_gather`` the int8 segments back to
    the full bucket.  Wire traffic is ~1 byte/element/phase against the
    f32 psum's 4 — the ~4x cut docs/PERFORMANCE.md quotes.

    The residual captures exactly the phase-1 quantization error
    (``v - dequant(q)``); the phase-2 requantization error is NOT fed
    back — it is bounded by the segment's own range and is what the
    rtol-gated convergence test covers.
    """
    numel = flat.shape[0]
    length = residual.shape[1]
    pad = length - numel
    v = flat.astype(jnp.float32)
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)])
    v = v + residual[0]
    q, scale = quantize_flat(v)
    new_residual = (v - dequantize_flat(q, scale))[None, :]
    chunk = length // nd
    peer_chunks = jax.lax.all_to_all(
        q.reshape(nd, chunk), sync_axes, split_axis=0, concat_axis=0, tiled=True
    )
    peer_scales = jax.lax.all_gather(scale, sync_axes, axis=0)
    segment = jnp.sum(
        peer_chunks.astype(jnp.float32) * peer_scales[:, None], axis=0
    )
    q2, scale2 = quantize_flat(segment)
    gathered = jax.lax.all_gather(q2, sync_axes, axis=0, tiled=True)
    scales2 = jax.lax.all_gather(scale2, sync_axes, axis=0)
    out = gathered.astype(jnp.float32) * jnp.repeat(scales2, chunk)
    return out[:numel], new_residual


def _sync_sharded(
    grad_full: jax.Array,
    sync_axes: tuple[str, ...],
    shard_axes: Any,
    shard_dim: int,
) -> jax.Array:
    """Reduce-scatter a full-size local gradient down to this device's
    shard along the leaf's sharded dimension, summing over every sync
    axis (``psum`` over the axes the shard does not consume)."""
    shard_tuple = (
        (shard_axes,) if isinstance(shard_axes, str) else tuple(shard_axes)
    )
    out = jax.lax.psum_scatter(
        grad_full, shard_tuple, scatter_dimension=shard_dim, tiled=True
    )
    other = tuple(a for a in sync_axes if a not in shard_tuple)
    if other:
        out = jax.lax.psum(out, other)
    return out


# --- the grad-sync step ------------------------------------------------------


def _resolve_sync_axes(batch_spec: P, mesh: Mesh) -> tuple[str, ...]:
    entries = tuple(batch_spec)
    dim0 = entries[0] if entries else None
    if dim0 is None:
        raise ValueError(
            "comms_overlap needs the batch sharded over the data axes on "
            f"dim 0; got batch spec {batch_spec}"
        )
    for extra in entries[1:]:
        if extra is not None:
            raise ValueError(
                "comms_overlap supports batch sharding on dim 0 only; got "
                f"batch spec {batch_spec} (sequence-sharded inputs must use "
                "the monolithic path)"
            )
    sync_axes = (dim0,) if isinstance(dim0, str) else tuple(dim0)
    if not set(sync_axes) <= set(SYNC_AXES):
        raise ValueError(
            f"comms_overlap syncs over {SYNC_AXES}; batch spec {batch_spec} "
            "shards dim 0 over other mesh axes"
        )
    for name, size in mesh.shape.items():
        if name not in sync_axes and size != 1:
            raise ValueError(
                f"comms_overlap requires every non-data mesh axis to be "
                f"trivial; axis {name!r} has size {size}"
            )
    return sync_axes


def build_overlap_grad_fn(
    loss_fn: Callable[..., tuple[jax.Array, tuple[dict, Any]]],
    mesh: Mesh,
    param_specs: Any,
    batch_spec: P,
    plan: BucketPlan,
    *,
    accum: int = 1,
    compress: bool = False,
) -> Callable:
    """Build the bucketed grad-sync step.

    Returns ``fn(params, x, y, residuals) -> (loss, aux, grads,
    new_residuals)`` where ``loss_fn(params, model_state, x, y) ->
    (loss, (aux, new_model_state))`` is the trainer's loss (called with
    an empty ``model_state`` — the trainer gates stateless models),
    ``residuals`` is ``ErrorFeedbackState.residual`` when ``compress``
    (the empty tuple otherwise), ``grads`` carries the leaf's own
    sharding (shard for sharded leaves, replicated otherwise), and
    ``loss``/``aux`` are the global (batch-mean) values, bitwise equal
    to the monolithic dp path's.

    With ``accum > 1`` the sync schedule pipelines: the prologue
    computes microbatch 0's gradients unsynced; each scan body computes
    microbatch m's gradients while issuing microbatch m-1's bucket
    collectives and accumulating their results (the same addition order
    as the monolithic scan, which GSPMD also syncs per microbatch — so
    parity survives pipelining); the epilogue drains the last pending
    sync.  Microbatches are the same strided slices the monolithic path
    takes, applied locally — identical because the batch axis is
    sharded and the stride preserves shard membership.
    """
    if accum < 1:
        raise ValueError(f"accum must be >= 1, got {accum}")
    sync_axes = _resolve_sync_axes(batch_spec, mesh)
    nd = 1
    for a in sync_axes:
        nd *= mesh.shape[a]
    if nd <= 1:
        raise ValueError(
            "comms_overlap needs more than one device on the data axes "
            f"(got {nd}); use the monolithic path on a single device"
        )
    for b in plan.sharded:
        shard_tuple = (
            (b.shard_axes,)
            if isinstance(b.shard_axes, str)
            else tuple(b.shard_axes)
        )
        if not set(shard_tuple) <= set(sync_axes):
            raise ValueError(
                f"sharded bucket {b.paths[0]} uses mesh axes {shard_tuple} "
                f"outside the sync axes {sync_axes}"
            )
    ef_specs = tuple(P(tuple(sync_axes)) for _ in plan.fused) if compress else ()

    def sync_buckets(
        flat_grads: list, residuals: tuple
    ) -> tuple[list, tuple]:
        out = list(flat_grads)
        new_residuals = []
        fused_i = 0
        for b in plan.buckets:
            if b.kind == "sharded":
                i = b.indices[0]
                out[i] = _sync_sharded(
                    flat_grads[i], sync_axes, b.shard_axes, b.shard_dim
                )
                continue
            parts = [flat_grads[i].ravel() for i in b.indices]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if compress:
                flat, res = _sync_fused_int8(
                    flat, residuals[fused_i], sync_axes, nd
                )
                new_residuals.append(res)
                fused_i += 1
            else:
                flat = jax.lax.psum(flat, sync_axes)
            offset = 0
            for i in b.indices:
                size = flat_grads[i].size
                out[i] = flat[offset : offset + size].reshape(
                    flat_grads[i].shape
                )
                offset += size
        return out, tuple(new_residuals)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_specs, batch_spec, batch_spec, ef_specs),
        out_specs=(P(), P(), param_specs, ef_specs),
        check_vma=False,
    )
    def grad_sync_step(params, x, y, residuals):
        flat_params, treedef = jax.tree_util.tree_flatten(params)
        full = list(flat_params)
        for b in plan.sharded:
            i = b.indices[0]
            full[i] = jax.lax.all_gather(
                flat_params[i], b.shard_axes, axis=b.shard_dim, tiled=True
            )
        full_params = jax.tree_util.tree_unflatten(treedef, full)

        def scaled(p, x_m, y_m):
            # loss/nd then psum == the global batch mean, exactly: nd is
            # a power of two on our meshes, so the scaling is a float
            # exponent shift that commutes bitwise with the summation.
            loss, (aux, _state) = loss_fn(p, {}, x_m, y_m)
            return loss / nd, aux

        grad_fn = jax.value_and_grad(scaled, has_aux=True)

        def one_microbatch(x_m, y_m):
            (loss, aux), grads = grad_fn(full_params, x_m, y_m)
            loss = jax.lax.psum(loss, sync_axes)
            aux = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a / nd, sync_axes), aux
            )
            return loss, aux, jax.tree_util.tree_leaves(grads)

        if accum == 1:
            loss, aux, flat_grads = one_microbatch(x, y)
            synced, new_residuals = sync_buckets(flat_grads, residuals)
            grads = jax.tree_util.tree_unflatten(treedef, synced)
            return loss, aux, grads, new_residuals

        def to_micro(leaf):
            n = leaf.shape[0]
            if n % accum:
                raise ValueError(
                    f"per-device batch {n} not divisible by "
                    f"grad_accum_steps={accum}"
                )
            return jnp.swapaxes(
                leaf.reshape((n // accum, accum) + leaf.shape[1:]), 0, 1
            )

        xs = jax.tree_util.tree_map(to_micro, x)
        ys = jax.tree_util.tree_map(to_micro, y)
        x0 = jax.tree_util.tree_map(lambda s: s[0], xs)
        y0 = jax.tree_util.tree_map(lambda s: s[0], ys)
        # Prologue: microbatch 0's gradients stay PENDING (unsynced) —
        # their collectives issue inside the first scan body, where
        # microbatch 1's forward/backward gives the scheduler compute
        # to hide them behind.
        loss0, aux0, pending = one_microbatch(x0, y0)
        acc = [jnp.zeros_like(g) for g in pending]

        def body(carry, xy):
            pending, acc, residuals = carry
            x_m, y_m = xy
            loss_m, aux_m, grads_m = one_microbatch(x_m, y_m)
            synced, residuals = sync_buckets(pending, residuals)
            acc = [a + s for a, s in zip(acc, synced)]
            return (grads_m, acc, residuals), (loss_m, aux_m)

        rest = (
            jax.tree_util.tree_map(lambda s: s[1:], xs),
            jax.tree_util.tree_map(lambda s: s[1:], ys),
        )
        (pending, acc, residuals), (losses_r, auxes_r) = jax.lax.scan(
            body, (pending, acc, residuals), rest
        )
        # Epilogue: drain the last microbatch's sync.
        synced, new_residuals = sync_buckets(pending, residuals)
        acc = [a + s for a, s in zip(acc, synced)]
        grads = jax.tree_util.tree_unflatten(
            treedef, [a / accum for a in acc]
        )
        loss = jnp.mean(jnp.concatenate([loss0[None], losses_r]))
        aux = jax.tree_util.tree_map(
            lambda a0, ar: jnp.mean(
                jnp.concatenate([a0[None], ar], axis=0), axis=0
            ),
            aux0,
            auxes_r,
        )
        return loss, aux, grads, new_residuals

    return grad_sync_step
