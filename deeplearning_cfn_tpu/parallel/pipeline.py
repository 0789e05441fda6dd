"""Pipeline parallelism: GPipe-style microbatch pipelining over the ``pp``
mesh axis.

No reference analog exists (SURVEY §2.3: "Not present anywhere in the
reference: ... pipeline parallelism"); it is part of the TPU build's
first-class parallelism surface (the ``pp`` axis of parallel/mesh.py).
Design, TPU-first:

- **Stage sharding is data**: layer-stacked parameters ``[L, ...]`` are
  reshaped to ``[pp, L/pp, ...]`` and sharded over ``pp`` with a leading
  ``PartitionSpec("pp", ...)`` — each device group holds only its stage's
  weights at rest (composes with FSDP/TP sharding of the trailing axes).
- **Partial-manual shard_map**: the schedule runs under
  ``shard_map(..., axis_names={"pp"})`` so only the pipeline axis is manual;
  batch/tensor axes (dp, fsdp, tp, sp) stay in GSPMD auto mode and keep
  their compiler-placed collectives inside each stage.
- **Static schedule via lax.scan**: M microbatches flow through pp stages in
  ``M + pp - 1`` ticks.  Each tick every stage runs its block stack on the
  activation it holds, then the activation ring-shifts one stage forward
  with ``lax.ppermute`` over ICI.  No data-dependent control flow — XLA
  compiles one program, and the bubble fraction is the textbook
  ``(pp-1)/(M+pp-1)``.
- **Differentiable**: the backward pipeline is derived by autodiff through
  scan + ppermute (reverse-mode ppermute is the inverse permutation), so
  one ``jax.grad`` gives pipelined backprop with no hand-written schedule.

The first/last stages' extra work (embedding, logits) stays OUTSIDE the
pipelined region — those run as ordinary GSPMD ops before/after, keeping
stage_fn uniform across stages (uniform stages = no schedule skew).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


class PipelineError(ValueError):
    pass


def _boundary_f32(dtype) -> bool:
    """Whether a pp-axis collective of this dtype must route through f32
    (XLA CPU crashes promoting low-precision all-reduces; see
    pipeline_apply)."""
    return dtype in (jnp.bfloat16, jnp.float16) and jax.default_backend() == "cpu"


def stack_stages(layer_tree: Any, n_stages: int) -> Any:
    """Reshape layer-stacked params ``[L, ...]`` -> ``[pp, L/pp, ...]``.

    The leading stage axis is the one sharded over ``pp``; scan order is
    preserved (stage s holds layers ``[s*L/pp, (s+1)*L/pp)``).
    """

    def reshape(x):
        L = x.shape[0]
        if L % n_stages:
            raise PipelineError(
                f"layer count {L} not divisible by pp={n_stages}"
            )
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree_util.tree_map(reshape, layer_tree)


def unstack_stages(layer_tree: Any) -> Any:
    """Inverse of :func:`stack_stages`: ``[pp, L/pp, ...]`` -> ``[L, ...]``
    in scan order (single-device fallback and decoding use the flat layout)."""
    return jax.tree_util.tree_map(
        lambda p: p.reshape(p.shape[0] * p.shape[1], *p.shape[2:]), layer_tree
    )


def stage_specs(layer_specs: Any) -> Any:
    """Prepend the ``pp`` axis to each per-layer PartitionSpec."""
    return jax.tree_util.tree_map(
        lambda s: P("pp", *s),
        layer_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def microbatch(x: jax.Array, n_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...].  B must divide evenly."""
    B = x.shape[0]
    if B % n_microbatches:
        raise PipelineError(
            f"batch {B} not divisible by n_microbatches={n_microbatches}"
        )
    return x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], tuple[jax.Array, jax.Array]],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    n_microbatches: int,
    axis: str = "pp",
) -> tuple[jax.Array, jax.Array]:
    """Run ``stage_fn`` as a pp-stage pipeline over microbatches of ``x``.

    ``stage_fn(local_stage_params, act) -> (act, aux)`` applies ONE stage's
    layer stack to one microbatch activation ``act`` and returns the new
    activation plus a scalar aux loss (0 where unused).  ``stage_params``
    leaves lead with the stage axis ``[pp, L/pp, ...]`` (see
    :func:`stack_stages`).  ``x`` is the full-batch input activation
    ``[B, ...]`` (already embedded); returns ``([B, ...], aux_scalar)``.

    Aux losses from bubble ticks (garbage activations warming the ring) are
    masked out by the validity predicate, then psum-reduced over stages and
    **averaged over microbatches** — per-invocation-mean aux terms (e.g. the
    MoE load-balancing loss, a mean over routed tokens) keep the same scale
    as an unpipelined step instead of growing with n_microbatches.
    """
    pp = mesh.shape.get(axis, 1)
    if pp <= 1:
        raise PipelineError(f"mesh axis {axis!r} has size {pp}; need > 1")
    for path, leaf in jax.tree_util.tree_leaves_with_path(stage_params):
        if leaf.shape[0] != pp:
            # A larger multiple would shard cleanly and then silently drop
            # every stage block but the first ([2, L/4, ...] -> p[0]).
            raise PipelineError(
                f"stage_params leaf {jax.tree_util.keystr(path)} has "
                f"{leaf.shape[0]} stages but mesh axis {axis!r} is {pp}"
            )
    xs = microbatch(x, n_microbatches)
    M = n_microbatches
    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
    # xs enters the manual region replicated over pp, so autodiff emits a
    # psum over pp for its cotangent; the output commit is an explicit psum.
    # Both cross the pp boundary in f32 on CPU (_boundary_f32): XLA CPU's
    # AllReducePromotion pass crashes on low-precision all-reduces
    # ("Invalid binary instruction opcode copy" in hlo_instruction.cc); on
    # TPU bf16 collectives run natively and no cast happens.
    compute_dtype = xs.dtype
    if _boundary_f32(compute_dtype):
        xs = xs.astype(jnp.float32)

    def schedule(params_local, xs):
        xs = xs.astype(compute_dtype)
        # params_local leaves: [1, L/pp, ...] — the local stage block.
        idx = jax.lax.axis_index(axis)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params_local)
        state0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs, aux_sum = carry
            # Stage 0 injects microbatch t (clamped; ticks >= M re-feed the
            # last microbatch and their results never land anywhere).
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            state_in = jnp.where(idx == 0, inject, state)
            y, aux = stage_fn(my_params, state_in)
            # At tick t, stage s processes microbatch t - s; only then is
            # its aux meaningful.
            valid_work = (t - idx >= 0) & (t - idx < M)
            aux_sum = aux_sum + jnp.where(valid_work, aux, 0.0)
            # The last stage commits microbatch t-(pp-1) once it exists.
            oidx = jnp.clip(t - (pp - 1), 0, M - 1)
            commit = (idx == pp - 1) & (t >= pp - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, oidx, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(commit, y, cur), oidx, 0
            )
            # Ring-shift activations one stage forward (ICI neighbor hop).
            state = jax.lax.ppermute(y, axis, fwd_perm)
            return (state, outs, aux_sum), None

        (_, outs, aux_sum), _ = jax.lax.scan(
            tick,
            (state0, out0, jnp.zeros((), jnp.float32)),
            jnp.arange(M + pp - 1),
        )
        # Output lives on the last stage; zero elsewhere then sum-replicate.
        acc = jnp.where(idx == pp - 1, outs, 0)
        if _boundary_f32(acc.dtype):
            acc = jax.lax.psum(acc.astype(jnp.float32), axis).astype(outs.dtype)
        else:
            acc = jax.lax.psum(acc, axis)
        # Average aux over microbatches: each microbatch contributed one
        # per-invocation mean, and M means summed would inflate the term M-x.
        return acc, jax.lax.psum(aux_sum, axis) / M

    # Stage-axis spec for params; everything else stays GSPMD-auto.
    param_in_specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    outs, aux = jax.shard_map(
        schedule,
        mesh=mesh,
        in_specs=(param_in_specs, P()),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )(stage_params, xs)
    return outs.reshape(x.shape), aux
