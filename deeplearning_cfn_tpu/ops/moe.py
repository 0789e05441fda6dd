"""Mixture-of-experts feed-forward with expert parallelism.

No reference analog exists (the reference is DP-only, SURVEY §2.3); expert
parallelism is part of the framework's first-class parallelism surface (the
``ep`` mesh axis, parallel/mesh.py).  The design is the canonical TPU MoE
recipe (GShard/Switch): **fixed-capacity dense dispatch** expressed as two
einsums against a [groups, tokens, experts, capacity] one-hot tensor — one
routing group per data-parallel shard — so every shape is static, the MXU
sees large batched matmuls, and with the group axis sharded over dp/fsdp and
the expert axis over ``ep``, XLA inserts the token all-to-alls automatically
and both dispatch buffers and expert compute scale down with the data-
parallel degree.  There is no scatter/gather, no dynamic shapes, and no
per-expert Python loop anywhere.

Capacity semantics: each expert processes at most C tokens per batch; tokens
over capacity are dropped from that expert's contribution (their residual
path still flows).  Top-1 assignments get slot priority over top-2 so the
primary expert of a token is the last to be dropped.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    # C = ceil(top_k * tokens * capacity_factor / n_experts), rounded up to
    # a multiple of 8 (TPU-friendly minor dims).
    capacity_factor: float = 1.25
    # Weight of the Switch load-balancing auxiliary loss.
    aux_loss_weight: float = 0.01


def expert_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    cap = math.ceil(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts)
    return max(8, int(math.ceil(cap / 8)) * 8)


def init_moe_params(
    cfg: MoEConfig, rng: jax.Array, dim: int, mlp_dim: int, dtype: Any = jnp.bfloat16
) -> dict:
    """Per-expert SwiGLU MLP weights, stacked on a leading expert axis."""
    keys = jax.random.split(rng, 4)
    E = cfg.n_experts

    def dense(key, shape, fan_in):
        scale = 1.0 / jnp.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    return {
        # Router stays f32: tiny, and routing decisions are precision-sensitive.
        "router": jax.random.normal(keys[0], (dim, E), jnp.float32) * 0.02,
        "w_gate": dense(keys[1], (E, dim, mlp_dim), dim),
        "w_up": dense(keys[2], (E, dim, mlp_dim), dim),
        "w_down": dense(keys[3], (E, mlp_dim, dim), mlp_dim),
    }


def moe_param_specs() -> dict:
    """Expert axis -> ep; within-expert matmul axes follow the dense-MLP 2D
    layout (fsdp x tp) so MoE composes with FSDP and tensor parallelism."""
    return {
        "router": P(None, None),
        "w_gate": P("ep", "fsdp", "tp"),
        "w_up": P("ep", "fsdp", "tp"),
        "w_down": P("ep", "tp", "fsdp"),
    }


from deeplearning_cfn_tpu.parallel.sharding import maybe_shard as _maybe_shard


def _n_data_groups(n_tokens: int) -> int:
    """Routing groups = data-parallel shards of the active mesh (GShard's
    G axis): capacity and dispatch are computed per group, so the [g, t, E,
    C] tensors and the expert matmuls shard over dp/fsdp x ep instead of
    being replicated per data shard.  All-or-nothing: a group count smaller
    than the shard count could not be sharded evenly over (dp, fsdp) anyway,
    so if the tokens don't split evenly we fall back to one unsharded group.
    1 when no mesh context is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return 1
    g = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    return g if g > 1 and n_tokens % g == 0 else 1


def moe_mlp(
    cfg: MoEConfig, params: dict, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """[B, S, d] -> ([B, S, d], aux_loss scalar).

    Canonical GShard layout: tokens are split into G routing groups (one
    per data-parallel shard); routing/capacity are local to a group, and
    dispatch/combine are einsums against a [G, t, E, C] one-hot tensor.
    Expert compute is a batched [G, E, C, d] x [E, d, m] matmul sharded over
    (dp/fsdp) x ep — XLA inserts the token all-to-all between the data and
    expert axes automatically.
    """
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    G = _n_data_groups(T)
    t = T // G  # tokens per routing group
    C = expert_capacity(cfg, t)
    group_axes = ("dp", "fsdp") if G > 1 else None
    xt = x.reshape(G, t, d)
    xt = _maybe_shard(xt, P(group_axes, None, None))

    router_logits = (xt.astype(jnp.float32)) @ params["router"]  # [G, t, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [G, t, k]
    if k > 1:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    # k == 1 keeps the raw top-1 probability (Switch): normalizing would
    # make the gate identically 1.0 — a constant with zero derivative
    # w.r.t. the router logits, leaving the router trainable only through
    # the aux loss.

    # Slot assignment with top-1 priority: within a group, experts fill
    # capacity from the k=0 choices of every token before any k=1 choice
    # claims a slot.
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [G, t, k, E]
    # [G, k, t, E] -> [G, k*t, E] so cumsum runs over all k=0 rows first.
    sel_priority = jnp.swapaxes(sel, 1, 2).reshape(G, k * t, E)
    pos = jnp.cumsum(sel_priority, axis=1) - sel_priority  # claim slot index
    pos = pos.reshape(G, k, t, E).swapaxes(1, 2)  # [G, t, k, E]
    within_cap = sel * (pos < C)  # claims that fit
    slot = jnp.sum(pos * within_cap, axis=-1).astype(jnp.int32)  # [G, t, k]

    # combine[g, i, e, c] = gate weight of token i in expert e slot c.
    slot_onehot = jax.nn.one_hot(slot, C, dtype=jnp.float32) * jnp.sum(
        within_cap, axis=-1, keepdims=True
    )  # [G, t, k, C]
    combine = jnp.einsum(
        "gike,gikc->giec", sel * gate_vals[..., None], slot_onehot
    )  # [G, t, E, C]
    dispatch = jnp.einsum("gike,gikc->giec", within_cap, slot_onehot)  # 0/1

    expert_in = jnp.einsum(
        "giec,gid->gecd", dispatch.astype(x.dtype), xt
    )  # [G, E, C, d]
    expert_in = _maybe_shard(expert_in, P(group_axes, "ep", None, None))
    gate = jax.nn.silu(
        jnp.einsum("gecd,edm->gecm", expert_in, params["w_gate"]).astype(jnp.float32)
    ).astype(x.dtype)
    up = jnp.einsum("gecd,edm->gecm", expert_in, params["w_up"])
    expert_out = jnp.einsum("gecm,emd->gecd", gate * up, params["w_down"])
    expert_out = _maybe_shard(expert_out, P(group_axes, "ep", None, None))
    y = jnp.einsum("giec,gecd->gid", combine.astype(x.dtype), expert_out)

    # Switch load-balancing loss: E * sum_e f_e * p_e per group, averaged
    # over groups; f_e = fraction of tokens whose top-1 choice is e, p_e =
    # mean router probability of e.  Minimized (=1) at uniform routing.
    f = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32), axis=1)
    p = jnp.mean(probs, axis=1)  # [G, E]
    aux_loss = cfg.aux_loss_weight * E * jnp.mean(jnp.sum(f * p, axis=-1))
    return y.reshape(B, S, d), aux_loss
