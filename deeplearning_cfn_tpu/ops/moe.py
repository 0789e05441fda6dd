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
from functools import partial
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


# --- routed experts without dropped tokens ----------------------------------
#
# The path a published expert model needs: no capacity, so no token is dropped
# at any imbalance.  The token-to-expert assignments are sorted by expert, the
# rows gathered into one [assignments, d] buffer, the three SwiGLU matmuls run
# as grouped matmuls over it (each expert's rows against its own weights), and
# the rows are weighted and summed back per token.  Every shape is static: the
# buffer has a row for each assignment that can go to an expert held here, and
# only the group sizes are data.  What depends on them is how far the work
# goes: the assignments to held experts sort to the front, their count is on
# the device, and the grouped matmul visits only the tiles that hold a
# group's rows.  Beside it, backward, every pass over the buffer (the sum of
# the two matmuls' cotangents, the SwiGLU's gradient, the weighting's with
# its gather of the result's cotangent) runs over the row tiles that begin
# before that count and overwrites what it reads (`_over_live_rows`): a tile
# past them is neither read nor written.  Forward there is one whole pass
# beside the matmuls, the SwiGLU under a row mask; the gather into the buffer
# has no select, and the weighting rides the token-major gather back
# (`_rows_back`), which reads a slot of every token and stays whole; where a
# token has more choices than experts held here, the same gather of the rows'
# cotangent runs over its live slots alone (`_live_slots_first`).  The
# one-hot path above stays for ``LlamaConfig.n_experts`` and the serving engine
# until experts are spread over the ``ep`` axis; a model module calls one or
# the other.


@dataclass(frozen=True)
class RoutedConfig:
    """A routed-experts layer: the router's variants as data.

    ``held = (first, count)`` is the chip's share under expert parallelism:
    the layer holds experts ``first .. first + count - 1`` of ``n_routed``,
    scores, selects and normalises over all ``n_routed``, and computes its own
    experts' part of the result.  What the absent experts would add is some
    other chip's to compute and to send; nothing here stands in for it.
    """

    n_routed: int
    top_k: int
    held: tuple[int, int] | None = None  # None: all of them
    score: str = "sigmoid"  # or "softmax"
    # A per-expert bias added to the scores for the selection only (the
    # auxiliary-loss-free balancing of arXiv:2408.15664): params["router_bias"].
    selection_bias: bool = False
    renormalize: bool = True  # the selected weights sum to 1 before `scale`
    # Added to the selected weights' sum before the division.
    renormalize_eps: float = 1e-20
    scale: float = 1.0
    # A shared expert every token passes through, of this width; 0: none.
    shared_dim: int = 0
    # An expert's form, as its model publishes it: ``swiglu`` is
    # ``w_down (silu(w_gate x) * w_up x)``, ``relu2`` is ``w_down relu(w_up x)^2``.
    expert: str = "swiglu"

    def __post_init__(self):
        first, count = self.span
        if not (1 <= self.top_k <= self.n_routed):
            raise ValueError(f"top_k={self.top_k} must be in [1, {self.n_routed}]")
        if first < 0 or count < 1 or first + count > self.n_routed:
            raise ValueError(f"held={self.held} is not a span of {self.n_routed} experts")
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown score function {self.score!r}")
        if self.expert not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert form {self.expert!r}")

    @property
    def span(self) -> tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_routed)

    def buffer_rows(self, n_tokens: int) -> int:
        """Rows of the sorted buffer.  A token's ``top_k`` choices are
        distinct experts, so at most ``min(top_k, count)`` of them are held
        here: that bounds the buffer, whatever the router does."""
        return n_tokens * min(self.top_k, self.span[1])


def init_routed_params(
    cfg: RoutedConfig, rng: jax.Array, dim: int, expert_dim: int, dtype: Any = jnp.bfloat16,
    rows_dim: int | None = None,
) -> dict:
    """The held experts' weights stacked on a leading axis (``w_gate`` only
    where the form has one), the router over all ``n_routed`` (float32:
    selection is precision-sensitive), and the shared expert.  ``rows_dim`` is
    the width of the rows the experts read and write where that is not the
    router's ``dim`` (latent experts)."""
    keys = jax.random.split(rng, 8)
    count = cfg.span[1]
    rows_dim = rows_dim or dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    params = {
        "router": jax.random.normal(keys[0], (dim, cfg.n_routed), jnp.float32) / math.sqrt(dim),
        "w_gate": dense(keys[1], (count, rows_dim, expert_dim), rows_dim),
        "w_up": dense(keys[2], (count, rows_dim, expert_dim), rows_dim),
        "w_down": dense(keys[3], (count, expert_dim, rows_dim), expert_dim),
    }
    if cfg.expert == "relu2":
        del params["w_gate"]
    if cfg.selection_bias:
        params["router_bias"] = jnp.zeros((cfg.n_routed,), jnp.float32)
    if cfg.shared_dim:
        params["shared_gate"] = dense(keys[4], (dim, cfg.shared_dim), dim)
        params["shared_up"] = dense(keys[5], (dim, cfg.shared_dim), dim)
        params["shared_down"] = dense(keys[6], (cfg.shared_dim, dim), cfg.shared_dim)
    return params


def routed_param_specs(cfg: RoutedConfig) -> dict:
    """fsdp x tp inside an expert, as the dense MLP.  The expert axis is
    not sharded: ``held`` says which experts this program's chips hold."""
    specs = {
        "router": P(None, None),
        "w_gate": P(None, "fsdp", "tp"),
        "w_up": P(None, "fsdp", "tp"),
        "w_down": P(None, "tp", "fsdp"),
    }
    if cfg.expert == "relu2":
        del specs["w_gate"]
    if cfg.selection_bias:
        specs["router_bias"] = P(None)
    if cfg.shared_dim:
        specs.update(
            shared_gate=P("fsdp", "tp"), shared_up=P("fsdp", "tp"), shared_down=P("tp", "fsdp")
        )
    return specs


def route(cfg: RoutedConfig, params: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [T, d] -> (experts [T, k] int32, weights [T, k] float32), over all
    ``n_routed`` experts, in float32 with a full-precision matmul: a score
    rounded to bfloat16 picks another expert than float32 does."""
    logits = jnp.matmul(
        x.astype(jnp.float32), params["router"], precision=jax.lax.Precision.HIGHEST
    )
    scores = jax.nn.sigmoid(logits) if cfg.score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    choice = scores
    if cfg.selection_bias:
        choice = scores + jax.lax.stop_gradient(params["router_bias"])
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + cfg.renormalize_eps)
    return experts.astype(jnp.int32), weights * cfg.scale


# Rows of a tile of the passes over the sorted buffer, from a sweep on v5e at
# the expert layer's shape (scripts/chip_grouped_matmul_sweep.py routing): a
# multiple of the grouped matmul's 512 rows, large enough that a loop's
# iteration costs little against a tile's megabytes.
ROW_TILE = 4096


def _live_tiles(n_rows: int, held: jax.Array) -> tuple[int, jax.Array]:
    """(rows of a tile, the tiles that begin before row `held`).  The tiles
    divide the buffer, so none overlaps another."""
    tile = n_rows if n_rows <= ROW_TILE else math.gcd(n_rows, ROW_TILE)
    return tile, jnp.minimum((held + tile - 1) // tile, n_rows // tile).astype(jnp.int32)


def _over_live_rows(f, held: jax.Array, tiled: tuple, whole: tuple = (), over: tuple = ()) -> tuple:
    """``f(*tiles, *whole) -> tuple`` over row tiles of the ``tiled`` arrays
    [R, ...], for the tiles that begin before row ``held`` and no other: the
    loop's trip count is that number, on the device.  Rows from ``held`` on
    come out as zeros within those tiles (the one tile at the boundary is where
    the mask selects).  ``over[n]`` names the ``tiled`` array that result ``n``
    overwrites, tile by tile after it is read: what that array held past the
    live tiles stays, and nobody may read it.  A result with no such array
    goes into zeros.  Nothing of a tile past the live ones is read, so those
    may hold what a kernel left uninitialised."""
    n_rows = tiled[0].shape[0]
    tile, live_tiles = _live_tiles(n_rows, held)
    shapes = jax.eval_shape(lambda *t: f(*t, *whole), *(a[:tile] for a in tiled))
    over = over or (None,) * len(shapes)
    fresh = tuple(
        jnp.zeros((n_rows,) + s.shape[1:], s.dtype) for s, j in zip(shapes, over) if j is None
    )

    def body(i, carry):
        tiled, fresh = list(carry[0]), list(carry[1])
        start = i * tile
        live = start + jnp.arange(tile) < held
        results = f(*(jax.lax.dynamic_slice_in_dim(a, start, tile) for a in tiled), *whole)
        n = 0
        for r, j in zip(results, over):
            r = jnp.where(live.reshape((tile,) + (1,) * (r.ndim - 1)), r, 0)
            if j is None:
                fresh[n] = jax.lax.dynamic_update_slice_in_dim(fresh[n], r, start, 0)
                n += 1
            else:
                tiled[j] = jax.lax.dynamic_update_slice_in_dim(tiled[j], r, start, 0)
        return tuple(tiled), tuple(fresh)

    tiled, fresh = jax.lax.fori_loop(0, live_tiles, body, (tuple(tiled), fresh))
    fresh = iter(fresh)
    return tuple(next(fresh) if j is None else tiled[j] for j in over)


def _rows_back(rows, slot, held, weight=None):
    """[R, d] -> [T, d]: token t gets the sum of rows[slot[t, :]] over its
    slots before row ``held``, each times ``weight[t, :]`` where given, added
    up in float32.  A slot of every token is read, whatever the count."""
    # [j, T]: the tokens on a tiled dimension, or the gather's result is laid out anew.
    slot = slot.T
    picked = rows[jnp.minimum(slot, rows.shape[0] - 1)]  # [j, T, d]
    if weight is not None:
        # Rounded to the rows' type as a product written to memory is: inside
        # a fusion the compiler would keep the float32 it computes in.
        bits = jnp.finfo(rows.dtype)
        picked = jax.lax.reduce_precision(picked * weight.T[..., None], bits.nexp, bits.nmant)
    picked = jnp.where((slot < held)[..., None], picked, 0)
    return jnp.sum(picked, axis=0, dtype=jnp.float32).astype(rows.dtype)


def _live_slots_first(slot, j):
    """[T, k] -> [T, j]: each token's ``j`` smallest slots.  The live rows are
    the buffer's front and at most ``j`` of a token's choices are held here,
    so every live slot of the token is among them: a token-major pass over
    them is no wider than the buffer.  One sort along a token's choices."""
    return jax.lax.sort(slot, dimension=1)[:, :j]


@jax.custom_vjp
def _rows_out(x, token, slot, held):
    """x [T, d] -> the buffer [R, d], once for each of its two grouped
    matmuls: row r is x[token[r]].  The gather runs whole: over counted tiles
    into zeros it took longer on the chip than it saved (PERF.md, PR 32), and
    the rows past ``held`` are some token's, which the matmuls do not read.
    ``slot`` [T, j] says where each token's rows went, so the transpose is a
    gather too and no scatter runs in either pass.  Each matmul's cotangent
    arrives by itself because the Pallas kernel leaves it uninitialised past
    the tiles it visits: they are added over the live tiles, not by JAX over
    the whole buffer."""
    rows = x[token]
    return rows, rows


def _rows_out_bwd(res, g):
    slot, held = res
    (g,) = _over_live_rows(lambda a, b: (a + b,), held, g, over=(0,))
    return _rows_back(g, slot, held), None, None, None


_rows_out.defvjp(
    lambda x, token, slot, held: (_rows_out(x, token, slot, held), (slot, held)), _rows_out_bwd
)


def _swiglu_tile(gate, up):
    return (jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up,)


@jax.custom_vjp
def _swiglu_rows(gate, up, held):
    """silu(gate) * up, the SiLU in float32, of the rows before ``held`` of
    two grouped matmuls' results; zeros from it on.  Forward it is one pass
    with a row mask: a result written tile by tile into zeros is a buffer
    more in the compiled step's temporaries (PERF.md, PR 32), which the
    passes backward avoid by overwriting what they read."""
    valid = (jnp.arange(gate.shape[0]) < held)[:, None]
    return jnp.where(valid, _swiglu_tile(gate, up)[0], 0)


def _swiglu_rows_bwd(res, g):
    gate, up, held = res

    def tile(g, up, gate):
        return jax.vjp(_swiglu_tile, gate, up)[1]((g,))

    # Over the cotangent and `up`, which nothing reads after this.
    d_gate, d_up = _over_live_rows(tile, held, (g, up, gate), over=(0, 1))
    return d_gate, d_up, None


_swiglu_rows.defvjp(
    lambda gate, up, held: (_swiglu_rows(gate, up, held), (gate, up, held)), _swiglu_rows_bwd
)


@jax.custom_vjp
def _rows_out_once(x, token, slot, held):
    """`_rows_out` for an expert with one matmul on its rows: the buffer
    once, and one cotangent, which `_rows_back` reads before ``held`` only."""
    return x[token]


_rows_out_once.defvjp(
    lambda x, token, slot, held: (x[token], (slot, held)),
    lambda res, g: (_rows_back(g, *res), None, None, None),
)


def _relu2_tile(up):
    return (jnp.square(jnp.maximum(up.astype(jnp.float32), 0)).astype(up.dtype),)


@jax.custom_vjp
def _relu2_rows(up, held):
    """relu(up)^2, squared in float32, of the rows before ``held`` of one
    grouped matmul's result; zeros from it on.  What `_swiglu_rows` is to a
    gated expert: one masked pass forward, the live tiles backward."""
    valid = (jnp.arange(up.shape[0]) < held)[:, None]
    return jnp.where(valid, _relu2_tile(up)[0], 0)


def _relu2_rows_bwd(res, g):
    up, held = res

    def tile(g, up):
        return jax.vjp(_relu2_tile, up)[1]((g,))

    # Over the cotangent, which nothing reads after this.
    (d_up,) = _over_live_rows(tile, held, (g, up), over=(0,))
    return d_up, None


_relu2_rows.defvjp(lambda up, held: (_relu2_rows(up, held), (up, held)), _relu2_rows_bwd)


def _weigh_tile(out, weight):
    return (out * weight[:, None],)


@jax.custom_vjp
def _weighted_rows_in(out, weights, row_weight, token, slot, held):
    """The other way: [R, d] -> [T, d], each token the sum of its rows before
    ``held``, each row times its weight: ``weights`` [T, j] by token, and
    ``row_weight`` [R] the same numbers in the buffer's order, which is how
    the backward pass reads them.  Forward the product rides the token-major
    gather; backward it runs over the live tiles."""
    return _rows_back(out, slot, held, weights)


def _weighted_rows_in_bwd(res, g):
    out, row_weight, token, slot, held = res

    def tile(out, weight, token, g):
        return jax.vjp(_weigh_tile, out, weight)[1]((g[token],))

    # Over `out`, which nothing reads after this.
    d_out, d_row = _over_live_rows(tile, held, (out, row_weight, token), (g,), over=(0, None))
    d_weights = jnp.where(slot < held, d_row[jnp.minimum(slot, d_row.shape[0] - 1)], 0)
    return d_out, d_weights, None, None, None, None


_weighted_rows_in.defvjp(
    lambda out, weights, row_weight, token, slot, held: (
        _weighted_rows_in(out, weights, row_weight, token, slot, held),
        (out, row_weight, token, slot, held),
    ),
    _weighted_rows_in_bwd,
)

# (rows, contraction, columns) of the grouped matmul's tiles on the TPU, from
# a sweep on v5e at the expert layer's shape (scripts/chip_grouped_matmul_sweep.py).
GROUPED_MATMUL_TILES = (512, 1024, 512)


def grouped_matmul_kind(backend: str | None = None) -> str:
    """``pallas`` on a TPU (the Mosaic grouped matmul), ``xla`` elsewhere
    (``jax.lax.ragged_dot``, which XLA expands to masked dense products off
    the TPU: the correctness path, as ``attention_kind``'s ``xla``)."""
    return "pallas" if (backend or jax.default_backend()) == "tpu" else "xla"


def grouped_matmul(
    rows: jax.Array, weights: jax.Array, group_sizes: jax.Array, kind: str,
    interpret: bool = False,
) -> jax.Array:
    """rows [R, a] sorted by group, weights [G, a, b], group_sizes [G] ->
    [R, b]: each group's rows times its own matrix.  The Pallas kernel visits
    the tiles that hold a group's rows and no other: what it leaves of its
    result past the last group's rows, and of its gradient with respect to
    ``rows``, is uninitialised (``ragged_dot`` gives zeros there).  A caller
    reads neither past the counted rows: ``_swiglu_rows`` and ``_rows_back``
    select by row, ``_over_live_rows`` stops at the counted tiles."""
    if kind == "xla":
        return jax.lax.ragged_dot(rows, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    R = rows.shape[0]
    tm, tk, tn = GROUPED_MATMUL_TILES
    tiles = (min(tm, R), min(tk, weights.shape[1]), min(tn, weights.shape[2]))
    if R % tiles[0]:
        raise ValueError(f"{R} rows do not divide into tiles of {tiles[0]}")
    return gmm(rows, weights, group_sizes, rows.dtype, tiles, interpret=interpret)


def routed_experts(
    cfg: RoutedConfig, params: dict, x: jax.Array, *, expert_rows: jax.Array | None = None,
    kind: str | None = None, interpret: bool = False,
) -> tuple[jax.Array, dict]:
    """[B, S, d] -> ([B, S, d], statistics of the routing).

    The result is the held experts' part of sum_i w_i E_i(x), plus the shared
    expert.  The router reads ``x``; the experts read ``expert_rows``
    [B, S, l] where a model gives them rows of their own (a latent of the
    hidden state) and the result is then [B, S, l], else ``x``.  The statistics are scalars for the step's counters (assignments
    in all and to held experts, the largest held expert's load, `dropped`:
    assignments to held experts less rows computed, which is 0, and
    `rows_run`: the buffer's rows that the backward pass's passes beside the
    matmuls run over, the live tiles' rows, `slots_read`: the buffer rows the
    two token-major gathers fetch, forward and backward) and ``selected``
    [T, k], the experts each token chose.
    """
    B, S, d = x.shape
    T, k = B * S, cfg.top_k
    first, count = cfg.span
    kind = kind or grouped_matmul_kind()
    xt = x.reshape(T, d)
    rows_in = xt if expert_rows is None else expert_rows.reshape(T, expert_rows.shape[-1])
    if expert_rows is not None and cfg.shared_dim:
        raise ValueError("a shared expert beside experts with rows of their own is the model's")
    with jax.named_scope("router"):
        experts, weights = route(cfg, params, xt)
    with jax.named_scope("dispatch"):
        # Assignments to held experts sort to the front by expert, the
        # others behind them under one more index.
        local = jnp.where((experts >= first) & (experts < first + count), experts - first, count)
        local = local.reshape(T * k)
        # In the activations' type: a float32 copy of the buffer, of its
        # gather and of both cotangents is 2 GB at 65,536 rows of 2048.
        weights = weights.astype(x.dtype)
        # The argsort, with the weights riding it into the buffer's order.
        by_row = jax.lax.stop_gradient(weights).reshape(T * k)
        by_expert, order, row_weight = jax.lax.sort(
            (local, jnp.arange(T * k, dtype=jnp.int32), by_row), num_keys=1, is_stable=True
        )
        R = cfg.buffer_rows(T)
        slot = jnp.argsort(order).astype(jnp.int32).reshape(T, k)  # inverse permutation
        # The gather back of the rows' cotangent, no wider than the buffer.  The
        # weighted pass keeps a token's every choice: with it and its cotangent
        # over the live slots the Nemotron step hangs on the chip (PERF.md, PR 48).
        slot_out = _live_slots_first(slot, count) if k > count else slot
        order, row_weight = order[:R], row_weight[:R]
        token = (order // k).astype(jnp.int32)
        group_sizes = jnp.sum(
            local[:, None] == jnp.arange(count, dtype=local.dtype)[None, :], axis=0, dtype=jnp.int32
        )
        held = jnp.sum(group_sizes)  # the rows that hold an assignment: the front of the buffer
        if cfg.expert == "swiglu":
            rows_gate, rows_up = _rows_out(rows_in, token, slot_out, held)
        else:
            rows_up = _rows_out_once(rows_in, token, slot_out, held)
    with jax.named_scope("experts"):
        mm = partial(grouped_matmul, group_sizes=group_sizes, kind=kind, interpret=interpret)
        if cfg.expert == "swiglu":
            wide = _swiglu_rows(mm(rows_gate, params["w_gate"]), mm(rows_up, params["w_up"]), held)
        else:
            wide = _relu2_rows(mm(rows_up, params["w_up"]), held)
        out = mm(wide, params["w_down"])
    with jax.named_scope("combine"):
        y = _weighted_rows_in(out, weights, row_weight, token, slot, held)
    if cfg.shared_dim:
        with jax.named_scope("shared"):
            g = jax.nn.silu((xt @ params["shared_gate"]).astype(jnp.float32)).astype(x.dtype)
            y = y + (g * (xt @ params["shared_up"])) @ params["shared_down"]
    tile, live_tiles = _live_tiles(R, held)
    stats = {
        "assignments": jnp.asarray(T * k, jnp.int32),
        "assignments_held": held,  # from the selection
        "load_max": jnp.max(group_sizes),
        "dropped": held - jnp.sum(by_expert[:R] < count, dtype=jnp.int32),  # from the buffer
        "rows_run": live_tiles * tile,
        "slots_read": jnp.asarray(slot.size + slot_out.size, jnp.int32),
        "selected": experts,
    }
    return y.reshape(B, S, rows_in.shape[-1]), stats


def routing_counters(cfg: RoutedConfig, stats: list[dict]) -> dict:
    """A step's statistics from `routed_experts` over every routed block (one
    dict a block, or stacked on a leading axis) as the scalars the trainer
    folds into `obs.tracing` counters: sums of assignments and of the buffer's
    rows the layers' passes ran over and the token-major gathers fetch, the
    largest and the mean load of a held expert, and what was dropped."""
    every = {
        k: jnp.concatenate([jnp.atleast_1d(s[k]) for s in stats])
        for k in ("assignments", "assignments_held", "rows_run", "slots_read", "load_max", "dropped")
    }
    held = jnp.sum(every["assignments_held"])
    return {
        "moe.assignments": jnp.sum(every["assignments"]),
        "moe.assignments_held": held,
        "moe.rows_run": jnp.sum(every["rows_run"]),
        "moe.slots_read": jnp.sum(every["slots_read"]),
        "moe.expert_load_max": jnp.max(every["load_max"]),
        "moe.expert_load_mean": held / (every["load_max"].shape[0] * cfg.span[1]),
        "moe.dropped": jnp.sum(every["dropped"]),
    }
