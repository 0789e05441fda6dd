"""What every decoder of this package shares, under all of them: `llama.py`
and the six kinds built beside it (`mla_moe`, `conv_attn_moe`,
`window_attn_moe`, `ssm_attn_moe`, `looped_decoder`, `mamba_attn`) import this
module, and it imports none of `models/`.

A kind's own module holds what *is* the kind: its configuration, a block's
parameters and their specs, its mixers and its block, `hidden_states`, its
FLOPs a token.  Everything else it calls from here:

- **runs of stacked weights**: the format of ``params["runs"]`` for a decoder
  whose layers differ in kind, and the scans over it;
- **the two ends**: the seeded matrix, the embedding lookup, the final norm and
  head, the next-token cross-entropy and its mean over the positions that have
  a target;
- **what a rematerialised unit keeps**: the policy and the wrapper that uses it;
- **the trainer's surface** of a functional model: the batch's spec, shardings
  from specs, a parameter count, the loss and the inspection entry of a decoder
  with one head, and the one place in `models/` that builds a `Trainer`.
"""

from __future__ import annotations

from functools import partial
from itertools import groupby
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning_cfn_tpu.ops.attention import rms_norm
from deeplearning_cfn_tpu.ops.moe import routing_counters
from deeplearning_cfn_tpu.parallel.sharding import maybe_shard

BATCH_SPEC = P(("dp", "fsdp"), "sp")  # [batch, seq] token arrays


# --- the pattern as data: runs of stacked weights -------------------------
#
# A layer's kind is whatever decides its parameters' shapes and its
# computation (a tuple, a string of blocks).  Consecutive layers of one kind
# are a *run*: one stack of weights and one `scan`.  These four functions are
# all that knows the format; a kind calls them with its own kinds, block
# parameters and block.


def runs_of(kinds) -> tuple:
    """Consecutive layers of one kind: ((kind, how many), ...)."""
    return tuple((kind, len(list(group))) for kind, group in groupby(kinds))


def init_runs(block_params, runs, key: jax.Array) -> list[dict]:
    """One dict of stacked weights a run, in forward order;
    ``block_params(key, kind)`` makes one block's."""
    stacks = []
    for (kind, n), run_key in zip(runs, jax.random.split(key, len(runs))):
        blocks = [block_params(k, kind) for k in jax.random.split(run_key, n)]
        stacks.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks))
    return stacks


def run_specs(block_specs, runs) -> list[dict]:
    """``block_specs(kind)`` with a leading, never sharded, layer axis."""
    is_spec = lambda x: isinstance(x, P)
    stack = lambda tree: jax.tree_util.tree_map(lambda s: P(None, *s), tree, is_leaf=is_spec)
    return [stack(block_specs(kind)) for kind, _ in runs]


def scan_runs(block_of, runs, stacks: list[dict], x: jax.Array) -> tuple[jax.Array, list[dict]]:
    """One `scan` a run, the runs in turn: ``block_of(kind)(x, lp)`` gives
    (x, the routing's statistics or None).  Returns the last block's output
    and each routed run's statistics stacked on its layer axis."""
    stats = []
    for (kind, _), stack in zip(runs, stacks, strict=True):
        x, run_stats = jax.lax.scan(block_of(kind), x, stack)
        if run_stats is not None:
            stats.append(run_stats)
    return x, stats


# --- the two ends of a decoder ---------------------------------------------


def dense_init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)


def embed(cfg, params: dict, tokens: jax.Array) -> jax.Array:
    # The stored table is P("tp", "fsdp"); gathering from it directly makes
    # the lookup output emb-sharded over fsdp, and GSPMD cannot reshard
    # {emb: fsdp} -> {batch: fsdp, seq: sp} without replicating the whole
    # activation ("involuntary full rematerialization", the round-1 dryrun
    # warning).  Constraining the bf16 working copy to P("tp", None) keeps
    # vocab sharded (the large axis) while the gather output inherits the
    # token sharding (batch over dp/fsdp, seq over sp) plus an unsharded
    # emb axis — exactly the activation layout, so the second constraint is
    # a no-op instead of a blocking reshard.
    table = maybe_shard(params["embed"].astype(cfg.dtype), P("tp", None))
    return maybe_shard(table[tokens], P(("dp", "fsdp"), "sp", None))


def head(cfg, norm: jax.Array, output: jax.Array, x: jax.Array) -> jax.Array:
    """Logits in the compute type (`llama.head_logits` says why)."""
    with jax.named_scope("final_norm"):
        x = rms_norm(x, norm, cfg.norm_eps)
    with jax.named_scope("head"):
        return x @ output


def token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Each position's cross-entropy, float32, in the logsumexp form of
    -log_softmax[target]: the [B, S, V] logits are only ever READ by
    reductions (XLA fuses the bf16->f32 convert into them) instead of
    materialized as an f32 copy plus a full-width f32 log_softmax — at V=32k
    that materialization was ~28% of the 435M training step
    (docs/BENCH_NOTES.md round-3 trace)."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - gold.astype(jnp.float32)


def head_loss(cfg, norm, output, x, targets, ahead: int) -> jax.Array:
    """Mean cross-entropy of `targets` over the positions that have one:
    the last `ahead` of a sequence hold a wrapped token and are left out."""
    logits = head(cfg, norm, output, x)
    with jax.named_scope("xent"):
        nll = token_nll(logits, targets)
        mask = (jnp.arange(targets.shape[1]) < targets.shape[1] - ahead).astype(jnp.float32)
        return jnp.sum(nll * mask) / (targets.shape[0] * jnp.sum(mask))


# --- what a rematerialised unit keeps ----------------------------------------


def remat_keeps(also: Callable[..., Any] | None = None) -> Callable[..., Any]:
    """The `jax.checkpoint` policy of every rematerialised decoder block
    (`llama.forward_with_aux`'s, and through `checkpointed` the five kinds'):
    a full-causal flash call's `out` and `lse` are kept, layers x [B, S, H, D]
    in the compute dtype and a float32 a row, so that the quadratic forward
    kernel runs once a step and not again for the backward pass; everything
    else is recomputed, or saved where ``also`` (another policy) says.  The
    policy finds the pair by its names: a block without such a call (ring or
    XLA attention, a head and its loss) keeps nothing.  A windowed call's pair
    is named too and NOT kept: its band step is 2.93 ms a call where the
    full-causal kernel is 15.36 (the Laguna cell), for more bytes of `out`
    (64 heads against 48).

    The next residual takes one of two roads, and this is the one place that
    says which.  (1) Kept from the forward pass to the backward pass, a layer
    of it a layer: a name where it is made (`checkpoint_name`, as
    `ops/pallas_attention.py` names `FLASH_RESIDUALS`) plus one line here that
    adds the name, priced in `models/llama_memory.py`; for a value that costs
    a kernel of the forward's size to make again, with the measurement that
    says so (the windowed pair's says it does not).  (2) Not kept: the block's
    recomputation makes it again, and a `custom_vjp` that saves its inputs
    alone (`ops/pallas_ssm_stages.py`'s stages; `ops/pallas_ssd.py`'s scan,
    with its chunks' states) bounds what lives between that recomputation and
    the block's backward pass.  No name, no line here, and nothing two
    packages must agree on: the road of an elementwise or linear-time stage."""
    from deeplearning_cfn_tpu.ops.pallas_attention import FLASH_RESIDUALS

    keeps = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)
    if also is None:
        return keeps
    return jax.checkpoint_policies.save_from_both_policies(keeps, also)


def checkpointed(cfg, fn):
    return jax.checkpoint(fn, policy=remat_keeps()) if cfg.remat else fn


# --- the trainer's surface of a functional decoder -----------------------------


class FunctionalInit:
    """Adapter giving the functional model the tiny surface Trainer.init
    expects (a flax-style ``init`` returning {"params": ...})."""

    def __init__(self, cfg: Any, init_fn):
        self.cfg = cfg
        self.init_fn = init_fn

    def init(self, rng: jax.Array, sample: jax.Array) -> dict:
        del sample
        return {"params": self.init_fn(self.cfg, rng)}


def shardings(specs: dict, mesh: Mesh) -> dict:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs, is_leaf=lambda x: isinstance(x, P)
    )


def count(cfg, init_params) -> int:
    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.key(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


def next_token_loss(cfg, norm, output, x, targets, stats: list[dict]) -> tuple[jax.Array, dict]:
    """The loss of a decoder with one head on its last block's output x:
    next-token cross-entropy, `targets[i]` the token that follows `tokens[i]`
    (the last one wrapped, and masked), with the routed runs' counters.  The
    head with its loss is rematerialised: the logits and their gradient are
    larger than everything else the backward pass keeps."""
    loss = checkpointed(cfg, partial(head_loss, cfg))(norm, output, x, targets, ahead=1)
    metrics = {"perplexity": jnp.exp(loss)}
    if stats:
        metrics["counters"] = routing_counters(cfg.routed, stats)
    return loss, metrics


def inspect_logits(cfg, norm, output, x, stats: list[dict]) -> dict:
    """float32 logits and each routed block's selection [blocks, T, k]: the
    inspection entry point, not the train hot path."""
    out = {"main": head(cfg, norm, output, x).astype(jnp.float32)}
    if stats:
        out["selected"] = jnp.concatenate([s["selected"] for s in stats])
    return out


def make_trainer(
    cfg, mesh: Mesh, trainer_config, *, init_params, lm_loss, param_specs, train_flops_per_token
) -> Any:
    """Wire a functional decoder into the generic SPMD Trainer: explicit 2D
    param shardings, token batch sharded over (dp/fsdp, sp), the kind's loss."""
    from deeplearning_cfn_tpu.train.trainer import Trainer

    return Trainer(
        FunctionalInit(cfg, init_params),
        mesh,
        trainer_config,
        loss_fn=lambda p, x, y: lm_loss(cfg, p, x, y, mesh),
        param_shardings=shardings(param_specs(cfg), mesh),
        batch_spec=BATCH_SPEC,
        # Analytic 6N numerator: flash attention runs in a Pallas custom
        # call whose FLOPs XLA cost analysis cannot see, so every MFU
        # consumer must use this instead (docs/BENCH_NOTES.md).
        analytic_flops_fn=lambda x: (
            train_flops_per_token(cfg, x.shape[1]) * x.shape[0] * x.shape[1]
        ),
    )
