"""Sharding rules: logical array axes -> mesh axes.

The reference never shards parameters — every strategy it implements is
data-parallel with replicated weights (SURVEY §2.3).  Here sharding is a
first-class, declarative layer: parameters carry logical axis names and a
rule table maps them onto mesh axes, in the pjit/GSPMD style.  XLA then
inserts the collectives (all-gather for FSDP params, reduce-scatter for
grads, all-to-all for experts) that Horovod/NCCL provided as a runtime
service in the reference (run.sh:70-79) — but fused into the compiled
program instead of a background daemon.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Default logical-to-mesh rules.  Keys are logical axis names used by models;
# values are mesh axis names (or tuples) or None (replicate).
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("dp", "fsdp"),  # data sharded over both flavors of DP
    "sequence": "sp",
    "embed": "fsdp",  # FSDP shards params along the embed/hidden axis
    "mlp": "tp",
    "heads": "tp",
    "kv": None,
    "vocab": "tp",
    "expert": "ep",
    "layers": None,
    "conv_kernel": None,
    "stage": "pp",
}


def spec_for(logical_axes: Sequence[str | None], rules: dict[str, Any] | None = None) -> P:
    rules = {**DEFAULT_RULES, **(rules or {})}
    return P(*(rules.get(a) if a is not None else None for a in logical_axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, rules: dict[str, Any] | None = None) -> NamedSharding:
    """Sharding for [batch, ...] arrays: batch split over the data axes."""
    return NamedSharding(mesh, spec_for(["batch"]) if rules is None else spec_for(["batch"], rules))


def _fsdp_spec_for_array(x: Any, mesh: Mesh, min_shard_elems: int = 2**14) -> P:
    """Heuristic FSDP rule when a model doesn't annotate logical axes:
    shard the largest dimension divisible by the fsdp axis size; replicate
    small arrays (biases, norms) where sharding buys nothing but latency."""
    fsdp = mesh.shape.get("fsdp", 1)
    if fsdp <= 1 or x.ndim == 0 or int(np.prod(x.shape)) < min_shard_elems:
        return P()
    dims = sorted(range(x.ndim), key=lambda d: x.shape[d], reverse=True)
    for d in dims:
        if x.shape[d] % fsdp == 0:
            spec: list[Any] = [None] * x.ndim
            spec[d] = "fsdp"
            return P(*spec)
    return P()


def infer_param_sharding(params: Any, mesh: Mesh) -> Any:
    """Pytree of NamedShardings for a parameter tree (heuristic FSDP)."""
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, _fsdp_spec_for_array(x, mesh)), params
    )


def maybe_shard(x: Any, spec: P) -> Any:
    """Apply a with_sharding_constraint hint when a mesh context is active;
    no-op otherwise.  Lets model code stay mesh-agnostic — the trainer sets
    the context mesh (trainer.train_step)."""
    if not jax.sharding.get_abstract_mesh().axis_names:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def bytes_by_device(tree: Any) -> dict[int, int]:
    """Bytes of ``tree``'s addressable shards resident on each device,
    keyed by device id — how a run shows that its state and its input
    reached every chip and did not pile up on device 0.  Reads shard
    metadata only: no transfer, no sync."""
    out: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return out


def shard_pytree(tree: Any, shardings: Any) -> Any:
    """Place a host pytree onto devices with the given shardings."""
    return jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), tree, shardings)
