"""A decoder with latent attention and routed experts: the block of
DeepSeek-V3 (arXiv:2412.19437), which `glm4_moe_lite` follows key for key.

Beside `models/llama.py`, and built from its parts (`attention_kind`,
`attend`, `swiglu`, the stacked-layer layout) and from
`models/decoder_stack.py`'s (the embedding, the head with its rematerialised
loss, the checkpoint wrapper) where the block is the same; what differs is here:

- **Latent attention (MLA).**  Queries go through a low-rank pair
  (`wq_a`, RMSNorm, `wq_b`); keys and values are expanded from one
  normalised latent per token (`wkv_a`, RMSNorm, `wkv_b`), and one rotary
  key of `qk_rope_head_dim`, shared by all heads, is concatenated to each
  head's un-rotated part.  Training computes keys and values expanded, so
  the core is ordinary causal attention over heads of
  `qk_nope_head_dim + qk_rope_head_dim` (the absorbed form, which attends in
  the latent space, is for serving's cache and is not here).
- **Feed-forward.**  The first `n_dense_layers` blocks have a dense SwiGLU;
  every other block routes each token to `top_k` of `n_routed_experts`
  (`ops/moe.routed_experts`: no capacity, nothing dropped) and adds a shared
  expert.  `held_experts = (first, count)` is the chip's share of every
  layer under expert parallelism.
- **Multi-token prediction** (DeepSeek-V3 section 2.2), `n_predict` 0 or 1:
  the last block's output and the next token's embedding, each normalised,
  are joined by one matrix, go through one more routed block and the main
  model's output head, and predict the token after the next.  The loss is
  the next-token cross-entropy plus `mtp_loss_weight` times that one's.

Parameters are `llama.py`'s layout: one `[L, ...]` stack per weight kind for
the dense blocks (`dense`), one for the routed blocks (`layers`), the
prediction module beside them (`mtp`).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning_cfn_tpu.models import decoder_stack
from deeplearning_cfn_tpu.models.decoder_stack import checkpointed, dense_init, embed, head, head_loss
from deeplearning_cfn_tpu.models.llama import attend, attention_kind, swiglu
from deeplearning_cfn_tpu.ops.attention import rms_norm, rotary_embedding
from deeplearning_cfn_tpu.ops.moe import (
    RoutedConfig,
    init_routed_params,
    routed_experts,
    routed_param_specs,
    routing_counters,
)


@dataclass(frozen=True)
class MlaMoeConfig:
    """Sizes under the names of the published `config.json` keys' meaning."""

    vocab_size: int = 154880
    dim: int = 2048
    n_layers: int = 47  # dense and routed blocks together
    n_dense_layers: int = 1  # first_k_dense_replace
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    mlp_dim: int = 10240  # the dense blocks' feed-forward
    expert_dim: int = 1536  # each routed expert's and the shared one's
    n_routed_experts: int = 64
    held_experts: tuple[int, int] | None = None  # (first, count); None: all
    top_k: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_predict: int = 1  # num_nextn_predict_layers
    mtp_loss_weight: float = 0.3
    max_seq_len: int = 202752
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    use_ring_attention: bool = False  # `attention_kind` asks; not built here

    def __post_init__(self):
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} must leave a routed block of "
                f"n_layers={self.n_layers}"
            )
        if self.n_predict not in (0, 1):
            raise ValueError(f"n_predict={self.n_predict}: one prediction module or none")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head has an even size")

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def routed(self) -> RoutedConfig:
        return RoutedConfig(
            n_routed=self.n_routed_experts,
            top_k=self.top_k,
            held=self.held_experts,
            score=self.scoring_func,
            selection_bias=True,
            renormalize=self.norm_topk_prob,
            scale=self.routed_scaling_factor,
            shared_dim=self.n_shared_experts * self.expert_dim,
        )

    @classmethod
    def tiny(cls, **kw) -> "MlaMoeConfig":
        """The structure at toy widths, for the CPU tests."""
        base = dict(
            vocab_size=128, dim=32, n_layers=3, n_heads=2, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16, mlp_dim=64, expert_dim=16,
            n_routed_experts=8, held_experts=(0, 4), top_k=2, max_seq_len=64, remat=False,
            dtype=jnp.float32,
        )
        return cls(**{**base, **kw})


# --- parameters ---------------------------------------------------------


def _attention_params(cfg: MlaMoeConfig, key: jax.Array) -> dict:
    keys = jax.random.split(key, 5)
    d, H = cfg.dim, cfg.n_heads
    init = partial(dense_init, dtype=cfg.dtype)
    return {
        "attn_norm": jnp.ones((d,), jnp.float32),
        "wq_a": init(keys[0], (d, cfg.q_lora_rank), d),
        "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "wq_b": init(keys[1], (cfg.q_lora_rank, H * cfg.qk_head_dim), cfg.q_lora_rank),
        "wkv_a": init(keys[2], (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "wkv_b": init(
            keys[3], (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            cfg.kv_lora_rank,
        ),
        "wo": init(keys[4], (H * cfg.v_head_dim, d), H * cfg.v_head_dim),
        "mlp_norm": jnp.ones((d,), jnp.float32),
    }


def _block_params(cfg: MlaMoeConfig, key: jax.Array, routed: bool) -> dict:
    k_attn, k_ff = jax.random.split(key)
    params = _attention_params(cfg, k_attn)
    if routed:
        params["moe"] = init_routed_params(cfg.routed, k_ff, cfg.dim, cfg.expert_dim, cfg.dtype)
    else:
        keys = jax.random.split(k_ff, 3)
        init = partial(dense_init, dtype=cfg.dtype)
        params["w_gate"] = init(keys[0], (cfg.dim, cfg.mlp_dim), cfg.dim)
        params["w_up"] = init(keys[1], (cfg.dim, cfg.mlp_dim), cfg.dim)
        params["w_down"] = init(keys[2], (cfg.mlp_dim, cfg.dim), cfg.mlp_dim)
    return params


def _stacked(cfg: MlaMoeConfig, key: jax.Array, n: int, routed: bool) -> dict:
    blocks = [_block_params(cfg, k, routed) for k in jax.random.split(key, n)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)


def init_params(cfg: MlaMoeConfig, rng: jax.Array) -> dict:
    keys = jax.random.split(rng, 6)
    d = cfg.dim
    params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, d), d, cfg.dtype),
        "output": dense_init(keys[1], (d, cfg.vocab_size), d, cfg.dtype),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": _stacked(cfg, keys[3], cfg.n_routed_layers, routed=True),
    }
    if cfg.n_dense_layers:
        params["dense"] = _stacked(cfg, keys[2], cfg.n_dense_layers, routed=False)
    if cfg.n_predict:
        params["mtp"] = {
            "hidden_norm": jnp.ones((d,), jnp.float32),
            "embed_norm": jnp.ones((d,), jnp.float32),
            "join": dense_init(keys[4], (2 * d, d), 2 * d, cfg.dtype),
            "block": _block_params(cfg, keys[5], routed=True),
            "final_norm": jnp.ones((d,), jnp.float32),
        }
    return params


def _block_specs(cfg: MlaMoeConfig, routed: bool) -> dict:
    specs = {
        "attn_norm": P(None), "q_norm": P(None), "kv_norm": P(None), "mlp_norm": P(None),
        "wq_a": P("fsdp", "tp"), "wq_b": P("fsdp", "tp"),
        "wkv_a": P("fsdp", "tp"), "wkv_b": P("fsdp", "tp"),
        "wo": P("tp", "fsdp"),
    }
    if routed:
        specs["moe"] = routed_param_specs(cfg.routed)
    else:
        specs.update(w_gate=P("fsdp", "tp"), w_up=P("fsdp", "tp"), w_down=P("tp", "fsdp"))
    return specs


def param_specs(cfg: MlaMoeConfig) -> dict:
    """fsdp on a matrix's input axis, tp on its output axis, as llama.py;
    the stacked layer axis is never sharded."""
    is_spec = lambda x: isinstance(x, P)
    stack = lambda tree: jax.tree_util.tree_map(lambda s: P(None, *s), tree, is_leaf=is_spec)
    specs = {
        "embed": P("tp", "fsdp"),
        "output": P("fsdp", "tp"),
        "final_norm": P(None),
        "layers": stack(_block_specs(cfg, routed=True)),
    }
    if cfg.n_dense_layers:
        specs["dense"] = stack(_block_specs(cfg, routed=False))
    if cfg.n_predict:
        specs["mtp"] = {
            "hidden_norm": P(None), "embed_norm": P(None), "join": P("fsdp", "tp"),
            "block": _block_specs(cfg, routed=True), "final_norm": P(None),
        }
    return specs


def param_shardings(cfg: MlaMoeConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def param_count(cfg: MlaMoeConfig) -> int:
    return decoder_stack.count(cfg, init_params)


def train_flops_per_token(cfg: MlaMoeConfig, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token costs: 6 per weight it
    passes through (an expert held here at its expectation, `top_k` times the
    held share; the router; the shared expert; both heads and the joining
    matrix), and the causal half of the score products."""
    d, H = cfg.dim, cfg.n_heads
    attention = (
        d * cfg.q_lora_rank + cfg.q_lora_rank * H * cfg.qk_head_dim
        + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        + H * cfg.v_head_dim * d
    )
    routed = cfg.routed
    experts = 3 * d * cfg.expert_dim * (
        routed.top_k * routed.span[1] / routed.n_routed + cfg.n_shared_experts
    )
    routed_block = attention + d * cfg.n_routed_experts + experts
    dense_block = attention + 3 * d * cfg.mlp_dim
    blocks = cfg.n_layers + cfg.n_predict
    weights = (
        cfg.n_dense_layers * dense_block
        + (cfg.n_routed_layers + cfg.n_predict) * routed_block
        + (1 + cfg.n_predict) * d * cfg.vocab_size
        + cfg.n_predict * 2 * d * d
    )
    scores = 3 * seq_len * H * (cfg.qk_head_dim + cfg.v_head_dim) * blocks
    return 6.0 * weights + scores


# --- forward ------------------------------------------------------------


def _latent_attention(
    cfg: MlaMoeConfig, mesh: Mesh | None, lp: dict, h: jax.Array, positions: jax.Array
) -> jax.Array:
    """MLA on the normalised input h [B, S, d] -> [B, S, d]."""
    B, S, _ = h.shape
    H, nope, rope, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("q_down"):
        cq = rms_norm(h @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
    with jax.named_scope("q_up"):
        q = (cq @ lp["wq_b"]).reshape(B, S, H, nope + rope)
    with jax.named_scope("kv_down"):
        ckv = h @ lp["wkv_a"]
        c = rms_norm(ckv[..., : cfg.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
        k_rope = ckv[..., cfg.kv_lora_rank :].reshape(B, S, 1, rope)
    with jax.named_scope("kv_up"):
        kv = (c @ lp["wkv_b"]).reshape(B, S, H, nope + vd)
        v = kv[..., nope:]
    with jax.named_scope("rope"):
        q = jnp.concatenate(
            [q[..., :nope], rotary_embedding(q[..., nope:], positions, cfg.rope_theta)], axis=-1
        )
        k_rope = rotary_embedding(k_rope, positions, cfg.rope_theta)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))], axis=-1
        )
    with jax.named_scope("core"):
        kind = attention_kind(cfg, mesh, S)
        if kind == "flash" and cfg.qk_head_dim != vd:
            kind = "xla"  # the kernel takes one head size for q, k and v
        attn = attend(kind, q, k, v, mesh)
    with jax.named_scope("out"):
        return attn.reshape(B, S, H * vd) @ lp["wo"]


def _block(
    cfg: MlaMoeConfig, mesh: Mesh | None, x: jax.Array, lp: dict, positions: jax.Array
) -> tuple[jax.Array, dict | None]:
    """One block, dense or routed by what `lp` holds: (x, the routing's
    statistics or None)."""
    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        x = x + _latent_attention(cfg, mesh, lp, h, positions)
    with jax.named_scope("mlp_norm"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if "moe" in lp:
        with jax.named_scope("moe"):
            y, stats = routed_experts(cfg.routed, lp["moe"], h)
        return x + y, stats
    with jax.named_scope("mlp"):
        return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def _scan_blocks(cfg, mesh, x, stack, positions):
    """The stack's blocks in turn, each rematerialised; the routing's
    statistics stacked on the layer axis (None for dense blocks)."""
    block = checkpointed(cfg, partial(_block, cfg, mesh))

    def body(x, lp):
        return block(x, lp, positions)

    return jax.lax.scan(body, x, stack)


def hidden_states(
    cfg: MlaMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> tuple[jax.Array, dict]:
    """tokens [B, S] -> (the last block's output before the final norm
    [B, S, d], the routed blocks' statistics stacked [L, ...])."""
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    if cfg.n_dense_layers:
        x, _ = _scan_blocks(cfg, mesh, x, params["dense"], positions)
    return _scan_blocks(cfg, mesh, x, params["layers"], positions)


def _predicted(
    cfg: MlaMoeConfig, params: dict, x: jax.Array, next_tokens: jax.Array, mesh: Mesh | None
) -> tuple[jax.Array, dict]:
    """The prediction module up to its own final norm's input: the last
    block's output x and the embedding of each position's next token,
    joined, through one routed block."""
    mtp = params["mtp"]
    with jax.named_scope("join"):
        joined = jnp.concatenate(
            [
                rms_norm(x, mtp["hidden_norm"], cfg.norm_eps),
                rms_norm(embed(cfg, params, next_tokens), mtp["embed_norm"], cfg.norm_eps),
            ],
            axis=-1,
        )
        h = joined @ mtp["join"]
    with jax.named_scope("block"):
        block = checkpointed(cfg, partial(_block, cfg, mesh))
        return block(h, mtp["block"], jnp.arange(x.shape[1], dtype=jnp.int32))


def lm_loss(
    cfg: MlaMoeConfig, params: dict, tokens: jax.Array, targets: jax.Array,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy plus `mtp_loss_weight` times the prediction
    module's of the token after.  `targets[i]` is the token that follows
    `tokens[i]` (the last one wrapped, and masked).  Each head with its loss
    is rematerialised: two sets of logits and one gradient of them are
    larger than everything else the backward pass keeps."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    one_head = checkpointed(cfg, partial(head_loss, cfg))
    loss = main = one_head(params["final_norm"], params["output"], x, targets, ahead=1)
    stats = [stats]
    metrics = {"perplexity": jnp.exp(main)}
    if cfg.n_predict:
        with jax.named_scope("mtp"):
            h, mtp_stats = _predicted(cfg, params, x, targets, mesh)
            mtp = one_head(
                params["mtp"]["final_norm"], params["output"], h,
                jnp.roll(targets, -1, axis=1), ahead=2,
            )
        stats.append(mtp_stats)
        loss = main + cfg.mtp_loss_weight * mtp
        metrics["mtp_loss"] = mtp
    metrics["counters"] = routing_counters(cfg.routed, stats)
    return loss, metrics


def logits(
    cfg: MlaMoeConfig, params: dict, tokens: jax.Array, targets: jax.Array | None = None,
    mesh: Mesh | None = None,
) -> dict:
    """float32 logits of the main head and, given `targets`, of the
    prediction module, with each routed block's selection [blocks, T, k]: the
    inspection entry point, not the train hot path."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    out = {
        "main": head(cfg, params["final_norm"], params["output"], x).astype(jnp.float32),
        "selected": stats["selected"],
    }
    if cfg.n_predict and targets is not None:
        h, mtp_stats = _predicted(cfg, params, x, targets, mesh)
        out["mtp"] = head(cfg, params["mtp"]["final_norm"], params["output"], h).astype(
            jnp.float32
        )
        out["selected"] = jnp.concatenate([out["selected"], mtp_stats["selected"][None]])
    return out


def make_trainer(cfg: MlaMoeConfig, mesh: Mesh, trainer_config) -> Any:
    """The generic SPMD Trainer on this model, as `llama.make_trainer`."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )
