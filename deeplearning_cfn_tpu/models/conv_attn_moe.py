"""A decoder whose layers take their token mixer from a list: a gated short
convolution or grouped-query attention with normalised heads, each followed
by a dense SwiGLU or routed experts.  The block of LFM2 (`lfm2_moe`).

Built from `models/llama.py`'s parts (`attention_kind`, `attend`, `swiglu`),
`ops/moe.routed_experts` and `models/decoder_stack.py`'s (runs of stacked
weights, the embedding, the head with its rematerialised loss, the routing
counters) where the block is the same; what differs is here:

- **The layer pattern is data.**  `layer_types[i]` is ``conv`` or
  ``full_attention``; the first `n_dense_layers` layers have a dense
  feed-forward, every other one routed experts (no shared one).  Layers of
  two kinds have different parameters, so one `[L, ...]` stack cannot hold
  them: consecutive layers of one kind are a *run*, each run one stack and
  one `scan`, and the runs follow each other in the program.
- **Gated short convolution.**  ``[B | C | u] = x W_in``; ``z = B * u``;
  ``c_t = sum_j w[j] * z[t - (L - 1) + j]`` over `conv_taps` taps with zeros
  before the sequence's start (depthwise, causal, no bias);
  ``out = (C * c) W_out``.  The taps are shifted multiply-adds in float32
  that XLA fuses with the two gates.
- **Attention** is causal GQA with an RMSNorm over each query and key head
  (one learned scale of `head_dim` for the queries, one for the keys) before
  the rotary embedding.
- **The head is the embedding table transposed**, after a final RMSNorm.

Parameters: ``embed``, ``final_norm`` and ``runs``, a list with one dict of
stacked weights per run, in forward order.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning_cfn_tpu.models import decoder_stack
from deeplearning_cfn_tpu.models.decoder_stack import (
    checkpointed,
    dense_init,
    embed,
    init_runs,
    run_specs,
    runs_of,
    scan_runs,
)
from deeplearning_cfn_tpu.models.llama import attend, attention_kind, swiglu
from deeplearning_cfn_tpu.ops.attention import rms_norm, rotary_embedding
from deeplearning_cfn_tpu.ops.conv import short_conv
from deeplearning_cfn_tpu.ops.moe import (
    RoutedConfig,
    init_routed_params,
    routed_experts,
    routed_param_specs,
)

MIXERS = ("conv", "full_attention")
# A run's kind: its mixer and whether its feed-forward is routed.
Kind = tuple[str, bool]


@dataclass(frozen=True)
class ConvAttnMoeConfig:
    """Sizes under the names of the published `config.json` keys' meaning."""

    vocab_size: int = 65536
    dim: int = 2048
    # 24 layers: conv, conv, attn, then [conv, conv, conv, attn] with a shorter tail.
    layer_types: tuple[str, ...] = (
        ("conv", "conv", "full_attention") + ("conv", "conv", "conv", "full_attention") * 4
        + ("conv", "conv", "full_attention", "conv", "conv")
    )
    n_dense_layers: int = 2  # num_dense_layers: the first layers' feed-forward
    n_heads: int = 32
    n_kv_heads: int = 8
    conv_taps: int = 3  # conv_L_cache
    mlp_dim: int = 7168  # the dense layers' feed-forward
    expert_dim: int = 1792
    n_experts: int = 32
    held_experts: tuple[int, int] | None = None  # (first, count); None: all
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    use_ring_attention: bool = False  # `attention_kind` asks; not built here

    def __post_init__(self):
        unknown = sorted(set(self.layer_types) - set(MIXERS))
        if not self.layer_types or unknown:
            raise ValueError(f"layer_types holds {unknown or 'nothing'}; a layer is one of {MIXERS}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of n_layers={self.n_layers}"
            )
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"dim={self.dim} does not divide into {self.n_heads} even heads over "
                f"{self.n_kv_heads} key/value heads"
            )

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def kinds(self) -> tuple[Kind, ...]:
        """Every layer's (mixer, routed)."""
        return tuple((m, i >= self.n_dense_layers) for i, m in enumerate(self.layer_types))

    @property
    def runs(self) -> tuple[tuple[Kind, int], ...]:
        """Consecutive layers of one kind: (kind, how many)."""
        return runs_of(self.kinds)

    @property
    def routed(self) -> RoutedConfig:
        return RoutedConfig(
            n_routed=self.n_experts,
            top_k=self.top_k,
            held=self.held_experts,
            score="sigmoid",
            selection_bias=self.use_expert_bias,
            renormalize=self.norm_topk_prob,
            renormalize_eps=1e-6,  # `lfm2_moe` adds it to the selected scores' sum
            scale=self.routed_scaling_factor,
            shared_dim=0,
        )

    @classmethod
    def tiny(cls, **kw) -> "ConvAttnMoeConfig":
        """The structure at toy widths, for the CPU tests."""
        base = dict(
            vocab_size=128, dim=32, n_dense_layers=1, n_heads=4, n_kv_heads=2, mlp_dim=64,
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            expert_dim=16, n_experts=8, held_experts=(0, 4), top_k=2, max_seq_len=64,
            remat=False, dtype=jnp.float32,
        )
        return cls(**{**base, **kw})


# --- parameters ---------------------------------------------------------


def _block_params(cfg: ConvAttnMoeConfig, key: jax.Array, kind: Kind) -> dict:
    mixer, routed = kind
    keys = jax.random.split(key, 8)
    d, hd = cfg.dim, cfg.head_dim
    init = partial(dense_init, dtype=cfg.dtype)
    params = {
        "operator_norm": jnp.ones((d,), jnp.float32),
        "ffn_norm": jnp.ones((d,), jnp.float32),
    }
    if mixer == "conv":
        params["conv_in"] = init(keys[0], (d, 3 * d), d)
        params["conv_w"] = init(keys[1], (cfg.conv_taps, d), cfg.conv_taps)
        params["conv_out"] = init(keys[2], (d, d), d)
    else:
        params["wq"] = init(keys[0], (d, cfg.n_heads * hd), d)
        params["wk"] = init(keys[1], (d, cfg.n_kv_heads * hd), d)
        params["wv"] = init(keys[2], (d, cfg.n_kv_heads * hd), d)
        params["wo"] = init(keys[3], (cfg.n_heads * hd, d), cfg.n_heads * hd)
        params["q_norm"] = jnp.ones((hd,), jnp.float32)
        params["k_norm"] = jnp.ones((hd,), jnp.float32)
    if routed:
        params["moe"] = init_routed_params(cfg.routed, keys[4], d, cfg.expert_dim, cfg.dtype)
    else:
        params["w_gate"] = init(keys[4], (d, cfg.mlp_dim), d)
        params["w_up"] = init(keys[5], (d, cfg.mlp_dim), d)
        params["w_down"] = init(keys[6], (cfg.mlp_dim, d), cfg.mlp_dim)
    return params


def init_params(cfg: ConvAttnMoeConfig, rng: jax.Array) -> dict:
    k_embed, k_runs = jax.random.split(rng)
    return {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "runs": init_runs(partial(_block_params, cfg), cfg.runs, k_runs),
    }


def _block_specs(cfg: ConvAttnMoeConfig, kind: Kind) -> dict:
    mixer, routed = kind
    specs = {"operator_norm": P(None), "ffn_norm": P(None)}
    if mixer == "conv":
        specs.update(conv_in=P("fsdp", "tp"), conv_w=P(None, "tp"), conv_out=P("tp", "fsdp"))
    else:
        specs.update(
            wq=P("fsdp", "tp"), wk=P("fsdp", "tp"), wv=P("fsdp", "tp"), wo=P("tp", "fsdp"),
            q_norm=P(None), k_norm=P(None),
        )
    if routed:
        specs["moe"] = routed_param_specs(cfg.routed)
    else:
        specs.update(w_gate=P("fsdp", "tp"), w_up=P("fsdp", "tp"), w_down=P("tp", "fsdp"))
    return specs


def param_specs(cfg: ConvAttnMoeConfig) -> dict:
    """fsdp on a matrix's input axis, tp on its output axis, as llama.py;
    a run's stacked layer axis is never sharded."""
    return {
        "embed": P("tp", "fsdp"),
        "final_norm": P(None),
        "runs": run_specs(partial(_block_specs, cfg), cfg.runs),
    }


def param_shardings(cfg: ConvAttnMoeConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def param_count(cfg: ConvAttnMoeConfig) -> int:
    return decoder_stack.count(cfg, init_params)


def train_flops_per_token(cfg: ConvAttnMoeConfig, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token costs: 6 per weight it
    passes through (an expert held here at its expectation, `top_k` times the
    held share; the router; the tied table once, as the head), and the causal
    half of the score products in the attention layers."""
    d, hd = cfg.dim, cfg.head_dim
    mixer = {
        "conv": 3 * d * d + cfg.conv_taps * d + d * d,
        "full_attention": 2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd,
    }
    routed = cfg.routed
    feed_forward = {
        True: d * cfg.n_experts + 3 * d * cfg.expert_dim * routed.top_k * routed.span[1] / routed.n_routed,
        False: 3 * d * cfg.mlp_dim,
    }
    weights = d * cfg.vocab_size + sum(mixer[m] + feed_forward[r] for m, r in cfg.kinds)
    scores = 3 * seq_len * cfg.n_heads * 2 * hd * cfg.layer_types.count("full_attention")
    return 6.0 * weights + scores


# --- forward ------------------------------------------------------------


def _conv_mixer(lp: dict, h: jax.Array) -> jax.Array:
    """The gated short convolution on the normalised input h [B, S, d]."""
    with jax.named_scope("in"):
        b, c, u = jnp.split(h @ lp["conv_in"], 3, axis=-1)
    with jax.named_scope("core"):
        f32 = lambda a: a.astype(jnp.float32)
        gated = f32(c) * short_conv(f32(b) * f32(u), f32(lp["conv_w"]))
        gated = gated.astype(h.dtype)
    with jax.named_scope("out"):
        return gated @ lp["conv_out"]


def _attention_mixer(
    cfg: ConvAttnMoeConfig, mesh: Mesh | None, lp: dict, h: jax.Array, positions: jax.Array
) -> jax.Array:
    """Causal GQA with normalised query and key heads on h [B, S, d]."""
    B, S, _ = h.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv"):
        q = (h @ lp["wq"]).reshape(B, S, cfg.n_heads, hd)
        k = (h @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
        v = (h @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    with jax.named_scope("rope"):
        q = rotary_embedding(q, positions, cfg.rope_theta)
        k = rotary_embedding(k, positions, cfg.rope_theta)
    with jax.named_scope("core"):
        attn = attend(attention_kind(cfg, mesh, S), q, k, v, mesh)
    with jax.named_scope("out"):
        return attn.reshape(B, S, cfg.n_heads * hd) @ lp["wo"]


def _block(
    cfg: ConvAttnMoeConfig, mesh: Mesh | None, x: jax.Array, lp: dict, positions: jax.Array
) -> tuple[jax.Array, dict | None]:
    """One block, its mixer and its feed-forward by what `lp` holds: (x, the
    routing's statistics or None)."""
    with jax.named_scope("operator_norm"):
        h = rms_norm(x, lp["operator_norm"], cfg.norm_eps)
    if "conv_in" in lp:
        with jax.named_scope("conv"):
            x = x + _conv_mixer(lp, h)
    else:
        with jax.named_scope("attn"):
            x = x + _attention_mixer(cfg, mesh, lp, h, positions)
    with jax.named_scope("ffn_norm"):
        h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "moe" in lp:
        with jax.named_scope("moe"):
            y, stats = routed_experts(cfg.routed, lp["moe"], h)
        return x + y, stats
    with jax.named_scope("mlp"):
        return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def hidden_states(
    cfg: ConvAttnMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> tuple[jax.Array, list[dict]]:
    """tokens [B, S] -> (the last block's output before the final norm
    [B, S, d], each routed run's statistics stacked on its layer axis)."""
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    block = checkpointed(cfg, partial(_block, cfg, mesh))
    # One block for every kind: it reads a layer's kind off the leaves it is given.
    return scan_runs(lambda kind: lambda x, lp: block(x, lp, positions), cfg.runs, params["runs"], x)


def lm_loss(
    cfg: ConvAttnMoeConfig, params: dict, tokens: jax.Array, targets: jax.Array,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy; `targets[i]` is the token that follows
    `tokens[i]` (the last one wrapped, and masked).  The head with its loss
    is rematerialised (`decoder_stack.next_token_loss`)."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    return decoder_stack.next_token_loss(
        cfg, params["final_norm"], params["embed"].T, x, targets, stats
    )


def logits(
    cfg: ConvAttnMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> dict:
    """float32 logits and each routed block's selection [blocks, T, k]: the
    inspection entry point, not the train hot path."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    return decoder_stack.inspect_logits(cfg, params["final_norm"], params["embed"].T, x, stats)


def make_trainer(cfg: ConvAttnMoeConfig, mesh: Mesh, trainer_config) -> Any:
    """The generic SPMD Trainer on this model, as `llama.make_trainer`."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )
