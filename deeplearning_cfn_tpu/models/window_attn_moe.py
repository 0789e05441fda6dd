"""A decoder whose attention layers are of two kinds in one stack: full causal
attention and a sliding window, with different head counts and different
rotary rules, a head-wise sigmoid gate on the attention output, and a dense
SwiGLU or routed experts with a shared one after it.  The block of Laguna
(`laguna`).

Beside `models/conv_attn_moe.py`, on `models/decoder_stack.py`'s
pattern-as-data machinery as it is (`runs_of`, `init_runs`, `run_specs`,
`scan_runs`: consecutive layers of one kind are a run, each run one stack of
weights and one `scan`), and built from the shared parts where the block is
the same (`llama.attention_kind`, `attend`, `swiglu`, `ops/moe.routed_experts`,
`decoder_stack`'s embedding, head with its rematerialised loss and routing
counters).  A module of its own and not two more entries in
`conv_attn_moe.MIXERS`: there a layer's kind is read off the
leaves it is given and every attention layer has the configuration's one head
count, rotary rule and mask; here the kind (mixer, heads, routed) decides
shapes, mask and rotary rule and is closed over by each run's block, the
norms carry the Llama family's names, and the head is untied.  What differs:

- **Two attention kinds.**  ``layer_types[i]`` is ``full_attention`` (causal
  over everything) or ``sliding_attention`` (query t sees keys
  ``t - sliding_window < j <= t``); ``heads_per_layer[i]`` query heads share the
  ``n_kv_heads`` key/value heads, so the kinds differ in group size too.  Both
  go through `llama.attend`, the window as its argument.
- **Two rotary rules** (`RotaryRule`): plain rotary over the whole head, or
  YaRN frequencies over the first ``partial`` of it with cos and sin scaled
  by the attention factor, the rest of the head passing through.
- **A gate a head**: ``g = sigmoid(x W_g)``, one scalar a head and token from
  the same normalised input as q, multiplies that head's output before the
  output projection (the head-wise gate of arXiv:2505.06708).
- **The feed-forward by `mlp_layer_types`**: ``dense`` or ``sparse`` (routed
  experts, sigmoid scores renormalised over the selected, a shared expert).

Parameters: ``embed``, ``output``, ``final_norm`` and ``runs``.  Scopes: a full
layer's attention under ``attn/{qkv,gate,rope,core,out}``, a sliding layer's
under ``attn_window/`` with the same names.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
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
from deeplearning_cfn_tpu.ops.attention import partial_rotary_embedding, rms_norm, yarn_inv_freq
from deeplearning_cfn_tpu.ops.moe import (
    RoutedConfig,
    init_routed_params,
    routed_experts,
    routed_param_specs,
)

MIXERS = ("full_attention", "sliding_attention")
FEED_FORWARDS = ("dense", "sparse")
# A run's kind: its mixer, its query heads, whether its feed-forward is routed.
Kind = tuple[str, int, bool]
# The scope a mixer's attention runs under.
SCOPES = {"full_attention": "attn", "sliding_attention": "attn_window"}


@dataclass(frozen=True)
class RotaryRule:
    """One kind of layer's rotary embedding.  ``partial`` of each head rotates
    (split halves within that part), the rest passes through.  With
    ``yarn_factor`` the frequencies are YaRN's (`ops.attention.yarn_inv_freq`)
    and cos and sin are multiplied by ``attention_factor``."""

    theta: float
    partial: float = 1.0
    yarn_factor: float | None = None
    original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, head_dim: int) -> np.ndarray:
        dim = int(head_dim * self.partial)
        if self.yarn_factor is None:
            return (self.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
        return yarn_inv_freq(
            dim, self.theta, self.yarn_factor, self.original_max, self.beta_fast, self.beta_slow
        )

    def rotate(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        return partial_rotary_embedding(
            x, positions, self.inv_freq(x.shape[-1]), self.attention_factor
        )


# Laguna-XS.2's `rope_parameters`.
FULL_ROTARY = RotaryRule(
    theta=500000.0, partial=0.5, yarn_factor=64.0, original_max=4096,
    beta_fast=64.0, beta_slow=1.0, attention_factor=1.4158883083359672,
)
SLIDING_ROTARY = RotaryRule(theta=10000.0)


@dataclass(frozen=True)
class WindowAttnMoeConfig:
    """Sizes under the names of the published `config.json` keys' meaning.
    The defaults are Laguna-XS.2's widths and its first four layers (the dense
    full layer and three window layers); `published()` is all 40."""

    vocab_size: int = 100352
    dim: int = 2048
    layer_types: tuple[str, ...] = ("full_attention",) + ("sliding_attention",) * 3
    mlp_layer_types: tuple[str, ...] = ("dense",) + ("sparse",) * 3
    heads_per_layer: tuple[int, ...] = (48, 64, 64, 64)  # num_attention_heads_per_layer
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    gating: bool = True
    full_rotary: RotaryRule = FULL_ROTARY
    sliding_rotary: RotaryRule = SLIDING_ROTARY
    mlp_dim: int = 8192  # the dense layers' feed-forward
    expert_dim: int = 512
    shared_expert_dim: int = 512
    n_experts: int = 256
    held_experts: tuple[int, int] | None = None  # (first, count); None: all
    top_k: int = 8
    routed_scaling_factor: float = 2.5
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    use_ring_attention: bool = False  # `attention_kind` asks; a window refuses it

    def __post_init__(self):
        n = len(self.layer_types)
        if not n or len(self.mlp_layer_types) != n or len(self.heads_per_layer) != n:
            raise ValueError(
                f"layer_types ({n}), mlp_layer_types ({len(self.mlp_layer_types)}) and "
                f"heads_per_layer ({len(self.heads_per_layer)}) name every layer"
            )
        unknown = sorted(set(self.layer_types) - set(MIXERS)) + sorted(
            set(self.mlp_layer_types) - set(FEED_FORWARDS)
        )
        if unknown:
            raise ValueError(f"{unknown}: a layer is one of {MIXERS} and one of {FEED_FORWARDS}")
        if any(h % self.n_kv_heads for h in self.heads_per_layer) or self.head_dim % 4:
            raise ValueError(
                f"heads {sorted(set(self.heads_per_layer))} do not share {self.n_kv_heads} "
                f"key/value heads, or head_dim={self.head_dim} has no even rotary half"
            )

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kinds(self) -> tuple[Kind, ...]:
        """Every layer's (mixer, query heads, routed)."""
        return tuple(
            (m, h, f == "sparse")
            for m, h, f in zip(self.layer_types, self.heads_per_layer, self.mlp_layer_types)
        )

    @property
    def runs(self) -> tuple[tuple[Kind, int], ...]:
        return runs_of(self.kinds)

    @property
    def routed(self) -> RoutedConfig:
        return RoutedConfig(
            n_routed=self.n_experts,
            top_k=self.top_k,
            held=self.held_experts,
            score="sigmoid",
            selection_bias=True,
            renormalize=True,
            scale=self.routed_scaling_factor,
            shared_dim=self.shared_expert_dim,
        )

    @classmethod
    def published(cls, **kw) -> "WindowAttnMoeConfig":
        """All 40 layers: the period of four ten times, the first layer dense."""
        period = cls()
        base = dict(
            layer_types=period.layer_types * 10,
            mlp_layer_types=("dense",) + ("sparse",) * 39,
            heads_per_layer=period.heads_per_layer * 10,
        )
        return cls(**{**base, **kw})

    @classmethod
    def tiny(cls, **kw) -> "WindowAttnMoeConfig":
        """The structure at toy widths, for the CPU tests: groups of 3 and 4,
        a window shorter than the tests' sequences."""
        base = dict(
            vocab_size=128, dim=32, n_kv_heads=2, head_dim=8, sliding_window=6,
            layer_types=("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",),
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            heads_per_layer=(6, 8, 8, 8, 6),
            full_rotary=RotaryRule(
                theta=500000.0, partial=0.5, yarn_factor=4.0, original_max=8,
                beta_fast=4.0, beta_slow=1.0, attention_factor=1.1386294361119891,
            ),
            mlp_dim=64, expert_dim=16, shared_expert_dim=16, n_experts=8, held_experts=(0, 4),
            top_k=2, max_seq_len=64, remat=False, dtype=jnp.float32,
        )
        return cls(**{**base, **kw})


# --- parameters ---------------------------------------------------------


def _block_params(cfg: WindowAttnMoeConfig, key: jax.Array, kind: Kind) -> dict:
    _, heads, routed = kind
    keys = jax.random.split(key, 8)
    d, hd = cfg.dim, cfg.head_dim
    init = partial(dense_init, dtype=cfg.dtype)
    params = {
        "attn_norm": jnp.ones((d,), jnp.float32),
        "mlp_norm": jnp.ones((d,), jnp.float32),
        "wq": init(keys[0], (d, heads * hd), d),
        "wk": init(keys[1], (d, cfg.n_kv_heads * hd), d),
        "wv": init(keys[2], (d, cfg.n_kv_heads * hd), d),
        "wo": init(keys[3], (heads * hd, d), heads * hd),
    }
    if cfg.gating:
        params["wg"] = init(keys[7], (d, heads), d)
    if routed:
        params["moe"] = init_routed_params(cfg.routed, keys[4], d, cfg.expert_dim, cfg.dtype)
    else:
        params["w_gate"] = init(keys[4], (d, cfg.mlp_dim), d)
        params["w_up"] = init(keys[5], (d, cfg.mlp_dim), d)
        params["w_down"] = init(keys[6], (cfg.mlp_dim, d), cfg.mlp_dim)
    return params


def init_params(cfg: WindowAttnMoeConfig, rng: jax.Array) -> dict:
    k_embed, k_output, k_runs = jax.random.split(rng, 3)
    return {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, cfg.dtype),
        "output": dense_init(k_output, (cfg.dim, cfg.vocab_size), cfg.dim, cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "runs": init_runs(partial(_block_params, cfg), cfg.runs, k_runs),
    }


def _block_specs(cfg: WindowAttnMoeConfig, kind: Kind) -> dict:
    specs = {
        "attn_norm": P(None), "mlp_norm": P(None),
        "wq": P("fsdp", "tp"), "wk": P("fsdp", "tp"), "wv": P("fsdp", "tp"), "wo": P("tp", "fsdp"),
    }
    if cfg.gating:
        specs["wg"] = P("fsdp", "tp")
    if kind[2]:
        specs["moe"] = routed_param_specs(cfg.routed)
    else:
        specs.update(w_gate=P("fsdp", "tp"), w_up=P("fsdp", "tp"), w_down=P("tp", "fsdp"))
    return specs


def param_specs(cfg: WindowAttnMoeConfig) -> dict:
    """fsdp on a matrix's input axis, tp on its output axis, as llama.py."""
    return {
        "embed": P("tp", "fsdp"),
        "output": P("fsdp", "tp"),
        "final_norm": P(None),
        "runs": run_specs(partial(_block_specs, cfg), cfg.runs),
    }


def param_shardings(cfg: WindowAttnMoeConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def param_count(cfg: WindowAttnMoeConfig) -> int:
    return decoder_stack.count(cfg, init_params)


def attended_keys(cfg: WindowAttnMoeConfig, mixer: str, seq_len: int) -> float:
    """Scores one query head computes over a sequence: the causal triangle
    (counted as half the square, as the other decoders count it), or the band
    of a window, ``S W - W (W - 1) / 2``."""
    w = min(cfg.sliding_window, seq_len)
    if mixer == "sliding_attention":
        return seq_len * w - w * (w - 1) / 2
    return seq_len * seq_len / 2


def train_flops_per_token(cfg: WindowAttnMoeConfig, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token costs: 6 per weight it
    passes through (an expert held here at its expectation, `top_k` times the
    held share; the shared expert; the router; the head), and the score
    products over the triangle or the band, by layer."""
    d, hd = cfg.dim, cfg.head_dim
    routed = cfg.routed
    weights, scores = d * cfg.vocab_size, 0.0
    for mixer, heads, sparse in cfg.kinds:
        weights += 2 * d * heads * hd + 2 * d * cfg.n_kv_heads * hd + (d * heads if cfg.gating else 0)
        if sparse:
            held = routed.top_k * routed.span[1] / routed.n_routed
            weights += d * cfg.n_experts + 3 * d * cfg.expert_dim * held + 3 * d * cfg.shared_expert_dim
        else:
            weights += 3 * d * cfg.mlp_dim
        # QK^T and PV, 2 hd each a score, three times that with the backward pass
        scores += 3 * 2 * 2 * hd * heads * attended_keys(cfg, mixer, seq_len) / seq_len
    return 6.0 * weights + scores


# --- forward ------------------------------------------------------------


def _attention_mixer(
    cfg: WindowAttnMoeConfig, mesh: Mesh | None, kind: Kind, lp: dict, h: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Gated GQA on the normalised input h [B, S, d]: full causal or under
    the window, rotated by the kind's rule."""
    mixer, heads, _ = kind
    sliding = mixer == "sliding_attention"
    rotary = cfg.sliding_rotary if sliding else cfg.full_rotary
    B, S, _ = h.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv"):
        q = (h @ lp["wq"]).reshape(B, S, heads, hd)
        k = (h @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
        v = (h @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    with jax.named_scope("rope"):
        q, k = rotary.rotate(q, positions), rotary.rotate(k, positions)
    with jax.named_scope("core"):
        attn = attend(
            attention_kind(cfg, mesh, S), q, k, v, mesh,
            window=cfg.sliding_window if sliding else None,
        )
    if cfg.gating:
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))  # [B, S, heads]
            attn = (attn.astype(jnp.float32) * gate[..., None]).astype(h.dtype)
    with jax.named_scope("out"):
        return attn.reshape(B, S, heads * hd) @ lp["wo"]


def _block(
    cfg: WindowAttnMoeConfig, mesh: Mesh | None, kind: Kind, x: jax.Array, lp: dict,
    positions: jax.Array,
) -> tuple[jax.Array, dict | None]:
    """One block of `kind`: (x, the routing's statistics or None)."""
    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope(SCOPES[kind[0]]):
        x = x + _attention_mixer(cfg, mesh, kind, lp, h, positions)
    with jax.named_scope("mlp_norm"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if kind[2]:
        with jax.named_scope("moe"):
            y, stats = routed_experts(cfg.routed, lp["moe"], h)
        return x + y, stats
    with jax.named_scope("mlp"):
        return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def hidden_states(
    cfg: WindowAttnMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> tuple[jax.Array, list[dict]]:
    """tokens [B, S] -> (the last block's output before the final norm
    [B, S, d], each routed run's statistics stacked on its layer axis)."""
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def block_of(kind: Kind):
        block = checkpointed(cfg, partial(_block, cfg, mesh, kind))
        return lambda x, lp: block(x, lp, positions)

    return scan_runs(block_of, cfg.runs, params["runs"], x)


def lm_loss(
    cfg: WindowAttnMoeConfig, params: dict, tokens: jax.Array, targets: jax.Array,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy; `targets[i]` is the token that follows
    `tokens[i]` (the last one wrapped, and masked).  The head with its loss
    is rematerialised (`decoder_stack.next_token_loss`)."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    return decoder_stack.next_token_loss(
        cfg, params["final_norm"], params["output"], x, targets, stats
    )


def logits(
    cfg: WindowAttnMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> dict:
    """float32 logits and each routed block's selection [blocks, T, k]: the
    inspection entry point, not the train hot path."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    return decoder_stack.inspect_logits(cfg, params["final_norm"], params["output"], x, stats)


def make_trainer(cfg: WindowAttnMoeConfig, mesh: Mesh, trainer_config) -> Any:
    """The generic SPMD Trainer on this model, as `llama.make_trainer`."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )
