"""BERT-family encoder for masked-LM pretraining.

BASELINE.json config: "BERT-base pretraining (new examples/jax-bert;
data-parallel over ICI)" — no reference analog (SURVEY §2.3), built
TPU-first: bf16 compute with f32 LayerNorm/softmax, non-causal fused
attention, DP/FSDP via the trainer's sharding layer.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deeplearning_cfn_tpu.ops.attention import dot_product_attention


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16

    @classmethod
    def base(cls) -> "BertConfig":
        return cls()

    @classmethod
    def tiny(cls, vocab_size: int = 256, seq_len: int = 64) -> "BertConfig":
        return cls(
            vocab_size=vocab_size,
            dim=64,
            n_layers=2,
            n_heads=4,
            mlp_dim=128,
            max_seq_len=seq_len,
            dropout=0.0,
            dtype=jnp.float32,
        )


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        cfg = self.cfg
        head_dim = cfg.dim // cfg.n_heads
        B, S, _ = x.shape
        h = x
        qkv = nn.DenseGeneral(
            (3, cfg.n_heads, head_dim), dtype=cfg.dtype, name="qkv"
        )(h)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = dot_product_attention(q, k, v, causal=False)
        attn = attn.reshape(B, S, cfg.dim)
        attn = nn.Dense(cfg.dim, dtype=cfg.dtype, name="attn_out")(attn)
        attn = nn.Dropout(cfg.dropout, deterministic=deterministic)(attn)
        x = nn.LayerNorm(dtype=jnp.float32, name="attn_ln")(x + attn)
        mlp = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, name="mlp_in")(x)
        mlp = nn.gelu(mlp)
        mlp = nn.Dense(cfg.dim, dtype=cfg.dtype, name="mlp_out")(mlp)
        mlp = nn.Dropout(cfg.dropout, deterministic=deterministic)(mlp)
        return nn.LayerNorm(dtype=jnp.float32, name="mlp_ln")(x + mlp)


def _encoder_trunk(
    cfg: BertConfig, tokens: jnp.ndarray, deterministic: bool
) -> tuple[jnp.ndarray, nn.Embed]:
    """Shared embed+layers trunk.  Submodule names are created on the
    CALLING module, so BertEncoder and BertClassifier produce identical
    trunk parameter trees — a pretrain checkpoint transfers by key
    intersection (transfer_trunk_params)."""
    S = tokens.shape[1]
    embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype, name="tok_embed")
    x = embed(tokens)
    pos = nn.Embed(cfg.max_seq_len, cfg.dim, dtype=cfg.dtype, name="pos_embed")(
        jnp.arange(S)[None, :]
    )
    x = nn.LayerNorm(dtype=jnp.float32, name="embed_ln")(x + pos)
    for i in range(cfg.n_layers):
        x = BertLayer(cfg, name=f"layer{i}")(x, deterministic=deterministic)
    return x, embed


class BertEncoder(nn.Module):
    cfg: BertConfig = field(default_factory=BertConfig)

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        """tokens [B, S] -> MLM logits [B, S, vocab] (f32)."""
        cfg = self.cfg
        x, embed = _encoder_trunk(cfg, tokens, deterministic)
        # MLM head: transform + tied output embedding.
        x = nn.Dense(cfg.dim, dtype=cfg.dtype, name="mlm_transform")(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(x)
        logits = embed.attend(x.astype(cfg.dtype))
        return logits.astype(jnp.float32)


class BertClassifier(nn.Module):
    """Sequence classification head over the shared trunk (the GLUE-style
    fine-tuning surface): first-token pooling -> tanh pooler -> logits."""

    cfg: BertConfig = field(default_factory=BertConfig)
    num_classes: int = 2

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        """tokens [B, S] -> class logits [B, num_classes] (f32)."""
        cfg = self.cfg
        x, _ = _encoder_trunk(cfg, tokens, deterministic)
        pooled = jnp.tanh(
            nn.Dense(cfg.dim, dtype=cfg.dtype, name="pooler")(x[:, 0])
        )
        logits = nn.Dense(self.num_classes, dtype=jnp.float32, name="classifier")(
            pooled.astype(jnp.float32)
        )
        return logits


def transfer_trunk_params(pretrained: dict, target: dict) -> dict:
    """Copy every trunk parameter subtree present in BOTH trees (tok_embed,
    pos_embed, embed_ln, layer*) from a pretrained tree into a target
    (e.g. freshly-initialized classifier) tree.  Head params absent from
    either side keep the target's initialization."""
    out = dict(target)
    for key, value in pretrained.items():
        if key in out:
            out[key] = value
    return out


def mlm_loss(model: BertEncoder):
    """loss_fn(params, masked_tokens, targets): targets < 0 are unmasked
    positions and excluded from the loss (the -100 convention)."""

    def loss_fn(params, x, y):
        logits = model.apply({"params": params}, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        safe_targets = jnp.maximum(y, 0)
        nll = -jnp.take_along_axis(logp, safe_targets[..., None], axis=-1)[..., 0]
        mask = (y >= 0).astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll * mask) / denom
        masked_acc = jnp.sum(
            (jnp.argmax(logits, -1) == safe_targets).astype(jnp.float32) * mask
        ) / denom
        return loss, {"masked_accuracy": masked_acc}

    return loss_fn
