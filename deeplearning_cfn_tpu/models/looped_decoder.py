"""A looped decoder: one stack of sandwich-normed Llama-family blocks applied
`passes` times a step on the same weights, a head and a loss after every
pass, and an exit gate whose distribution mixes the losses (Ouro, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741).

Beside `models/llama.py`, and built from its parts: `decoder_block` is the
block (the two post-norms are leaves it finds in the layer's tree),
`attend` / `attention_kind` the attention; the embedding, the checkpoint
wrapper with its policy and a token's cross-entropy are
`models/decoder_stack.py`'s.  What differs is here:

- **The loop.**  h = E[tokens]; `passes` times: h through the L blocks in
  turn, n = RMSNorm_f(h), logits = n W_out, and the next pass starts from n.
  The passes are one `lax.scan` around the layer scan, the layer stack its
  constant, so one pass is compiled and JAX's transpose sums the passes'
  weight gradients in the scan's carry, in the parameters' type.
- **A head unit a pass.**  Final norm, head, cross-entropy a token and the
  gate's logit are rematerialised as one unit that keeps nothing, so one
  pass's logits [B, S, V] are alive at a time, forward and backward; what
  leaves it is n and two float32 values a token.
- **The exit gate and the objective** (`exit_mix`).  z_t = n_t . w_g + b_g in
  float32, lambda_t = sigmoid(z_t) for every pass but the last; the survival
  S_t = S_{t-1} (1 - lambda_t), S_0 = 1; the exit distribution p_t = lambda_t
  S_{t-1}, and the last pass takes what is left, p_T = S_{T-1}.  The training
  loss is the mean over the positions that have a target of
  sum_t p_t l_t - beta H(p): the paper's first-stage objective, the expected
  loss under the exit distribution less an entropy term (a uniform prior).
  The scan computes a gate logit on the last pass too, for a body that is the
  same every pass; `exit_mix` does not read it.

Early exit at inference and the second-stage training of the gate on a frozen
model are not here (nothing in this repo serves such a model yet).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning_cfn_tpu.models import decoder_stack, llama
from deeplearning_cfn_tpu.models.decoder_stack import checkpointed, embed, token_nll
from deeplearning_cfn_tpu.models.llama import LlamaConfig, attend, attention_kind, decoder_block
from deeplearning_cfn_tpu.ops.attention import rms_norm

POST_NORMS = ("attn_post_norm", "mlp_post_norm")


@dataclass(frozen=True)
class LoopedDecoderConfig:
    """The decoder's sizes are a `LlamaConfig`'s (dense, untied, no pipeline);
    `passes` is the published `total_ut_steps`, `exit_beta` the entropy
    term's weight."""

    decoder: LlamaConfig
    passes: int = 4
    exit_beta: float = 0.05

    def __post_init__(self):
        dec = self.decoder
        if self.passes < 1:
            raise ValueError(f"passes={self.passes}: the stack runs at least once")
        if dec.moe is not None or dec.pp_stages > 1 or dec.tied_embeddings:
            raise ValueError("the looped stack is dense, untied and not pipelined")

    @classmethod
    def tiny(cls, passes: int = 4, exit_beta: float = 0.05, **kw) -> "LoopedDecoderConfig":
        """The structure at toy widths, for the CPU tests."""
        dec = replace(LlamaConfig.tiny(), tied_embeddings=False, **kw)
        return cls(dec, passes, exit_beta)


# --- parameters ---------------------------------------------------------


def init_params(cfg: LoopedDecoderConfig, rng: jax.Array) -> dict:
    """`llama.init_params`' tree with the two post-norms a layer (ones) and the
    gate: `exit_gate_w` [d] at the head's scale and `exit_gate_b` [] zero, both
    float32, so that a fresh gate puts about half of what is left on a pass."""
    dec = cfg.decoder
    k_model, k_gate = jax.random.split(rng)
    params = llama.init_params(dec, k_model)
    for name in POST_NORMS:
        params["layers"][name] = jnp.ones((dec.n_layers, dec.dim), jnp.float32)
    params["exit_gate_w"] = jax.random.normal(k_gate, (dec.dim,), jnp.float32) / np.sqrt(dec.dim)
    params["exit_gate_b"] = jnp.zeros((), jnp.float32)
    return params


def param_specs(cfg: LoopedDecoderConfig) -> dict:
    specs = llama.param_specs(cfg.decoder)
    for name in POST_NORMS:
        specs["layers"][name] = P(None, None)
    specs["exit_gate_w"] = P(None)
    specs["exit_gate_b"] = P()
    return specs


def param_shardings(cfg: LoopedDecoderConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def param_count(cfg: LoopedDecoderConfig) -> int:
    return decoder_stack.count(cfg, init_params)


def train_flops_per_token(cfg: LoopedDecoderConfig, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token costs: every pass, 6 per
    weight of the blocks, of the head and of the gate, and the causal half of
    the score products.  A weight counts once a pass: that is the model's
    work, not recomputation.  The table is a lookup."""
    dec = cfg.decoder
    block = (
        2 * dec.dim * dec.n_heads * dec.head_dim + 2 * dec.dim * dec.n_kv_heads * dec.head_dim
        + 3 * dec.dim * dec.mlp_dim
    )
    weights = dec.n_layers * block + dec.dim * dec.vocab_size + dec.dim
    scores = dec.n_layers * dec.n_heads * dec.head_dim * seq_len
    return cfg.passes * 6.0 * (weights + scores)


# --- forward ------------------------------------------------------------


def _pass_head(
    dec: LlamaConfig, top: dict, h: jax.Array, targets: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One pass's unit on the stack's output h [B, S, d]: (n, the final norm's
    output, which the head reads and the next pass starts from; the logits in
    the compute type; each token's cross-entropy [B, S] float32; the gate's
    logit [B, S] float32).  `top` holds the leaves outside the stack."""
    with jax.named_scope("final_norm"):
        n = rms_norm(h, top["final_norm"], dec.norm_eps)
    with jax.named_scope("head"):
        logits = n @ top["output"]
    with jax.named_scope("xent"):
        nll = token_nll(logits, targets)
    with jax.named_scope("exit_gate"):
        # float32 with a full-precision product, as a router's scores: one
        # output a token, and what the passes' losses are weighed by.
        z = jnp.matmul(
            n.astype(jnp.float32), top["exit_gate_w"], precision=jax.lax.Precision.HIGHEST
        ) + top["exit_gate_b"]
    return n, logits, nll, z


def _passes(
    cfg: LoopedDecoderConfig, params: dict, tokens: jax.Array, targets: jax.Array,
    mesh: Mesh | None, keep_logits: bool = False,
):
    """The loop: ((l_t, z_t) [passes, B, S] float32, and with `keep_logits`
    every pass's logits [passes, B, S, V], which only inspection asks for)."""
    dec = cfg.decoder
    S = tokens.shape[1]
    with jax.named_scope("embed"):
        x = embed(dec, params, tokens)
    positions = jnp.arange(S, dtype=jnp.int32)

    def own_batch(q, k, v):
        return attend(attention_kind(dec, mesh, S), q, k, v, mesh), None

    block = checkpointed(dec, partial(decoder_block, dec, own_batch))
    top = {k: v for k, v in params.items() if k not in ("embed", "layers")}

    def unit(top, h):
        n, logits, nll, z = _pass_head(dec, top, h, targets)
        return n, (nll, z, logits) if keep_logits else (nll, z)

    unit = checkpointed(dec, unit)

    def one_pass(h, _):
        with jax.named_scope("loop_pass"):
            h, _ = jax.lax.scan(
                lambda x, lp: (block(x, lp, positions)[0], None), h, params["layers"]
            )
        with jax.named_scope("loop_head"):
            return unit(top, h)

    return jax.lax.scan(one_pass, x, None, length=cfg.passes)[1]


def exit_mix(nll: jax.Array, z: jax.Array, beta: float) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The objective a token, from its losses and gate logits [T, ...] (the
    last logit unread): (sum_t p_t l_t - beta H(p), p [T, ...], H), float32.

    log p_t comes from sums of `log_sigmoid(+-z)` and never from the log of a
    product, so a gate that has saturated (lambda at 0 or 1 in float32) still
    has a finite entropy and a finite gradient."""
    z = z[:-1].astype(jnp.float32)
    first = jnp.zeros_like(nll[:1], jnp.float32)
    # log S_0 .. log S_{T-1}: what is left before each pass.
    log_left = jnp.concatenate([first, jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)])
    # Every pass but the last takes lambda_t of it, the last all of it.
    log_p = log_left + jnp.concatenate([jax.nn.log_sigmoid(z), first])
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.sum(p * nll, axis=0) - beta * entropy, p, entropy


def lm_loss(
    cfg: LoopedDecoderConfig, params: dict, tokens: jax.Array, targets: jax.Array,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """The mixed objective over the positions that have a target (a
    sequence's last holds a wrapped token and is left out).  `perplexity` is
    the last pass's; the counters are one observation a step."""
    nll, z = _passes(cfg, params, tokens, targets, mesh)
    with jax.named_scope("exit_mix"):
        mask = jnp.ones(targets.shape, jnp.float32).at[:, -1].set(0.0)
        mean = lambda a: jnp.sum(a * mask, axis=(-2, -1)) / jnp.sum(mask)
        mixed, p, entropy = exit_mix(nll, z, cfg.exit_beta)
        loss = mean(mixed)
        by_pass, mass = mean(nll), mean(p)
    counters = {"loop.passes": jnp.asarray(cfg.passes, jnp.float32), "loop.exit_entropy": mean(entropy)}
    for t in range(cfg.passes):
        counters[f"loop.loss.{t + 1}"] = by_pass[t]
        counters[f"loop.exit_mass.{t + 1}"] = mass[t]
    return loss, {"perplexity": jnp.exp(by_pass[-1]), "counters": counters}


def logits(
    cfg: LoopedDecoderConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> dict:
    """float32 logits of every pass [passes, B, S, V] and the gate's logits
    [passes, B, S]: the inspection entry point, not the train hot path."""
    _, z, every = _passes(cfg, params, tokens, jnp.zeros_like(tokens), mesh, keep_logits=True)
    return {"logits": every.astype(jnp.float32), "gate": z}


def make_trainer(cfg: LoopedDecoderConfig, mesh: Mesh, trainer_config) -> Any:
    """The generic SPMD Trainer on this model, as `llama.make_trainer`."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )
