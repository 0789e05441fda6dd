"""Llama-3-family decoder — the framework's flagship large-model config.

No reference analog exists (SURVEY §2.3: the reference is DP-only and
vision-only); BASELINE.json names "Llama-3 8B FSDP via pjit on a v5p slice"
as a first-class target, so this model is built TPU-first from scratch:

- **Functional, not Module-boxed**: parameters are a plain pytree with a
  parallel tree of PartitionSpecs (``param_specs``).  Sharding is data, so
  the same model runs replicated, FSDP, FSDP x TP, or with sequence
  sharding by swapping the spec tree — the pjit/GSPMD idiom.
- **Scan over layers**: one stacked parameter per weight kind ([L, ...]),
  ``lax.scan`` over the layer axis — one compiled block regardless of
  depth, which keeps compile time and HBM for the 8B config sane.
- **Remat per layer** (``jax.checkpoint``) trades recompute for activation
  memory, the standard TPU recipe for fitting long sequences.
- **GQA + RoPE + RMSNorm + SwiGLU**, bf16 compute with f32 softmax/norms.
- Sequence axis annotated with ``sp`` sharding constraints so long-context
  runs shard activations over the sequence axis; attention then induces
  XLA all-gathers of K/V over ``sp`` (all-to-all context parallelism), and
  the opt-in ring-attention path (parallel/ring_attention.py) replaces that
  with a ppermute ring for the very long regime.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning_cfn_tpu.models import decoder_stack
from deeplearning_cfn_tpu.models.decoder_stack import embed, head, remat_keeps, token_nll
from deeplearning_cfn_tpu.ops.attention import (
    dot_product_attention,
    rms_norm,
    rotary_embedding,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full": recompute the whole block in backward but the flash kernel's
    # `out` and `lse` (remat_keeps): the lowest memory there is, which is
    # layers x [B, S, H, D] in the compute dtype above the residual stream.
    # "dots": save matmul outputs, recompute only elementwise
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) — the
    # standard transformer policy; measured +3% step throughput on the
    # 435M bench shape for a modest activation-memory increase.
    remat_policy: str = "full"
    # Tie input/output embeddings (small configs); 8B does not tie.
    tied_embeddings: bool = False
    # Sequence-parallel ring attention (parallel/ring_attention.py) instead
    # of dense attention: required when S/sp blocks are the only thing that
    # fits; needs a mesh passed to forward().
    use_ring_attention: bool = False
    # Pallas flash-attention kernel (ops/pallas_attention.py) instead of XLA
    # attention: blockwise online softmax, never materializes [S, S] in HBM.
    use_flash_attention: bool = False
    # Mixture-of-experts MLP (ops/moe.py): n_experts > 0 replaces the dense
    # SwiGLU with a top-k routed expert bank sharded over the ``ep`` mesh
    # axis.  0 = dense model.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Pipeline parallelism (parallel/pipeline.py): pp_stages > 1 splits the
    # decoder stack into stages sharded over the ``pp`` mesh axis and runs a
    # GPipe microbatch schedule.  n_layers must divide evenly; ring
    # attention (manual sp collectives) cannot nest inside the pipeline's
    # shard_map region — dense/flash attention applies instead.
    pp_stages: int = 1
    # Microbatches per step when pipelining; 0 = pp_stages (minimum).  More
    # microbatches shrink the (pp-1)/(M+pp-1) bubble at the cost of smaller
    # per-tick matmuls.
    pp_microbatches: int = 0

    def __post_init__(self):
        if self.n_experts > 0 and not (1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, n_experts="
                f"{self.n_experts}]"
            )
        if self.pp_stages > 1:
            if self.n_layers % self.pp_stages:
                raise ValueError(
                    f"n_layers={self.n_layers} not divisible by "
                    f"pp_stages={self.pp_stages}"
                )
            if self.use_ring_attention:
                raise ValueError(
                    "ring attention (manual sp collectives) cannot nest "
                    "inside the pipeline shard_map region; use dense or "
                    "flash attention with pp_stages > 1"
                )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()  # defaults above are the 8B shape

    @classmethod
    def m435(cls, seq_len: int = 1024) -> "LlamaConfig":
        """The ~435M single-chip benchmark shape (docs/BENCH_NOTES.md:
        30k tok/s at 42% analytic MFU on one v5e) — big enough to fill
        the MXU, small enough for one 16 GB chip with adamw.

        head_dim 128 (8 heads), the real-Llama convention: the round-3
        trace showed head_dim 64 feeding the 128-wide MXU half-empty in
        every attention matmul — same FLOPs, measured 0.32 -> 0.41 MFU
        from this change alone."""
        return cls(
            vocab_size=32000,
            dim=1024,
            n_layers=24,
            n_heads=8,
            n_kv_heads=8,
            mlp_dim=4096,
            max_seq_len=seq_len,
            tied_embeddings=True,
            use_flash_attention=True,
            # Fits comfortably at the bench shape; +3% over full remat.
            remat_policy="dots",
        )

    @classmethod
    def b1(cls, seq_len: int = 1024) -> "LlamaConfig":
        """~1.1B — the largest config the 16 GiB v5e trains with adamw
        (the round-3 verdict's 'largest-real-model' demand: the 435M
        bench left the HBM-limit machinery analytic-only).  Full remat
        (dots-saveable OOMs at this scale), bf16 adam moments (optax
        default: moments follow param dtype), flash attention, tied
        embeddings.  Predicted-vs-measured HBM for this config is the
        memory model's hardware validation row (docs/MEMORY_8B.md)."""
        return cls(
            vocab_size=32000,
            dim=2048,
            n_layers=20,
            n_heads=16,
            n_kv_heads=16,
            mlp_dim=5632,
            max_seq_len=seq_len,
            tied_embeddings=True,
            use_flash_attention=True,
            remat_policy="full",
        )

    @classmethod
    def b3(cls, seq_len: int = 1024) -> "LlamaConfig":
        """~2.9B — the adafactor rung of the on-hardware ladder.  adamw
        cannot hold this on a 16 GiB chip (params+grads+bf16 moments =
        ~23.5 GB); with adafactor's factored state the per-param charge
        drops to params+grads (~11.8 GB), leaving room for full-remat
        activations at batch 4 x 1024 (llama_memory predicts ~13.2
        GiB/chip).  Same conventions as b1: head_dim 128, flash
        attention, tied embeddings, full remat."""
        return cls(
            vocab_size=32000,
            dim=2560,
            n_layers=36,
            n_heads=20,
            n_kv_heads=20,
            mlp_dim=6912,
            max_seq_len=seq_len,
            tied_embeddings=True,
            use_flash_attention=True,
            remat_policy="full",
        )

    @classmethod
    def tiny(cls, vocab_size: int = 256, seq_len: int = 128, **kw) -> "LlamaConfig":
        return cls(
            vocab_size=vocab_size,
            dim=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            mlp_dim=128,
            max_seq_len=seq_len,
            remat=False,
            tied_embeddings=True,
            **kw,
        )

    @classmethod
    def tiny_moe(cls, n_experts: int = 4, **kw) -> "LlamaConfig":
        return cls.tiny(n_experts=n_experts, **kw)

    @property
    def moe(self) -> "MoEConfig | None":
        if self.n_experts <= 0:
            return None
        from deeplearning_cfn_tpu.ops.moe import MoEConfig

        return MoEConfig(
            n_experts=self.n_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_loss_weight=self.moe_aux_weight,
        )


# --- parameters ---------------------------------------------------------

def init_params(cfg: LlamaConfig, rng: jax.Array) -> dict:
    """Stacked-layer parameter pytree.  Weight layout chosen for the MXU:
    every matmul is [in, out] so the forward is x @ W with no transposes."""
    keys = jax.random.split(rng, 10)
    d, hd = cfg.dim, cfg.head_dim
    L = cfg.n_layers

    dense_init = partial(decoder_stack.dense_init, dtype=cfg.dtype)

    layers: dict = {
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "wq": dense_init(keys[1], (L, d, cfg.n_heads * hd), d),
        "wk": dense_init(keys[2], (L, d, cfg.n_kv_heads * hd), d),
        "wv": dense_init(keys[3], (L, d, cfg.n_kv_heads * hd), d),
        "wo": dense_init(keys[4], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
        "mlp_norm": jnp.ones((L, d), jnp.float32),
    }
    if cfg.moe is not None:
        from deeplearning_cfn_tpu.ops.moe import init_moe_params

        moe_keys = jax.random.split(keys[5], L)
        stacked = [
            init_moe_params(cfg.moe, mk, d, cfg.mlp_dim, cfg.dtype) for mk in moe_keys
        ]
        layers["moe"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *stacked
        )
    else:
        layers["w_gate"] = dense_init(keys[5], (L, d, cfg.mlp_dim), d)
        layers["w_up"] = dense_init(keys[6], (L, d, cfg.mlp_dim), d)
        layers["w_down"] = dense_init(keys[7], (L, cfg.mlp_dim, d), cfg.mlp_dim)
    params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tied_embeddings:
        params["output"] = dense_init(keys[8], (d, cfg.vocab_size), d)
    if cfg.pp_stages > 1:
        from deeplearning_cfn_tpu.parallel.pipeline import stack_stages

        # [L, ...] -> [pp, L/pp, ...]: the leading stage axis shards over pp.
        params["layers"] = stack_stages(params["layers"], cfg.pp_stages)
    return params


def param_specs(cfg: LlamaConfig) -> dict:
    """PartitionSpec tree: FSDP shards the embed/hidden axis, TP shards
    heads/mlp/vocab — the standard 2D layout.  Layer axis (from scan
    stacking) is never sharded."""
    layers: dict = {
        "attn_norm": P(None, None),
        "wq": P(None, "fsdp", "tp"),
        "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),
        "mlp_norm": P(None, None),
    }
    if cfg.moe is not None:
        from deeplearning_cfn_tpu.ops.moe import moe_param_specs

        # Prepend the stacked-layer axis to each per-expert spec.
        layers["moe"] = jax.tree_util.tree_map(
            lambda s: P(None, *s),
            moe_param_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        layers["w_gate"] = P(None, "fsdp", "tp")
        layers["w_up"] = P(None, "fsdp", "tp")
        layers["w_down"] = P(None, "tp", "fsdp")
    if cfg.pp_stages > 1:
        from deeplearning_cfn_tpu.parallel.pipeline import stage_specs

        layers = stage_specs(layers)
    specs = {
        "embed": P("tp", "fsdp"),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tied_embeddings:
        specs["output"] = P("fsdp", "tp")
    return specs


def param_shardings(cfg: LlamaConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def active_param_count(cfg: LlamaConfig) -> int:
    """Parameters a token actually flows through: for MoE configs the
    expert MLP banks count at top_k/n_experts (a token routes through
    top_k experts), router and everything else fully."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    # Expert weights: [L, E, ...] stacks of w_gate/w_up/w_down.
    expert = 3 * cfg.n_layers * cfg.n_experts * cfg.dim * cfg.mlp_dim
    active_expert = expert * cfg.moe_top_k // cfg.n_experts
    return total - expert + active_expert


def train_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Analytic fwd+bwd FLOPs per trained token: the standard 6N weight
    term (N = ACTIVE params — MoE experts count at top_k/n_experts) plus
    the causal attention term (12·L·dim·S halved by the causal mask).
    The honest MFU numerator for flash-attention runs —
    ``compiled.cost_analysis()`` cannot see inside Pallas custom calls
    (docs/BENCH_NOTES.md), so XLA-reported flops under-count exactly the
    op this model routes through Pallas."""
    return 6.0 * active_param_count(cfg) + 6.0 * cfg.n_layers * cfg.dim * seq_len


def param_count(cfg: LlamaConfig) -> int:
    return decoder_stack.count(cfg, init_params)


# --- forward ------------------------------------------------------------


def attention_kind(
    cfg: LlamaConfig, mesh: Mesh | None, seq_len: int, backend: str | None = None
) -> str:
    """Which attention implementation a block will use: ``ring`` (sp > 1),
    ``flash`` (Pallas kernel, TPU at/above the measured crossover), or
    ``xla`` (fused XLA attention — also the fastest choice below the
    crossover and the correctness path off-TPU)."""
    if cfg.use_ring_attention and mesh is not None and mesh.shape.get("sp", 1) > 1:
        return "ring"
    backend = backend or jax.default_backend()
    if cfg.use_flash_attention and backend == "tpu":
        from deeplearning_cfn_tpu.ops.pallas_attention import FLASH_CROSSOVER_SEQ

        if seq_len >= FLASH_CROSSOVER_SEQ:
            return "flash"
    return "xla"


def attend(
    kind: str, q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh | None,
    window: int | None = None,
) -> jax.Array:
    """Causal attention by the implementation `attention_kind` named, on
    [B, S, H, D] tensors; the scores are scaled by D ** -0.5.  Shared by the
    decoder blocks (this module's, models/mla_moe.py's and the two
    pattern-as-data decoders').  ``window`` W: query t sees the keys
    ``t - W < j <= t``, W keys with its own (the one convention, stated in
    ops/pallas_attention.py); ``ring`` refuses one."""
    if kind == "ring":
        from deeplearning_cfn_tpu.parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, mesh, causal=True, window=window)
    if kind == "flash":
        from deeplearning_cfn_tpu.ops.pallas_attention import flash_attention

        # attention_kind only answers "flash" on a tpu backend, so
        # this is always the compiled Mosaic kernel.
        return flash_attention(q, k, v, causal=True, mesh=mesh, interpret=False, window=window)
    # "xla" covers use_flash_attention off-TPU (the Pallas kernel
    # needs Mosaic) AND below-crossover sequences where XLA's
    # fused attention measures faster than the Pallas kernel
    # (docs/BENCH_NOTES.md): use_flash means "fastest memory-safe
    # attention", not "always Pallas".
    return dot_product_attention(q, k, v, causal=True, window=window)


def swiglu(h: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """The gated feed-forward: silu in float32, the products in h's type."""
    gate = jax.nn.silu((h @ w_gate).astype(jnp.float32)).astype(h.dtype)
    return (gate * (h @ w_up)) @ w_down


def decoder_block(
    cfg: LlamaConfig,
    kv_context: Callable[[jax.Array, jax.Array, jax.Array], tuple[jax.Array, Any]],
    x: jax.Array,
    lp: dict,
    positions: jax.Array,
) -> tuple[jax.Array, jax.Array, Any]:
    """One decoder block of the Llama family, the only one: the trainer
    (`forward_with_aux`), the cached decoder (models/llama_decode.py) and
    the serving engine (serve/engine.py) all run this function.

    They differ in where keys and values live, so that is the argument.
    ``kv_context(q, k, v)`` gets the rotated q [B, S, H, D] and this
    call's k, v [B, S, Hkv, D] and returns (the attention output
    [B, S, H, D], what its owner carries on to the next call: nothing, the
    written cache buffers, the fresh k and v).

    A sandwich-normed block is the same block with more leaves: where the
    layer holds ``attn_post_norm`` the mixer's output goes through an RMSNorm
    of that weight before it joins the residual stream, and where it holds
    ``mlp_post_norm`` the dense feed-forward's does (models/looped_decoder.py
    holds both).  The leaves are the switch: a tree without them traces as
    it did before they existed.

    Returns (x, aux, carried): aux is the MoE load-balancing loss, 0 for
    dense models; carried is the context's second result, untouched.
    """
    B, S, d = x.shape
    hd = cfg.head_dim
    # The named scopes are metadata for a profile's op names
    # (attn_norm / attn{qkv,rope,core,out} / attn_post_norm / mlp_norm /
    # mlp / mlp_post_norm): the computation is the same with and without them.
    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            q = (h @ lp["wq"]).reshape(B, S, cfg.n_heads, hd)
            k = (h @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
            v = (h @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
        with jax.named_scope("rope"):
            q = rotary_embedding(q, positions, cfg.rope_theta)
            k = rotary_embedding(k, positions, cfg.rope_theta)
        with jax.named_scope("core"):
            attn, carried = kv_context(q, k, v)
        with jax.named_scope("out"):
            y = attn.reshape(B, S, cfg.n_heads * hd) @ lp["wo"]
            if "attn_post_norm" not in lp:
                x = x + y
    if "attn_post_norm" in lp:
        with jax.named_scope("attn_post_norm"):
            x = x + rms_norm(y, lp["attn_post_norm"], cfg.norm_eps)
    with jax.named_scope("mlp_norm"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if cfg.moe is not None:
            from deeplearning_cfn_tpu.ops.moe import moe_mlp

            y, aux = moe_mlp(cfg.moe, lp["moe"], h)
            return x + y, aux, carried
        y = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        if "mlp_post_norm" not in lp:
            x = x + y
    if "mlp_post_norm" in lp:
        with jax.named_scope("mlp_post_norm"):
            x = x + rms_norm(y, lp["mlp_post_norm"], cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32), carried


def head_logits(cfg: LlamaConfig, params: dict, x: jax.Array) -> jax.Array:
    """The final norm and the head on x [..., d]: logits in the COMPUTE
    dtype.  Materializing the [B, S, V] f32 copy here cost ~1 GB of HBM
    writes per pass at the 435M bench shape and dominated the out-of-scan
    step time (round-3 trace, docs/BENCH_NOTES.md).  Consumers that reduce
    over the vocab convert inside their reductions (exact: bf16 -> f32 is
    lossless), so loss numerics are identical to an f32 materialization;
    the decode and serve programs cast what they sample from."""
    output = params["embed"].astype(cfg.dtype).T if cfg.tied_embeddings else params["output"]
    return head(cfg, params["final_norm"], output, x)


def forward_with_aux(
    cfg: LlamaConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (logits [B, S, V] in the compute dtype,
    aux_loss scalar).

    aux_loss is the summed MoE load-balancing loss over layers (0 for dense
    configs) — added to the training objective, excluded from perplexity.
    """
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    positions = jnp.arange(S, dtype=jnp.int32)

    def own_batch(q, k, v):
        # The training context: keys and values are the batch's own, and
        # nothing is carried from one call to the next.
        return attend(attention_kind(cfg, mesh, S), q, k, v, mesh), None

    block = partial(decoder_block, cfg, own_batch)
    if cfg.remat:
        dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        policy = remat_keeps(dots if cfg.remat_policy == "dots" else None)
        block = jax.checkpoint(block, static_argnums=(), policy=policy)

    def scan_body(carry, lp):
        x, aux_sum = carry
        x, aux, _ = block(x, lp, positions)
        return (x, aux_sum + aux), None

    if cfg.pp_stages > 1 and mesh is not None and mesh.shape.get("pp", 1) > 1:
        from deeplearning_cfn_tpu.parallel.pipeline import pipeline_apply

        def stage_fn(stage_layers, act):
            # One stage's L/pp layers, scanned exactly like the full stack.
            (act, aux), _ = jax.lax.scan(
                scan_body, (act, jnp.zeros((), jnp.float32)), stage_layers
            )
            return act, aux

        x, aux_sum = pipeline_apply(
            stage_fn,
            params["layers"],
            x,
            mesh,
            n_microbatches=cfg.pp_microbatches or cfg.pp_stages,
        )
    else:
        layer_tree = params["layers"]
        if cfg.pp_stages > 1:
            # Stage-stacked params but no pp mesh axis (single-device runs):
            # fold [pp, L/pp, ...] back to [L, ...] and scan sequentially.
            from deeplearning_cfn_tpu.parallel.pipeline import unstack_stages

            layer_tree = unstack_stages(layer_tree)
        (x, aux_sum), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), layer_tree
        )
    return head_logits(cfg, params, x), aux_sum


def forward(
    cfg: LlamaConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> jax.Array:
    """f32 logits — the inspection/eval entry point, not the train hot
    path (the loss consumes compute-dtype logits directly)."""
    return forward_with_aux(cfg, params, tokens, mesh)[0].astype(jnp.float32)


def make_trainer(cfg: LlamaConfig, mesh: Mesh, trainer_config) -> Any:
    """Wire a Llama config into the generic SPMD Trainer: explicit 2D
    param shardings, token batch sharded over (dp/fsdp, sp), causal-LM loss."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=causal_lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )


def causal_lm_loss(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,
    targets: jax.Array,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Mean next-token cross-entropy; last position excluded (its rolled
    target wraps to the sequence start).  MoE configs add the router
    load-balancing aux loss to the objective (not to perplexity)."""
    logits, aux = forward_with_aux(cfg, params, tokens, mesh)
    with jax.named_scope("xent"):
        nll = token_nll(logits, targets)
        mask = jnp.ones_like(nll).at[:, -1].set(0.0)
        loss = jnp.sum(nll * mask) / jnp.sum(mask)
    metrics = {"perplexity": jnp.exp(loss)}
    if cfg.moe is not None:
        metrics["moe_aux_loss"] = aux
    return loss + aux, metrics
