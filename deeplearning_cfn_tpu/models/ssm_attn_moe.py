"""A decoder whose blocks are half layers: each a pre-norm residual around
*one* part, a Mamba-2 mixer, grouped-query attention or routed latent experts,
by a pattern string.  The stack of Nemotron-H (`nemotron_h`).

On `models/decoder_stack.py`'s run machinery (`init_runs`, `run_specs`,
`scan_runs`), and built from the shared parts where the part is the same
(`llama.attention_kind`, `attend`, `ops/conv.short_conv`,
`ops/moe.routed_experts`, `decoder_stack`'s embedding, head with its
rematerialised loss and routing counters).  What differs:

- **The pattern is a string** over ``M`` (Mamba-2), ``*`` (attention) and
  ``E`` (experts), `hybrid_override_pattern`.  A run of single blocks would
  unroll ``EMEMEMEMEM*`` into eleven bodies, so the repeated *unit* is found
  first (`units_of`): a pair such as ``EM`` five times is one run, one stack
  of weights and one `scan` whose body is the pair's two blocks, each
  rematerialised by itself.
- **Mamba-2** (arXiv:2405.21060).  ``[z | xBC | dt] = n W_in``; ``xBC`` goes
  through a depthwise causal convolution of `conv_taps` taps with a bias and a
  SiLU, and parts into x [H, P], B and C [G, N] (a head reads its group's);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the scan is
  `ops/ssd.ssd`, or `ops/pallas_ssd.ssd` where `pallas_ssd.takes_kernel` says
  so (a TPU, lane-aligned chunk, state and groups);
  ``y = RMSNorm_group(y * silu(z)) * w`` over each group's channels (the gate
  first), then ``W_out``.  The convolution with its SiLU and the gated norm
  are `_conv_silu` and `_gate_norm`, or the fused kernels of
  `ops/pallas_ssm_stages.py` where its rules say so (a TPU, channels and a
  group whole lane tiles, whole row tiles).
- **Attention** is causal GQA with no positional encoding and no bias.
- **Latent experts.**  The router reads the normalised hidden state at full
  width; the experts read and write a latent of it (``latent_in`` d -> l, the
  held experts' ``w_down relu(w_up .)^2`` through `ops/moe.routed_experts` with
  rows of their own, ``latent_out`` l -> d); a shared relu^2 expert reads the
  full width beside them.

Parameters: ``embed``, ``output``, ``final_norm`` and ``runs``, a list with one
list a run: the unit's blocks in order, each a dict of weights stacked on the
run's axis.  Scopes: ``ssm_norm``, ``ssm/{in_proj,conv,scan,gate_norm,out_proj}``;
``attn_norm``, ``attn/{qkv,core,out}``; ``moe_norm``,
``moe/{router,latent_in,dispatch,experts,combine,latent_out,shared}``.
"""

from __future__ import annotations

import math
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
    scan_runs,
)
from deeplearning_cfn_tpu.models.llama import attend, attention_kind
from deeplearning_cfn_tpu.ops import pallas_ssd, pallas_ssm_stages
from deeplearning_cfn_tpu.ops.attention import rms_norm
from deeplearning_cfn_tpu.ops.conv import short_conv
from deeplearning_cfn_tpu.ops.moe import (
    RoutedConfig,
    init_routed_params,
    routed_experts,
    routed_param_specs,
)
from deeplearning_cfn_tpu.ops.ssd import ssd

BLOCKS = ("M", "*", "E")
# Nemotron-3-Super's `hybrid_override_pattern`, 88 blocks.
PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*" + "EMEMEMEMEM*" * 4 + "EMEMEMEM*EMEMEMEME"
)


def units_of(pattern: str) -> tuple[tuple[str, int], ...]:
    """The pattern as runs of a repeated unit, ((unit, how many), ...): at
    each place the unit of one or two blocks whose repetitions reach furthest,
    the single block where both reach as far (``EMEMEMEMEM*`` is ``EM`` five
    times, then ``*`` once; ``M*E`` is the pair ``M*`` once, then ``E``)."""
    runs, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for size in (1, 2):
            unit, n = pattern[i : i + size], 1
            while pattern.startswith(unit, i + n * size):
                n += 1
            if len(unit) == size and n * size > len(best[0]) * best[1]:
                best = (unit, n)
        runs.append(best)
        i += len(best[0]) * best[1]
    return tuple(runs)


@dataclass(frozen=True)
class SsmAttnMoeConfig:
    """Sizes under the names of the published `config.json` keys' meaning.
    The defaults are Nemotron-3-Super's widths and one period of its pattern
    (published blocks 26-36 of `PUBLISHED_PATTERN`'s 88)."""

    vocab_size: int = 131072
    dim: int = 4096
    pattern: str = "EMEMEMEMEM*"
    ssm_heads: int = 128  # mamba_num_heads
    ssm_head_dim: int = 64  # mamba_head_dim
    ssm_groups: int = 8  # n_groups
    ssm_state: int = 128  # ssm_state_size
    conv_taps: int = 4  # conv_kernel
    chunk: int = 128  # chunk_size
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    latent_dim: int = 1024  # moe_latent_size
    expert_dim: int = 2688  # moe_intermediate_size
    shared_expert_dim: int = 5376  # moe_shared_expert_intermediate_size
    n_experts: int = 512
    held_experts: tuple[int, int] | None = None  # (first, count); None: all
    top_k: int = 22
    routed_scaling_factor: float = 5.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    use_ring_attention: bool = False  # `attention_kind` asks; not built here

    def __post_init__(self):
        unknown = sorted(set(self.pattern) - set(BLOCKS))
        if not self.pattern or unknown:
            raise ValueError(f"pattern holds {unknown or 'nothing'}; a block is one of {BLOCKS}")
        if self.ssm_heads % self.ssm_groups or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.ssm_heads} state-space heads over {self.ssm_groups} groups, "
                f"{self.n_heads} query heads over {self.n_kv_heads} key/value heads"
            )

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """x, B and C side by side: what the convolution runs over."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def runs(self) -> tuple[tuple[str, int], ...]:
        return units_of(self.pattern)

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
            expert="relu2",
        )

    @classmethod
    def tiny(cls, **kw) -> "SsmAttnMoeConfig":
        """The structure at toy widths, for the CPU tests: two pairs and an
        attention block, groups of two state-space heads, chunks of 8."""
        base = dict(
            vocab_size=128, dim=32, pattern="EMEM*", ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
            ssm_state=8, chunk=8, n_heads=4, n_kv_heads=2, head_dim=8, latent_dim=16,
            expert_dim=24, shared_expert_dim=40, n_experts=8, held_experts=(0, 4), top_k=3,
            remat=False, dtype=jnp.float32,
        )
        return cls(**{**base, **kw})


# --- parameters ---------------------------------------------------------


def _block_params(cfg: SsmAttnMoeConfig, key: jax.Array, block: str) -> dict:
    keys = jax.random.split(key, 6)
    d = cfg.dim
    init = partial(dense_init, dtype=cfg.dtype)
    params = {"norm": jnp.ones((d,), jnp.float32)}
    if block == "M":
        H, inner, conv = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
        # dt log-uniform in [1e-3, 1e-1] through the softplus' inverse, A in
        # [1, 16]: Mamba-2's own initialisation.
        dt = jnp.exp(jax.random.uniform(keys[2], (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        params.update(
            in_proj=init(keys[0], (d, inner + conv + H), d),
            conv_w=init(keys[1], (cfg.conv_taps, conv), cfg.conv_taps),
            conv_bias=jnp.zeros((conv,), jnp.float32),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(jax.random.uniform(keys[3], (H,), minval=1.0, maxval=16.0)),
            D=jnp.ones((H,), jnp.float32),
            gate_norm=jnp.ones((inner,), jnp.float32),
            out_proj=init(keys[4], (inner, d), inner),
        )
    elif block == "*":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        params.update(
            wq=init(keys[0], (d, q), d), wk=init(keys[1], (d, kv), d),
            wv=init(keys[2], (d, kv), d), wo=init(keys[3], (q, d), q),
        )
    else:
        params.update(
            moe=init_routed_params(
                cfg.routed, keys[0], d, cfg.expert_dim, cfg.dtype, rows_dim=cfg.latent_dim
            ),
            latent_in=init(keys[1], (d, cfg.latent_dim), d),
            latent_out=init(keys[2], (cfg.latent_dim, d), cfg.latent_dim),
            shared_up=init(keys[3], (d, cfg.shared_expert_dim), d),
            shared_down=init(keys[4], (cfg.shared_expert_dim, d), cfg.shared_expert_dim),
        )
    return params


def _unit_params(cfg: SsmAttnMoeConfig, key: jax.Array, unit: str) -> list[dict]:
    return [_block_params(cfg, k, b) for k, b in zip(jax.random.split(key, len(unit)), unit)]


def init_params(cfg: SsmAttnMoeConfig, rng: jax.Array) -> dict:
    k_embed, k_output, k_runs = jax.random.split(rng, 3)
    return {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, cfg.dtype),
        "output": dense_init(k_output, (cfg.dim, cfg.vocab_size), cfg.dim, cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "runs": init_runs(partial(_unit_params, cfg), cfg.runs, k_runs),
    }


def _block_specs(cfg: SsmAttnMoeConfig, block: str) -> dict:
    specs = {"norm": P(None)}
    if block == "M":
        specs.update(
            in_proj=P("fsdp", "tp"), conv_w=P(None, "tp"), conv_bias=P("tp"), dt_bias=P(None),
            A_log=P(None), D=P(None), gate_norm=P("tp"), out_proj=P("tp", "fsdp"),
        )
    elif block == "*":
        specs.update(wq=P("fsdp", "tp"), wk=P("fsdp", "tp"), wv=P("fsdp", "tp"), wo=P("tp", "fsdp"))
    else:
        specs.update(
            moe=routed_param_specs(cfg.routed),
            latent_in=P("fsdp", "tp"), latent_out=P("tp", "fsdp"),
            shared_up=P("fsdp", "tp"), shared_down=P("tp", "fsdp"),
        )
    return specs


def param_specs(cfg: SsmAttnMoeConfig) -> dict:
    """fsdp on a matrix's input axis, tp on its output axis, as llama.py."""
    return {
        "embed": P("tp", "fsdp"),
        "output": P("fsdp", "tp"),
        "final_norm": P(None),
        "runs": run_specs(lambda unit: [_block_specs(cfg, b) for b in unit], cfg.runs),
    }


def param_shardings(cfg: SsmAttnMoeConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def param_count(cfg: SsmAttnMoeConfig) -> int:
    return decoder_stack.count(cfg, init_params)


def train_flops_per_token(cfg: SsmAttnMoeConfig, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token costs: 6 per weight it
    passes through (an expert held here at its expectation, `top_k` times the
    held share), the recurrence's 4 N P a head forward and twice that backward
    (the fewest any form of the scan does), and the causal half of the score
    products in the attention blocks."""
    d = cfg.dim
    routed = cfg.routed
    held = routed.top_k * routed.span[1] / routed.n_routed
    weights = {
        "M": d * (cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads)
        + cfg.conv_taps * cfg.ssm_conv_dim + cfg.ssm_inner * d,
        "*": 2 * d * cfg.n_heads * cfg.head_dim + 2 * d * cfg.n_kv_heads * cfg.head_dim,
        "E": d * cfg.n_experts + 2 * d * cfg.latent_dim + 2 * d * cfg.shared_expert_dim
        + 2 * cfg.latent_dim * cfg.expert_dim * held,
    }
    scan = 3 * 4 * cfg.ssm_state * cfg.ssm_head_dim * cfg.ssm_heads
    scores = 3 * seq_len * cfg.n_heads * 2 * cfg.head_dim
    return (
        6.0 * (d * cfg.vocab_size + sum(weights[b] for b in cfg.pattern))
        + scan * cfg.pattern.count("M") + scores * cfg.pattern.count("*")
    )


# --- forward ------------------------------------------------------------


def _conv_silu(xBC: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """silu(depthwise causal conv(xBC) + bias), the taps in float32."""
    f32 = jnp.float32
    return jax.nn.silu(short_conv(xBC.astype(f32), w.astype(f32)) + bias).astype(xBC.dtype)


def _gate_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int, eps: float) -> jax.Array:
    """RMSNorm_group(y * silu(z)) * w on [B, S, inner]: the gate first, then
    each group's channels by their own mean square, all in float32."""
    B, S, inner = y.shape
    f32 = jnp.float32
    gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(B, S, groups, inner // groups)
    gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return (gated.reshape(B, S, inner) * w).astype(y.dtype)


def _ssm_mixer(cfg: SsmAttnMoeConfig, lp: dict, n: jax.Array) -> jax.Array:
    """Mamba-2 on the normalised input n [B, S, d].  Between the passes each of
    the two elementwise stages holds its inputs in the activations' type and
    not its float32 intermediates (0.3 GB each a sequence of 8192 at these
    widths, several of each): as a fused kernel of `ops/pallas_ssm_stages.py`
    where its rule takes the shapes and the backend, else as the jnp stage
    rematerialised by itself."""
    B, S, _ = n.shape
    H, G, N = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    with jax.named_scope("in_proj"):
        z, xBC, dt = jnp.split(
            n @ lp["in_proj"], (cfg.ssm_inner, cfg.ssm_inner + cfg.ssm_conv_dim), axis=-1
        )
    with jax.named_scope("conv"):
        kernel = pallas_ssm_stages.takes_conv_kernel(xBC, lp["conv_w"])
        conv = pallas_ssm_stages.conv_silu if kernel else jax.checkpoint(_conv_silu)
        xBC = conv(xBC, lp["conv_w"], lp["conv_bias"])
    with jax.named_scope("scan"):
        x, Bm, Cm = jnp.split(xBC, (cfg.ssm_inner, cfg.ssm_inner + G * N), axis=-1)
        x, Bm, Cm = x.reshape(B, S, H, cfg.ssm_head_dim), Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
        # The shapes and the backend choose: the fused kernels, or the XLA form.
        scan = pallas_ssd.ssd if pallas_ssd.takes_kernel(x, Bm, cfg.chunk) else ssd
        y = scan(
            x, jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"]), -jnp.exp(lp["A_log"]),
            Bm, Cm, lp["D"], cfg.chunk,
        )
    with jax.named_scope("gate_norm"):
        y = y.reshape(B, S, cfg.ssm_inner)
        if pallas_ssm_stages.takes_gate_norm_kernel(y, z, G):
            y = pallas_ssm_stages.gate_norm(y, z, lp["gate_norm"], G, cfg.norm_eps)
        else:
            y = jax.checkpoint(partial(_gate_norm, groups=G, eps=cfg.norm_eps))(y, z, lp["gate_norm"])
    with jax.named_scope("out_proj"):
        return y @ lp["out_proj"]


def _attention_mixer(cfg: SsmAttnMoeConfig, mesh: Mesh | None, lp: dict, n: jax.Array) -> jax.Array:
    """Causal GQA without positions on the normalised input n [B, S, d]."""
    B, S, _ = n.shape
    with jax.named_scope("qkv"):
        q = (n @ lp["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
        k = (n @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        v = (n @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    with jax.named_scope("core"):
        attn = attend(attention_kind(cfg, mesh, S), q, k, v, mesh)
    with jax.named_scope("out"):
        return attn.reshape(B, S, cfg.n_heads * cfg.head_dim) @ lp["wo"]


def _relu2(h: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    up = h @ w_up
    return jnp.square(jnp.maximum(up.astype(jnp.float32), 0)).astype(h.dtype) @ w_down


def _latent_experts(cfg: SsmAttnMoeConfig, lp: dict, n: jax.Array) -> tuple[jax.Array, dict]:
    """The routed experts in their latent and the shared one beside them, on
    the normalised input n [B, S, d]: (the part's result, the routing's
    statistics)."""
    with jax.named_scope("latent_in"):
        latent = n @ lp["latent_in"]
    routed, stats = routed_experts(cfg.routed, lp["moe"], n, expert_rows=latent)
    with jax.named_scope("latent_out"):
        y = routed @ lp["latent_out"]
    with jax.named_scope("shared"):
        return y + _relu2(n, lp["shared_up"], lp["shared_down"]), stats


def _block(
    cfg: SsmAttnMoeConfig, mesh: Mesh | None, block: str, x: jax.Array, lp: dict
) -> tuple[jax.Array, dict | None]:
    """One block of `block`'s kind: x + part(RMSNorm(x)), and the routing's
    statistics where the part is the experts."""
    scope = {"M": "ssm", "*": "attn", "E": "moe"}[block]
    with jax.named_scope(scope + "_norm"):
        n = rms_norm(x, lp["norm"], cfg.norm_eps)
    with jax.named_scope(scope):
        if block == "M":
            return x + _ssm_mixer(cfg, lp, n), None
        if block == "*":
            return x + _attention_mixer(cfg, mesh, lp, n), None
        y, stats = _latent_experts(cfg, lp, n)
        return x + y, stats


def hidden_states(
    cfg: SsmAttnMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> tuple[jax.Array, list[dict]]:
    """tokens [B, S] -> (the last block's output before the final norm
    [B, S, d], each routed run's statistics stacked on its axis)."""
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)

    def unit_of(unit: str):
        blocks = [checkpointed(cfg, partial(_block, cfg, mesh, b)) for b in unit]

        def body(x, lps):
            routed = []
            for block, lp in zip(blocks, lps, strict=True):
                x, stats = block(x, lp)
                if stats is not None:
                    routed.append(stats)
            # A unit's routed blocks side by side on a leading axis, so that a
            # run's statistics are [repetitions, routed blocks a unit, ...].
            stacked = jax.tree_util.tree_map(lambda *s: jnp.stack(s), *routed) if routed else None
            return x, stacked

        return body

    x, stats = scan_runs(unit_of, cfg.runs, params["runs"], x)
    # [repetitions, blocks a unit, ...] -> [blocks, ...], in forward order.
    flat = lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])
    return x, [jax.tree_util.tree_map(flat, s) for s in stats]


def lm_loss(
    cfg: SsmAttnMoeConfig, params: dict, tokens: jax.Array, targets: jax.Array,
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
    cfg: SsmAttnMoeConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> dict:
    """float32 logits and each routed block's selection [blocks, T, k]: the
    inspection entry point, not the train hot path."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    return decoder_stack.inspect_logits(cfg, params["final_norm"], params["output"], x, stats)


def make_trainer(cfg: SsmAttnMoeConfig, mesh: Mesh, trainer_config) -> Any:
    """The generic SPMD Trainer on this model, as `llama.make_trainer`."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )
