"""A decoder whose layers are a mixer half and a dense SwiGLU half, the mixer a
Mamba-1 selective state-space layer in most layers and multi-query attention
in one of every `attn_layer_period`.  The stack of Jamba (`jamba`,
arXiv:2403.19887) with one expert, so every feed-forward half is dense.

On `models/decoder_stack.py`'s run machinery (`runs_of`, `init_runs`,
`run_specs`, `scan_runs`), and built from the shared parts where the part is
the same (`llama.attention_kind`, `attend`, `swiglu`, `ops/conv.short_conv`,
`decoder_stack`'s embedding, head and loss).  What is this kind's own:

- **The layer order is two numbers**: layer i attends where
  ``i mod attn_layer_period == attn_layer_offset`` and is a Mamba layer
  otherwise; consecutive layers of one kind are a run (7 Mamba, 1 attention,
  6 Mamba in one period of 14: three runs, three scans).
- **Mamba-1** (arXiv:2312.00752).  ``[x | z] = n W_in``; x goes through a
  depthwise causal convolution of `conv_taps` taps with a bias and a SiLU;
  ``[delta | B | C] = x W_x`` (inner -> rank + 2 N), each of the three through
  an RMSNorm of its own (Jamba's); ``dt = softplus(delta W_dt + b_dt)`` in
  float32; ``A = -exp(A_log)`` [inner, N]; the scan is
  `ops/selective_scan.selective_scan`, or `ops/pallas_selective_scan`'s where
  its `takes_kernel` says so (a TPU, whole time chunks, channels whole lane
  tiles); ``y = y * silu(z)``, then ``W_out``.  No norm after the gate.  The
  convolution with its SiLU is `_conv_silu`, or the fused kernel of
  `ops/pallas_ssm_stages.py` where its rule says so.
- **Attention** is causal multi-query attention with no positional encoding
  and no bias (the Mamba layers order the tokens).
- **The table is tied**: the head reads ``embed`` transposed, so the table's
  gradient is the lookup's scatter plus the head's matmul in one leaf.

Parameters: ``embed``, ``final_norm`` and ``runs``, a list with one dict a run
of weights stacked on the run's axis.  Float32 leaves: ``A_log``, ``D``,
``dt_bias``, ``conv_w``, ``conv_bias`` and every norm's scale.  Scopes: ``ssm_norm``,
``ssm/{in_proj,conv,x_proj,bcdt_norm,dt_proj,scan,gate,out_proj}``;
``attn_norm``, ``attn/{qkv,core,out}``; ``mlp_norm``, ``mlp``.  Counters
(`metrics["counters"]`, folded by `Trainer.fit`): ``ssm.dt_mean`` and
``ssm.dt_max`` over tokens, channels and Mamba layers; with ``A_log`` they say
how far a state carries.
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
    runs_of,
    scan_runs,
)
from deeplearning_cfn_tpu.models.llama import attend, attention_kind, swiglu
from deeplearning_cfn_tpu.ops import pallas_selective_scan, pallas_ssm_stages
from deeplearning_cfn_tpu.ops.attention import rms_norm
from deeplearning_cfn_tpu.ops.conv import short_conv
from deeplearning_cfn_tpu.ops.selective_scan import selective_scan

@dataclass(frozen=True)
class MambaAttnConfig:
    """Sizes under the names of the published `config.json` keys' meaning.  The
    defaults are AI21-Jamba2-3B's widths and one period of its layers
    (published layers 0-13 of 28)."""

    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 14
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    ssm_inner: int = 5120  # mamba_expand * hidden_size
    ssm_state: int = 16  # mamba_d_state
    conv_taps: int = 4  # mamba_d_conv
    dt_rank: int = 160  # mamba_dt_rank
    mlp_dim: int = 8192  # intermediate_size
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    use_ring_attention: bool = False  # `attention_kind` asks; not built here

    def __post_init__(self):
        if not 0 <= self.attn_layer_offset < self.attn_layer_period or self.n_layers < 1:
            raise ValueError(
                f"{self.n_layers} layers, attention at {self.attn_layer_offset} "
                f"of every {self.attn_layer_period}"
            )
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over {self.n_kv_heads} key/value heads")

    @property
    def kinds(self) -> tuple[str, ...]:
        period, offset = self.attn_layer_period, self.attn_layer_offset
        return tuple("attention" if i % period == offset else "mamba" for i in range(self.n_layers))

    @property
    def runs(self) -> tuple[tuple[str, int], ...]:
        return runs_of(self.kinds)

    @classmethod
    def tiny(cls, **kw) -> "MambaAttnConfig":
        """The structure at toy widths, for the CPU tests: a period of four
        layers with the attention layer third, twice."""
        base = dict(
            vocab_size=128, dim=32, n_layers=8, attn_layer_period=4, attn_layer_offset=2,
            ssm_inner=64, ssm_state=8, dt_rank=4, mlp_dim=48, n_heads=4, n_kv_heads=1, head_dim=8,
            remat=False, dtype=jnp.float32,
        )
        return cls(**{**base, **kw})


# --- parameters ---------------------------------------------------------


def _layer_params(cfg: MambaAttnConfig, key: jax.Array, kind: str) -> dict:
    keys = jax.random.split(key, 9)
    d, inner, N, R = cfg.dim, cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
    init = partial(dense_init, dtype=cfg.dtype)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    params = {
        "mixer_norm": ones(d), "mlp_norm": ones(d),
        "w_gate": init(keys[0], (d, cfg.mlp_dim), d), "w_up": init(keys[1], (d, cfg.mlp_dim), d),
        "w_down": init(keys[2], (cfg.mlp_dim, d), cfg.mlp_dim),
    }
    if kind == "mamba":
        # dt log-uniform in [1e-3, 1e-1] through the softplus' inverse and
        # A[c, n] = n + 1 (S4D-real): Mamba's own initialisation.
        dt = jnp.exp(jax.random.uniform(keys[3], (inner,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        params.update(
            in_proj=init(keys[4], (d, 2 * inner), d),
            # float32: its elements are of order 0.5 (fan-in 4 taps), where bfloat16's
            # spacing is 0.002-0.004 and an AdamW step of 3e-4 moves nothing.
            conv_w=dense_init(keys[5], (cfg.conv_taps, inner), cfg.conv_taps, jnp.float32),
            conv_bias=jnp.zeros((inner,), jnp.float32),
            x_proj=init(keys[6], (inner, R + 2 * N), inner),
            dt_norm=ones(R), b_norm=ones(N), c_norm=ones(N),
            dt_proj=init(keys[7], (R, inner), R),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (inner, N))),
            D=ones(inner),
            out_proj=init(keys[8], (inner, d), inner),
        )
    else:
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        params.update(
            wq=init(keys[3], (d, q), d), wk=init(keys[4], (d, kv), d),
            wv=init(keys[5], (d, kv), d), wo=init(keys[6], (q, d), q),
        )
    return params


def init_params(cfg: MambaAttnConfig, rng: jax.Array) -> dict:
    k_embed, k_runs = jax.random.split(rng)
    return {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, cfg.dtype),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "runs": init_runs(partial(_layer_params, cfg), cfg.runs, k_runs),
    }


def _layer_specs(kind: str) -> dict:
    specs = {
        "mixer_norm": P(None), "mlp_norm": P(None),
        "w_gate": P("fsdp", "tp"), "w_up": P("fsdp", "tp"), "w_down": P("tp", "fsdp"),
    }
    if kind == "mamba":
        specs.update(
            in_proj=P("fsdp", "tp"), conv_w=P(None, "tp"), conv_bias=P("tp"),
            x_proj=P("tp", None), dt_norm=P(None), b_norm=P(None), c_norm=P(None),
            dt_proj=P(None, "tp"), dt_bias=P("tp"), A_log=P("tp", None), D=P("tp"),
            out_proj=P("tp", "fsdp"),
        )
    else:
        specs.update(wq=P("fsdp", "tp"), wk=P("fsdp", "tp"), wv=P("fsdp", "tp"), wo=P("tp", "fsdp"))
    return specs


def param_specs(cfg: MambaAttnConfig) -> dict:
    """fsdp on a matrix's input axis, tp on its output axis, as llama.py."""
    return {
        "embed": P("tp", "fsdp"),
        "final_norm": P(None),
        "runs": run_specs(_layer_specs, cfg.runs),
    }


def param_shardings(cfg: MambaAttnConfig, mesh: Mesh) -> dict:
    return decoder_stack.shardings(param_specs(cfg), mesh)


def param_count(cfg: MambaAttnConfig) -> int:
    return decoder_stack.count(cfg, init_params)


def train_flops_per_token(cfg: MambaAttnConfig, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token costs: 6 per weight it
    passes through (the tied table once, as the head; the lookup is none), the
    recurrence's 4 N a channel forward and twice that backward (the fewest any
    form of the scan does), and the causal half of the score products in the
    attention layers."""
    d, inner, N = cfg.dim, cfg.ssm_inner, cfg.ssm_state
    weights = {
        "mamba": d * 2 * inner + cfg.conv_taps * inner + inner * (cfg.dt_rank + 2 * N)
        + cfg.dt_rank * inner + inner * d,
        "attention": 2 * d * cfg.n_heads * cfg.head_dim + 2 * d * cfg.n_kv_heads * cfg.head_dim,
    }
    scan = 3 * 4 * N * inner
    scores = 3 * seq_len * cfg.n_heads * 2 * cfg.head_dim
    kinds = cfg.kinds
    return (
        6.0 * (d * cfg.vocab_size + sum(weights[k] + 3 * d * cfg.mlp_dim for k in kinds))
        + scan * kinds.count("mamba") + scores * kinds.count("attention")
    )


# --- forward ------------------------------------------------------------


def _conv_silu(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """silu(depthwise causal conv(x) + bias), the taps in float32."""
    f32 = jnp.float32
    return jax.nn.silu(short_conv(x.astype(f32), w.astype(f32)) + bias).astype(x.dtype)


@jax.checkpoint
def _gate(y: jax.Array, z: jax.Array) -> jax.Array:
    """y * silu(z) in float32; rematerialised by itself, so that its inputs and
    not its float32 intermediates live until the layer's backward pass."""
    return (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)


@jax.custom_vjp
def _softplus(p: jax.Array) -> jax.Array:
    """softplus whose backward pass reads its result: d/dp = 1 - exp(-dt), so
    the scan's dt is the one float32 [B, S, inner] array the stage keeps."""
    return jax.nn.softplus(p)


_softplus.defvjp(lambda p: (_softplus(p),) * 2, lambda dt, g: (g * -jnp.expm1(-dt),))


def _ssm_mixer(cfg: MambaAttnConfig, lp: dict, n: jax.Array) -> tuple[jax.Array, dict]:
    """Mamba-1 on the normalised input n [B, S, d]: (the mixer's result, dt's
    sum and largest value for the counters)."""
    N, R = cfg.ssm_state, cfg.dt_rank
    with jax.named_scope("in_proj"):
        x, z = jnp.split(n @ lp["in_proj"], 2, axis=-1)
    with jax.named_scope("conv"):
        kernel = pallas_ssm_stages.takes_conv_kernel(x, lp["conv_w"])
        conv = pallas_ssm_stages.conv_silu if kernel else jax.checkpoint(_conv_silu)
        x = conv(x, lp["conv_w"], lp["conv_bias"])
    with jax.named_scope("x_proj"):
        delta, Bm, Cm = jnp.split(x @ lp["x_proj"], (R, R + N), axis=-1)
    with jax.named_scope("bcdt_norm"):
        delta, Bm, Cm = (
            rms_norm(a, lp[w], cfg.norm_eps)
            for a, w in ((delta, "dt_norm"), (Bm, "b_norm"), (Cm, "c_norm"))
        )
    with jax.named_scope("dt_proj"):
        dt = _softplus(
            jnp.matmul(delta, lp["dt_proj"], preferred_element_type=jnp.float32) + lp["dt_bias"]
        )
        stats = {"dt_sum": jnp.sum(dt), "dt_max": jnp.max(dt)}
    with jax.named_scope("scan"):
        A = -jnp.exp(lp["A_log"])
        # The shapes and the backend choose: the fused kernels, or the plain form.
        kernels = pallas_selective_scan.takes_kernel(x, A)
        scan = pallas_selective_scan.selective_scan if kernels else selective_scan
        y = scan(x, dt, A, Bm, Cm, lp["D"])
    with jax.named_scope("gate"):
        y = _gate(y, z)
    with jax.named_scope("out_proj"):
        return y @ lp["out_proj"], stats


def _attention_mixer(cfg: MambaAttnConfig, mesh: Mesh | None, lp: dict, n: jax.Array) -> jax.Array:
    """Causal multi-query attention without positions on n [B, S, d]."""
    B, S, _ = n.shape
    with jax.named_scope("qkv"):
        q = (n @ lp["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
        k = (n @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        v = (n @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    with jax.named_scope("core"):
        attn = attend(attention_kind(cfg, mesh, S), q, k, v, mesh)
    with jax.named_scope("out"):
        return attn.reshape(B, S, cfg.n_heads * cfg.head_dim) @ lp["wo"]


def layer(
    cfg: MambaAttnConfig, mesh: Mesh | None, kind: str, x: jax.Array, lp: dict
) -> tuple[jax.Array, dict | None]:
    """One layer of `kind`: x + Mixer(RMSNorm(x)), then x + SwiGLU(RMSNorm(x));
    and a Mamba layer's dt statistics."""
    scope = {"mamba": "ssm", "attention": "attn"}[kind]
    with jax.named_scope(scope + "_norm"):
        n = rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
    with jax.named_scope(scope):
        if kind == "mamba":
            y, stats = _ssm_mixer(cfg, lp, n)
        else:
            y, stats = _attention_mixer(cfg, mesh, lp, n), None
    x = x + y
    with jax.named_scope("mlp_norm"):
        n = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        return x + swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"]), stats


def hidden_states(
    cfg: MambaAttnConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> tuple[jax.Array, list[dict]]:
    """tokens [B, S] -> (the last layer's output before the final norm
    [B, S, d], each Mamba run's dt statistics stacked on its axis)."""
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    layer_of = lambda kind: checkpointed(cfg, partial(layer, cfg, mesh, kind))
    return scan_runs(layer_of, cfg.runs, params["runs"], x)


def _head(cfg: MambaAttnConfig, params: dict) -> jax.Array:
    """The tied head: the table in the compute type, transposed."""
    return params["embed"].astype(cfg.dtype).T


def dt_counters(cfg: MambaAttnConfig, stats: list[dict], tokens: int) -> dict:
    """``ssm.dt_mean`` and ``ssm.dt_max`` over tokens, channels and Mamba layers."""
    layers = cfg.kinds.count("mamba")
    total = sum(jnp.sum(s["dt_sum"]) for s in stats)
    return {
        "ssm.dt_mean": total / (layers * tokens * cfg.ssm_inner),
        "ssm.dt_max": jnp.max(jnp.stack([jnp.max(s["dt_max"]) for s in stats])),
    }


def lm_loss(
    cfg: MambaAttnConfig, params: dict, tokens: jax.Array, targets: jax.Array,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy; `targets[i]` is the token that follows
    `tokens[i]` (the last one wrapped, and masked).  The head with its loss
    is rematerialised, as `decoder_stack.next_token_loss` does."""
    x, stats = hidden_states(cfg, params, tokens, mesh)
    loss = checkpointed(cfg, partial(decoder_stack.head_loss, cfg))(
        params["final_norm"], _head(cfg, params), x, targets, ahead=1
    )
    metrics = {"perplexity": jnp.exp(loss)}
    if stats:
        metrics["counters"] = dt_counters(cfg, jax.lax.stop_gradient(stats), tokens.size)
    return loss, metrics


def logits(
    cfg: MambaAttnConfig, params: dict, tokens: jax.Array, mesh: Mesh | None = None
) -> dict:
    """float32 logits: the inspection entry point, not the train hot path."""
    x, _ = hidden_states(cfg, params, tokens, mesh)
    return decoder_stack.inspect_logits(cfg, params["final_norm"], _head(cfg, params), x, [])


def make_trainer(cfg: MambaAttnConfig, mesh: Mesh, trainer_config) -> Any:
    """The generic SPMD Trainer on this model, as `llama.make_trainer`."""
    return decoder_stack.make_trainer(
        cfg, mesh, trainer_config, init_params=init_params, lm_loss=lm_loss,
        param_specs=param_specs, train_flops_per_token=train_flops_per_token,
    )
