"""Autoregressive decoding for the Llama family — the inference path.

The reference is a training-only stack (no serving/inference anywhere in
SURVEY.md); generation is part of the TPU framework's completeness story
for its flagship transformer.  TPU-first design:

- **Static shapes end-to-end**: the KV cache is a fixed [L, B, max_seq,
  Hkv, D] buffer; every decode step attends over the full buffer with a
  position mask instead of slicing a growing prefix — no dynamic shapes,
  one compiled step regardless of position.
- **Whole generation inside one jit**: prefill writes the prompt's K/V
  with a single batched forward, then ``lax.scan`` runs the decode steps
  (sample -> embed -> one-token forward -> cache update) with the cache as
  carry.  Python never touches the loop.
- **Scan over layers with cache carry**: the decode step runs the
  trainer's own block (``llama.decoder_block``) over the training weights
  (scan-stacked [L, ...]), handing it a KV context that writes and reads
  the per-layer cache slice, so parameter layout and block math are
  identical between training and inference — a checkpoint restores
  straight into serving.
- Greedy or temperature sampling via ``jax.random.categorical``.

Pipeline checkpoints decode directly (stage-stacked layers fold back to
the flat scan layout).  MoE configs route per decode call: expert
capacity is recomputed for each step's tokens, so with a config whose
prompt overflows expert capacity the cached logits can differ from the
teacher-forced training forward (which drops overflowed tokens batch-
wide).  This per-call routing is the standard serving behavior; the
dense path is bit-matched to training by tests/test_llama_decode.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from deeplearning_cfn_tpu.models.llama import LlamaConfig, decoder_block, head_logits
from deeplearning_cfn_tpu.ops.attention import dot_product_attention


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class KVCache:
    """Per-layer K/V buffers, layer axis leading (scan carry)."""

    k: jax.Array  # [L, B, max_seq, Hkv, D]
    v: jax.Array


def init_cache(cfg: LlamaConfig, batch: int, max_seq: int) -> KVCache:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
    )


def _flat_layers(cfg: LlamaConfig, params: dict) -> dict:
    """Training params may be stage-stacked ([pp, L/pp, ...]); decoding
    always scans the flat [L, ...] layout."""
    layers = params["layers"]
    if cfg.pp_stages > 1:
        from deeplearning_cfn_tpu.parallel.pipeline import unstack_stages

        layers = unstack_stages(layers)
    return layers


def sample_token(
    logits: jax.Array,  # [..., V] float32
    key: jax.Array,
    temperature: float,
) -> jax.Array:
    """Greedy argmax at temperature 0.0, else ``categorical(logits / T)``.

    Shared by :func:`generate` and the serving plane's paged decode step
    (serve/engine.py) so both paths sample with byte-identical math —
    the bit-parity contract in tests/test_serve.py depends on it.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def _attend_cached(
    q: jax.Array,  # [B, S, H, D]
    cache_k: jax.Array,  # [B, max_seq, Hkv, D]
    cache_v: jax.Array,
    valid_len: jax.Array,  # scalar: positions < valid_len are real
    causal_offset: jax.Array,  # position of q[0] in the sequence
) -> jax.Array:
    """Attention over the full static cache: the training attention op
    with an explicit validity+causal mask (causality by position, since q
    and cache indices are offset from each other)."""
    S = q.shape[1]
    max_seq = cache_k.shape[1]
    kpos = jnp.arange(max_seq)
    qpos = causal_offset + jnp.arange(S)
    mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < valid_len)
    return dot_product_attention(
        q, cache_k, cache_v, causal=False, mask=mask[None, None]
    )


def _forward_cached(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # [B, S]
    cache: KVCache,
    offset: jax.Array,  # scalar: position of tokens[:, 0]
) -> tuple[jax.Array, KVCache]:
    """Forward over S tokens starting at ``offset``, reading and writing
    the cache.  Returns (logits [B, S, V], updated cache)."""
    B, S = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = offset + jnp.arange(S, dtype=jnp.int32)
    valid_len = offset + S
    layers = _flat_layers(cfg, params)

    def scan_body(x, layer):
        lp, lk, lv = layer

        def through_cache(q, k, v):
            # The decoder's context: this call's k and v are written into
            # the layer's buffer at `offset` first, so each token attends
            # to itself through the cache, and the buffers are carried on.
            new_k = jax.lax.dynamic_update_slice(lk, k.astype(lk.dtype), (0, offset, 0, 0))
            new_v = jax.lax.dynamic_update_slice(lv, v.astype(lv.dtype), (0, offset, 0, 0))
            return _attend_cached(q, new_k, new_v, valid_len, offset), (new_k, new_v)

        x, _aux, written = decoder_block(cfg, through_cache, x, lp, positions)
        return x, written

    x, (new_k, new_v) = jax.lax.scan(scan_body, x, (layers, cache.k, cache.v))
    logits = head_logits(cfg, params, x)
    return logits.astype(jnp.float32), KVCache(k=new_k, v=new_v)


@partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature"),
)
def generate(
    cfg: LlamaConfig,
    params: dict,
    prompt: jax.Array,  # [B, S_prompt] int32
    rng: jax.Array,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
) -> jax.Array:
    """Prefill + scan-decode.  Returns [B, max_new_tokens] sampled tokens.

    temperature 0.0 = greedy argmax; > 0 samples from
    ``softmax(logits / temperature)``.
    """
    B, S = prompt.shape
    max_seq = S + max_new_tokens
    if max_seq > cfg.max_seq_len:
        raise ValueError(
            f"prompt {S} + {max_new_tokens} new tokens exceeds "
            f"max_seq_len={cfg.max_seq_len}"
        )
    cache = init_cache(cfg, B, max_seq)
    logits, cache = _forward_cached(
        cfg, params, prompt, cache, jnp.asarray(0, jnp.int32)
    )

    def sample(logits_1, key):
        return sample_token(logits_1, key, temperature)

    keys = jax.random.split(rng, max_new_tokens)
    first = sample(logits[:, -1], keys[0])

    def step(carry, key):
        token, cache, pos = carry
        logits, cache = _forward_cached(
            cfg, params, token[:, None], cache, pos
        )
        nxt = sample(logits[:, -1], key)
        return (nxt, cache, pos + 1), token

    # max_new_tokens - 1 decode steps: the scan emits its carried token,
    # so the final sampled token comes out as the end carry (no wasted
    # trailing forward).
    (last, _, _), tokens = jax.lax.scan(
        step, (first, cache, jnp.asarray(S, jnp.int32)), keys[1:]
    )
    return jnp.concatenate(
        [jnp.swapaxes(tokens, 0, 1), last[:, None]], axis=1
    )  # [B, max_new_tokens]
