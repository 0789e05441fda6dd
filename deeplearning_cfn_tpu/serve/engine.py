"""Continuous-batching decode engine over the paged K/V pool.

Two jitted step functions and one host-side scheduler:

- :func:`paged_prefill` — forward one (padded) prompt, scattering its K/V
  into the slot's pages and sampling the first token.  One compile for
  any prompt length ≤ ``prefill_len``.
- :func:`paged_decode_step` — advance EVERY active slot by one token in a
  single call: scatter each slot's last token's K/V to its pages, gather
  each slot's block table back into a contiguous context, attend under a
  per-slot validity mask, sample.  One compile for the engine's lifetime
  regardless of which slots are occupied (inactive slots scatter to an
  out-of-range page under ``mode="drop"`` and their outputs are ignored).
- :class:`ContinuousBatchingEngine` — admits queued requests into free
  slots at step boundaries (prefill the newcomer, resume decode for the
  rest), retires finished requests, recycles their pages, and journals
  serve metrics (TTFT / inter-token latency / queue depth / tokens/s)
  against an injectable clock so the soak and chaos harnesses run on
  virtual time.

Every layer these programs run is ``models.llama.decoder_block``, the
block the trainer and ``models/llama_decode`` run, and the tail is
``llama.head_logits`` and ``llama_decode.sample_token``.  The engine's own
part is the block's KV context (:func:`_through_pool`): scatter the new
tokens' K/V to their pages, gather the slot's pages back, mask by position
and length, in ``llama_decode``'s write-then-attend order.  With a pool
shaped so the gathered context equals `generate`'s ``max_seq``, greedy
outputs are bit-identical to the whole-generation ``lax.scan`` path
(tests/test_serve.py parity).

Prefill/decode disaggregation (where the topology allows — see
serve/placement.py): :func:`prefill_kv` computes a prompt's K/V on a
dedicated prefill device with local causal attention, and
:func:`scatter_prompt_kv` lands the transferred K/V in the decode
device's pool.  Numerically equivalent but not bit-pinned (the local
attention reduces over ``prefill_len``, not the gathered context).
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_cfn_tpu.models.llama import LlamaConfig, decoder_block, head_logits
from deeplearning_cfn_tpu.models.llama_decode import _flat_layers, sample_token
from deeplearning_cfn_tpu.ops.attention import dot_product_attention
from deeplearning_cfn_tpu.serve.paged_cache import (
    BlockAllocator,
    PagedKVCache,
    init_paged_cache,
)


class ServeAdmissionError(ValueError):
    """A request the engine cannot ever serve (or backpressure rejected):
    raised at submit() — an accepted request is never silently dropped."""


@dataclass(frozen=True)
class ServeConfig:
    """Host-side scheduler shape.  Everything the jitted steps need is
    carried by array shapes, so this config never enters a trace."""

    num_slots: int = 8
    block_size: int = 16
    blocks_per_slot: int = 8  # max context = block_size * blocks_per_slot
    prefill_len: int = 64  # static prompt pad length (one prefill compile)
    num_blocks: int = 0  # 0 -> num_slots * blocks_per_slot (full occupancy)
    temperature: float = 0.0
    max_queue: int = 0  # 0 -> unbounded; else submit() rejects when full

    @property
    def max_context(self) -> int:
        return self.block_size * self.blocks_per_slot

    @property
    def resolved_num_blocks(self) -> int:
        return self.num_blocks or self.num_slots * self.blocks_per_slot


@dataclass
class ServeRequest:
    request_id: str
    prompt: np.ndarray  # [P] int32 token ids
    max_new_tokens: int
    arrival_s: float = 0.0


@dataclass
class Completion:
    request_id: str
    tokens: list[int]  # the max_new_tokens sampled tokens
    prompt_len: int
    arrival_s: float
    first_token_s: float
    finish_s: float
    token_times_s: list[float] = field(default_factory=list)


@dataclass
class _Slot:
    request: ServeRequest
    blocks: list[int]
    table: np.ndarray  # [blocks_per_slot] int32, 0-padded past the owned blocks
    length: int  # tokens resident in the pool (prompt + decoded-in)
    generated: list[int]
    token_times: list[float]


def _through_pool(cfg, params, cache, x, positions, write_blk, write_off, table, qpos, valid_len):
    """The decoder's layers over the paged pool: (x, the written pool).

    ``x`` is [B, T, d] (prefill: B=1, T=prefill_len; decode: B=num_slots,
    T=1).  Every layer is ``llama.decoder_block``; what the engine adds
    is the block's KV context for a layer's pool ``lk``/``lv``
    [num_blocks, bs, Hkv, D]: scatter this call's k and v to the flattened
    [B*T] targets ``write_blk``/``write_off`` (an out-of-range block is a
    dropped write), gather each row's pages by ``table``
    [B, blocks_per_slot] into a contiguous context, and attend under the
    causal+validity mask of ``llama_decode._attend_cached`` (``qpos``
    [B, T], ``valid_len`` [B]).  Write-then-attend, as there: the new
    tokens' K/V land in the pool first, so each token attends to itself
    through the cache.
    """

    def scan_body(x, layer):
        lp, lk, lv = layer

        def pool(q, k, v):
            B, T, n_kv, hd = k.shape
            new_k = lk.at[write_blk, write_off].set(
                k.astype(lk.dtype).reshape(B * T, n_kv, hd), mode="drop"
            )
            new_v = lv.at[write_blk, write_off].set(
                v.astype(lv.dtype).reshape(B * T, n_kv, hd), mode="drop"
            )
            ctx_k = new_k[table].reshape(B, -1, n_kv, hd)  # [B, max_ctx, Hkv, D]
            ctx_v = new_v[table].reshape(B, -1, n_kv, hd)
            kpos = jnp.arange(ctx_k.shape[1])
            mask = (kpos[None, None, :] <= qpos[:, :, None]) & (
                kpos[None, None, :] < valid_len[:, None, None]
            )
            attn = dot_product_attention(q, ctx_k, ctx_v, causal=False, mask=mask[:, None])
            return attn, (new_k, new_v)

        x, _aux, written = decoder_block(cfg, pool, x, lp, positions)
        return x, written

    x, (new_k, new_v) = jax.lax.scan(
        scan_body, x, (_flat_layers(cfg, params), cache.k, cache.v)
    )
    return x, PagedKVCache(k=new_k, v=new_v)


@partial(jax.jit, static_argnames=("cfg", "temperature"), donate_argnums=(2,))
def paged_prefill(
    cfg: LlamaConfig,
    params: dict,
    cache: PagedKVCache,
    tokens: jax.Array,  # [1, prefill_len] int32, zero-padded past `length`
    length: jax.Array,  # [] int32: real prompt length
    blocks: jax.Array,  # [blocks_per_slot] int32 physical pages, 0-padded
    key: jax.Array,
    temperature: float = 0.0,
) -> tuple[jax.Array, PagedKVCache]:
    """Prefill one slot through the pool; returns (first token, cache).

    Pad rows (p >= length) scatter out of range (dropped) and their
    logits rows are never read, so one compile covers every prompt
    length; the sampled token comes from row ``length - 1``.
    """
    _, S = tokens.shape
    bs = cache.block_size
    num_blocks = cache.num_blocks
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.arange(S, dtype=jnp.int32)
    pidx = jnp.arange(S, dtype=jnp.int32)
    write_blk = jnp.where(pidx < length, blocks[pidx // bs], num_blocks)
    write_off = pidx % bs
    table = blocks[None, :]
    qpos = positions[None, :]
    valid_len = length[None] if length.ndim == 0 else length
    x, cache = _through_pool(
        cfg, params, cache, x, positions, write_blk, write_off, table, qpos, valid_len
    )
    logits = head_logits(cfg, params, x).astype(jnp.float32)  # [1, S, V]
    first = sample_token(logits[0, length - 1], key, temperature)
    return first, cache


@partial(jax.jit, static_argnames=("cfg", "temperature"), donate_argnums=(2,))
def paged_decode_step(
    cfg: LlamaConfig,
    params: dict,
    cache: PagedKVCache,
    tokens: jax.Array,  # [num_slots] int32: each slot's last sampled token
    lengths: jax.Array,  # [num_slots] int32: tokens resident per slot
    tables: jax.Array,  # [num_slots, blocks_per_slot] int32
    active: jax.Array,  # [num_slots] bool
    key: jax.Array,
    temperature: float = 0.0,
) -> tuple[jax.Array, PagedKVCache]:
    """One decode step for every slot at once; returns (next tokens, cache).

    The single compile the serving plane lives on: slot occupancy, request
    lengths, and page placement are all DATA (this is what the DLC410
    sentinel and the soak test pin down).  Inactive slots write to block
    id ``num_blocks`` (dropped) and their sampled tokens are discarded by
    the scheduler.
    """
    S = tokens.shape[0]
    bs = cache.block_size
    num_blocks = cache.num_blocks
    x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]  # [S, 1, d]
    positions = lengths[:, None]  # each new token sits at position `length`
    write_blk = jnp.where(
        active, tables[jnp.arange(S), lengths // bs], num_blocks
    )
    write_off = lengths % bs
    qpos = positions
    valid_len = lengths + 1
    x, cache = _through_pool(
        cfg, params, cache, x, positions, write_blk, write_off, tables, qpos, valid_len
    )
    logits = head_logits(cfg, params, x).astype(jnp.float32)  # [S, 1, V]
    nxt = sample_token(logits[:, 0], key, temperature)
    return nxt, cache


@partial(jax.jit, static_argnames=("cfg", "temperature"))
def prefill_kv(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # [1, prefill_len] int32
    length: jax.Array,  # [] int32
    key: jax.Array,
    temperature: float = 0.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Disaggregated prefill: compute a prompt's K/V with LOCAL causal
    attention (no pool access), for a dedicated prefill device.  Returns
    (first token, ks [L, prefill_len, Hkv, D], vs) — the caller transfers
    ks/vs to the decode device and lands them with scatter_prompt_kv.
    """
    _, S = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.arange(S, dtype=jnp.int32)
    kpos = jnp.arange(S)
    mask = (kpos[None, :] <= kpos[:, None]) & (kpos[None, :] < length)
    layers = _flat_layers(cfg, params)

    def own_prompt(q, k, v):
        # The prefill device's context: the prompt itself under the
        # causal-and-length mask; the fresh k and v are what it carries out.
        return dot_product_attention(q, k, v, causal=False, mask=mask[None, None]), (k, v)

    def scan_body(x, lp):
        x, _aux, fresh = decoder_block(cfg, own_prompt, x, lp, positions)
        return x, fresh

    x, (ks, vs) = jax.lax.scan(scan_body, x, layers)
    logits = head_logits(cfg, params, x).astype(jnp.float32)
    first = sample_token(logits[0, length - 1], key, temperature)
    return first, ks[:, 0].astype(cfg.dtype), vs[:, 0].astype(cfg.dtype)


@partial(jax.jit, donate_argnums=(0,))
def scatter_prompt_kv(
    cache: PagedKVCache,
    ks: jax.Array,  # [L, prefill_len, Hkv, D]
    vs: jax.Array,
    length: jax.Array,  # [] int32
    blocks: jax.Array,  # [blocks_per_slot] int32
) -> PagedKVCache:
    """Land a transferred prompt K/V in the pool (decode-device side of
    disaggregated prefill)."""
    S = ks.shape[1]
    bs = cache.block_size
    pidx = jnp.arange(S, dtype=jnp.int32)
    write_blk = jnp.where(pidx < length, blocks[pidx // bs], cache.num_blocks)
    write_off = pidx % bs
    k = cache.k.at[:, write_blk, write_off].set(ks.astype(cache.k.dtype), mode="drop")
    v = cache.v.at[:, write_blk, write_off].set(vs.astype(cache.v.dtype), mode="drop")
    return PagedKVCache(k=k, v=v)


class ContinuousBatchingEngine:
    """Slot scheduler: admit at step boundaries, decode everyone at once.

    ``clock`` is any zero-arg float callable (``VirtualClock`` in tests
    and chaos; ``time.monotonic`` in production) — all latency metrics
    are measured on it, never on the wall.  ``placement`` (optional, see
    serve/placement.py) switches prefill to the disaggregated path.
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        params: dict,
        serve_cfg: ServeConfig | None = None,
        clock: Callable[[], float] = time.monotonic,
        name: str = "serve0",
        placement=None,
        journal: bool = True,
    ):
        self.cfg = cfg
        self.serve_cfg = serve_cfg or ServeConfig()
        self.clock = clock
        self.name = name
        self.placement = placement
        self.journal = journal
        scfg = self.serve_cfg
        if scfg.prefill_len > scfg.max_context:
            raise ValueError(
                f"prefill_len {scfg.prefill_len} exceeds max context "
                f"{scfg.max_context}"
            )
        decode_device = placement.decode_devices[0] if placement else None
        self.params = (
            jax.device_put(params, decode_device) if decode_device else params
        )
        if placement and placement.disaggregated:
            self._prefill_params = jax.device_put(
                params, placement.prefill_devices[0]
            )
        else:
            self._prefill_params = self.params
        self.cache = init_paged_cache(
            cfg, scfg.resolved_num_blocks, scfg.block_size
        )
        if decode_device:
            self.cache = jax.device_put(self.cache, decode_device)
        self.allocator = BlockAllocator(scfg.resolved_num_blocks)
        self.slots: list[_Slot | None] = [None] * scfg.num_slots
        self.queue: deque[ServeRequest] = deque()
        self._key = jax.random.key(0)
        # --- metrics (virtual-clock latencies; see docs/SERVING.md) -----
        self.steps = 0
        self.admitted = 0
        self.completed = 0
        self.rejected = 0
        self.prefills = 0
        self.tokens_out = 0
        self.kv_transfer_bytes = 0
        self.max_wait_steps = 0
        self._enqueued_step: dict[str, int] = {}
        self._ttft_s: list[float] = []
        self._itl_s: list[float] = []
        self._started_at = self.clock()

    # --- admission ------------------------------------------------------
    def submit(self, request: ServeRequest, arrival_s: float | None = None) -> None:
        """Accept a request (or raise ServeAdmissionError).  Acceptance is
        a promise: an accepted request always completes or is replayed."""
        scfg = self.serve_cfg
        prompt = np.asarray(request.prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ServeAdmissionError(
                f"{request.request_id}: prompt must be a non-empty 1-D "
                f"token array, got shape {prompt.shape}"
            )
        if request.max_new_tokens < 1:
            raise ServeAdmissionError(
                f"{request.request_id}: max_new_tokens must be >= 1"
            )
        if prompt.size > scfg.prefill_len:
            raise ServeAdmissionError(
                f"{request.request_id}: prompt of {prompt.size} tokens "
                f"exceeds prefill_len={scfg.prefill_len}"
            )
        if prompt.size + request.max_new_tokens - 1 > scfg.max_context:
            raise ServeAdmissionError(
                f"{request.request_id}: prompt {prompt.size} + "
                f"{request.max_new_tokens} new tokens exceeds max context "
                f"{scfg.max_context}"
            )
        if scfg.max_queue and len(self.queue) >= scfg.max_queue:
            self.rejected += 1
            raise ServeAdmissionError(
                f"{request.request_id}: queue full ({scfg.max_queue}); "
                "backpressure — retry against another replica"
            )
        request.prompt = prompt
        if arrival_s is not None:
            request.arrival_s = arrival_s
        elif request.arrival_s == 0.0:
            request.arrival_s = self.clock()
        self._enqueued_step[request.request_id] = self.steps
        self.queue.append(request)

    def _blocks_needed(self, request: ServeRequest) -> int:
        # Resident tokens peak at prompt + max_new - 1: the final sampled
        # token is returned but never written back to the pool.
        resident = request.prompt.size + request.max_new_tokens - 1
        return max(1, math.ceil(resident / self.serve_cfg.block_size))

    def _admit_one(self, slot_idx: int, completions: list[Completion]) -> bool:
        scfg = self.serve_cfg
        request = self.queue[0]
        blocks = self.allocator.allocate(self._blocks_needed(request))
        if blocks is None:
            return False  # page pressure: stay queued, FIFO (no overtake)
        self.queue.popleft()
        wait = self.steps - self._enqueued_step.pop(request.request_id, self.steps)
        self.max_wait_steps = max(self.max_wait_steps, wait)
        table = np.zeros(scfg.blocks_per_slot, np.int32)
        table[: len(blocks)] = blocks
        padded = np.zeros((1, scfg.prefill_len), np.int32)
        padded[0, : request.prompt.size] = request.prompt
        length = np.asarray(request.prompt.size, np.int32)
        if self.placement and self.placement.disaggregated:
            first, ks, vs = prefill_kv(
                self.cfg,
                self._prefill_params,
                padded,
                length,
                self._key,
                temperature=scfg.temperature,
            )
            # The KV handoff — the real cost of disaggregated serving.
            ks = jax.device_put(ks, self.placement.decode_devices[0])
            vs = jax.device_put(vs, self.placement.decode_devices[0])
            self.kv_transfer_bytes += int(ks.nbytes) + int(vs.nbytes)
            self.cache = scatter_prompt_kv(
                self.cache, ks, vs, length, jnp.asarray(table)
            )
        else:
            first, self.cache = paged_prefill(
                self.cfg,
                self.params,
                self.cache,
                padded,
                length,
                jnp.asarray(table),
                self._key,
                temperature=scfg.temperature,
            )
        self.prefills += 1
        self.admitted += 1
        now = self.clock()
        first_token = int(np.asarray(first))
        self._ttft_s.append(now - request.arrival_s)
        self.tokens_out += 1
        slot = _Slot(
            request=request,
            blocks=blocks,
            table=table,
            length=int(request.prompt.size),
            generated=[first_token],
            token_times=[now],
        )
        if request.max_new_tokens == 1:
            self._retire(slot, completions)
        else:
            self.slots[slot_idx] = slot
        return True

    def _retire(self, slot: _Slot, completions: list[Completion]) -> None:
        self.allocator.free(slot.blocks)
        self.completed += 1
        completions.append(
            Completion(
                request_id=slot.request.request_id,
                tokens=list(slot.generated),
                prompt_len=int(slot.request.prompt.size),
                arrival_s=slot.request.arrival_s,
                first_token_s=slot.token_times[0],
                finish_s=slot.token_times[-1],
                token_times_s=list(slot.token_times),
            )
        )

    # --- the step boundary ----------------------------------------------
    def step(self) -> list[Completion]:
        """One continuous-batching step: admit newcomers into free slots
        (prefill), then one batched decode for every active slot, then
        retire finished requests and recycle their pages."""
        completions: list[Completion] = []
        for i, slot in enumerate(self.slots):
            if not self.queue:
                break
            if slot is None and not self._admit_one(i, completions):
                break
        scfg = self.serve_cfg
        active_idx = [i for i, s in enumerate(self.slots) if s is not None]
        if active_idx:
            tokens = np.zeros(scfg.num_slots, np.int32)
            lengths = np.zeros(scfg.num_slots, np.int32)
            tables = np.zeros((scfg.num_slots, scfg.blocks_per_slot), np.int32)
            active = np.zeros(scfg.num_slots, bool)
            for i in active_idx:
                s = self.slots[i]
                tokens[i] = s.generated[-1]
                lengths[i] = s.length
                tables[i] = s.table
                active[i] = True
            nxt, self.cache = paged_decode_step(
                self.cfg,
                self.params,
                self.cache,
                tokens,
                lengths,
                tables,
                active,
                self._key,
                temperature=scfg.temperature,
            )
            nxt = np.asarray(nxt)
            now = self.clock()
            for i in active_idx:
                s = self.slots[i]
                s.length += 1
                s.generated.append(int(nxt[i]))
                self._itl_s.append(now - s.token_times[-1])
                s.token_times.append(now)
                self.tokens_out += 1
                if len(s.generated) >= s.request.max_new_tokens:
                    self._retire(s, completions)
                    self.slots[i] = None
        self.steps += 1
        return completions

    # --- introspection ---------------------------------------------------
    def pending(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def inflight_requests(self) -> list[ServeRequest]:
        """Queued + slotted requests — what a front-end must replay if
        this replica dies (completions already emitted are safe)."""
        out = [s.request for s in self.slots if s is not None]
        out.extend(self.queue)
        return out

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @staticmethod
    def _quantiles_ms(samples: list[float]) -> dict[str, float]:
        if not samples:
            return {}
        arr = np.asarray(samples, np.float64) * 1e3
        return {
            "p50": round(float(np.quantile(arr, 0.50)), 3),
            "p95": round(float(np.quantile(arr, 0.95)), 3),
            "p99": round(float(np.quantile(arr, 0.99)), 3),
            "max": round(float(arr.max()), 3),
        }

    def snapshot(self) -> dict:
        elapsed = self.clock() - self._started_at
        return {
            "replica": self.name,
            "steps": self.steps,
            "admitted": self.admitted,
            "completed": self.completed,
            "rejected": self.rejected,
            "active_slots": self.active_slots,
            "queue_depth": self.queue_depth,
            "tokens_out": self.tokens_out,
            "tokens_per_s": round(self.tokens_out / elapsed, 3)
            if elapsed > 0
            else 0.0,
            "ttft_ms": self._quantiles_ms(self._ttft_s),
            "itl_ms": self._quantiles_ms(self._itl_s),
            "free_blocks": self.allocator.free_blocks,
            "recycled_blocks": self.allocator.recycled,
            "max_wait_steps": self.max_wait_steps,
            "kv_transfer_bytes": self.kv_transfer_bytes,
            "disaggregated": bool(self.placement and self.placement.disaggregated),
        }

    def journal_metrics(self) -> dict:
        """Record the serve_metrics journal event the exporter folds into
        dlcfn_serve_* gauges (obs/exporter.py)."""
        snap = self.snapshot()
        if self.journal:
            from deeplearning_cfn_tpu.obs.recorder import get_recorder

            get_recorder().record("serve_metrics", **snap)
        return snap
