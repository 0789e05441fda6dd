"""Sweep of the routed experts' grouped matmuls on the chip, at the shape of
one expert layer of the `glm-4.7-flash.train-s8192` cell: 16,384 tokens, top 4
of 64, 16 experts held (a buffer of 65,536 rows of which about a quarter hold
an assignment), d 2048, expert width 1536, bf16.  `GROUPED_MATMUL_TILES` in
`ops/moe.py` is picked from its output.

For each candidate (XLA's `ragged_dot`, the Pallas grouped matmul at several
tilings) the host clock's time of one forward and backward pass of the three
matmuls with their SwiGLU, and from a `jax.profiler` capture the device time of
the operations that took most of it.  Then the flash attention's forward
kernel by tile at both decoder cells' shapes (`DEFAULT_BLOCK_Q` /
`DEFAULT_BLOCK_K` in `ops/pallas_attention.py` are picked from that table),
and the backward kernels at the cell's 20 heads of 256.  `window` (run by name
only) is the three kernels under a sliding window at the shape of
`laguna-xs.2.train-s8192`'s window layers (64/8 heads of 128, S 8192, window
512) by tile, with the full-causal kernels at its full layers' 48 heads beside
them (`WINDOW_FWD_BLOCKS`, `WINDOW_BWD_DKV_BLOCKS`, `WINDOW_BWD_DQ_BLOCKS`), and
the forward's band step by its rows a chunk (`_BAND_ROW_CHUNK`).  `routing` (run by
name only) is the routed layer at the four routed cells' shapes, each read
from its cell's configuration and traffic (tokens x a token's slots x the
rows' width), at four shares of the buffer that hold an assignment (what the
cells run, 1.5% to 25%, and a deployment's full buffer).  First the
token-major pass alone (`ops/moe._rows_back` with the weights, as `combine`
runs it) by form: the whole gather into a [j, T, d] array, which is what the
layer runs, at a token's every choice and, where a token has more choices
than experts held, after the sort that puts its live slots first
(`_live_slots_first`); then the two forms PR 48 swept and did not take, the
pass tiled over tokens by the tokens of a tile and the inverse form that adds
the live row tiles into a float32 [T, d] accumulator.  Then one whole layer,
`ops/moe.routed_experts`, forward and backward: device time by the layer's
scopes from a capture.  In a tree whose `ops/moe.py` has no
`_live_slots_first` (copy this file there) it measures that tree's whole
gather and layer, which is how the parent is read beside the change.  `row_tile` (run by name only)
is the layer at the GLM cell's shape and both SwiGLU widths by the tile of its
passes over the sorted buffer (`ROW_TILE` in `ops/moe.py` is picked from it).
Through chiprun; one JSON line
per row.  Name a sweep to run it alone.

    chiprun -- python3 scripts/chip_grouped_matmul_sweep.py [experts] [attention] [routing] [row_tile] [window]
"""

from __future__ import annotations

import itertools
import json
import shutil
import sys
import tempfile
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "scripts"))

TOKENS, TOP_K, N_ROUTED, HELD, D, WIDTH = 16384, 4, 64, 16, 2048, 1536
# Tilings (rows, contraction, columns) that fit the kernel's default scoped
# VMEM; 1024 x 1024 x 1024 and anything 1536 wide do not (compiled for v5e).
TILES = (
    (512, 1024, 1024), (512, 512, 512), (256, 1024, 1024), (1024, 512, 512),
    (512, 1024, 512), (512, 512, 1024), (1024, 512, 1024), (256, 512, 512),
)
# (batch, seq, q heads, kv heads, head size): the attention of
# `mistral-7b-v0.3.train-s4096` and of `glm-4.7-flash.train-s8192`.
ATTENTION_SHAPES = ((2, 4096, 32, 8, 128), (2, 8192, 20, 20, 256))
FORWARD_BLOCKS = (512, 1024, 2048)  # q block and kv block, every combination
# The windowed sweep: the window layers of `laguna-xs.2.train-s8192`, and its
# full layers for the full-causal kernels beside them.
WINDOW_SHAPE, WINDOW, WINDOW_BLOCKS = (2, 8192, 64, 8, 128), 512, (256, 512, 1024)
WINDOW_FULL_SHAPE = (2, 8192, 48, 8, 128)
CALLS = 5
# The row-tile sweep: the expert widths of the GLM and the LFM2 cell, the tiles
# of the passes over the buffer, and the shares of the assignments held here
# (an expert-parallel rank of four holds a quarter on average; 1: every expert).
ROUTING_WIDTHS = (1536, 1792)
ROUTING_TILES = (2048, 4096, 8192, 16384)
ROUTING_SHARES = (0.12, 0.25, 0.5, 1.0)
ROUTING_SCOPES = ("router", "dispatch", "experts", "combine")
# The routing sweep: the cells whose configurations give the shapes, the
# shares of the buffer's rows that hold an assignment, and the tokens of a tile
# of the token-major pass's tiled form.
ROUTED_CELLS = (
    "lfm2-8b-a1b.train-s8192", "glm-4.7-flash.train-s8192", "laguna-xs.2.train-s8192",
    "nemotron-3-super-120b-a12b.train-s8192x1",
)
LIVE_SHARES = (0.015, 0.15, 0.25, 1.0)
TOKEN_TILES = (256, 512, 1024, 2048, 4096)


def top_operations(trace_dir: str, n: int = 6) -> list:
    import re

    from benchmarks import trace_reduce

    rows = trace_reduce.load_events(trace_dir)
    device = trace_reduce.devices(rows)[0]
    by_kind: dict[str, list] = {}
    for s, e, name in trace_reduce.op_intervals(rows, device):
        kind = re.sub(r"\.\d+$", "", trace_reduce.short_name(name))
        entry = by_kind.setdefault(kind, [0, 0])
        entry[0] += e - s
        entry[1] += 1
    top = sorted(by_kind.items(), key=lambda kv: -kv[1][0])[:n]
    return [[k, round(ns / 1e6 / CALLS, 3), c // CALLS] for k, (ns, c) in top]


def experts_sweep() -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops import moe

    keys = jax.random.split(jax.random.key(0), 6)
    rows_n = TOKENS * TOP_K
    rows = jax.random.normal(keys[0], (rows_n, D), jnp.bfloat16)
    w_gate, w_up = (
        jax.random.normal(k, (HELD, D, WIDTH), jnp.bfloat16) / D**0.5 for k in keys[1:3]
    )
    w_down = jax.random.normal(keys[3], (HELD, WIDTH, D), jnp.bfloat16) / WIDTH**0.5
    # Uniform routing: each assignment is held with probability 1/4.
    experts = jax.random.randint(keys[4], (rows_n,), 0, N_ROUTED)
    sizes = jnp.sum(experts[:, None] == jnp.arange(HELD)[None, :], axis=0, dtype=jnp.int32)
    held = int(jnp.sum(sizes))
    flops = 3 * 3 * 2 * held * D * WIDTH  # three matmuls, forward and twice backward

    def chain(kind):
        def loss(rows, w_gate, w_up, w_down):
            # As `routed_experts` chains them; the kernel's rows past the
            # last group's are uninitialised and stay out of the sum.
            mm = lambda a, w: moe.grouped_matmul(a, w, sizes, kind)
            out = mm(moe._swiglu_rows(mm(rows, w_gate), mm(rows, w_up), held), w_down)
            return jnp.sum(jnp.where((jnp.arange(rows_n) < held)[:, None], out, 0), dtype=jnp.float32)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    candidates = [("xla", None)] + [("pallas", t) for t in TILES]
    for kind, tiles in candidates:
        row = {"grouped_matmul": kind, "tiles": tiles, "held_rows": held, "rows": rows_n}
        if tiles:
            moe.GROUPED_MATMUL_TILES = tiles
        run = chain(kind)
        try:
            jax.block_until_ready(run(rows, w_gate, w_up, w_down))
        except Exception as e:  # a tiling Mosaic refuses is a row too
            print(json.dumps({**row, "error": str(e)[:300]}, allow_nan=False), flush=True)
            continue
        t0 = time.perf_counter()
        jax.block_until_ready([run(rows, w_gate, w_up, w_down) for _ in range(CALLS)])
        seconds = (time.perf_counter() - t0) / CALLS
        row["forward_backward_ms"] = 1e3 * seconds
        row["share_of_bf16_peak"] = flops / seconds / 197e12
        trace_dir = tempfile.mkdtemp(prefix="gmm_sweep_")
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready([run(rows, w_gate, w_up, w_down) for _ in range(CALLS)])
        row["top_operations_ms_and_calls"] = top_operations(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(row, allow_nan=False), flush=True)


def routing_input(share: float, tokens=TOKENS, dim=D, n_routed=N_ROUTED, held=HELD, base=0.25):
    """x [1, tokens, dim] whose first n_routed columns are the router's logits
    (the router is an identity on them): noise, and per token a push towards
    the held experts (as many choices held as can be), none (`base` of the
    buffer held) or away from them (none held), mixed so that `share` of the
    buffer's rows hold an assignment."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(int(share * 1000)), 3)
    towards = max(0.0, (share - base) / (1 - base))
    away = max(0.0, 1 - share / base)
    u = jax.random.uniform(keys[0], (tokens, 1))
    push = jnp.where(u < towards, 8.0, jnp.where(u > 1 - away, -8.0, 0.0))
    logits = jax.random.normal(keys[1], (tokens, n_routed)) + push * (jnp.arange(n_routed) < held)
    x = jax.random.normal(keys[2], (tokens, dim)).at[:, :n_routed].set(logits)
    return x.astype(jnp.bfloat16).reshape(1, tokens, dim)


def scope_ms(trace_dir: str) -> dict:
    """Device milliseconds a call under each of the layer's scopes, and in all."""
    from benchmarks import scope_reduce, trace_reduce

    rows = trace_reduce.load_events(trace_dir)
    device = trace_reduce.devices(rows)[0]
    names = scope_reduce.load_op_names(trace_dir, device)
    by_scope = {scope: [] for scope in ROUTING_SCOPES}
    every = []
    for start, end, operation in trace_reduce.op_intervals(rows, device):
        if trace_reduce.CONTAINER.match(operation):
            continue
        every.append((start, end))
        for scope in ROUTING_SCOPES:
            if scope_reduce.has_scope(names.get(operation, ""), scope):
                by_scope[scope].append((start, end))
    ms = lambda spans: round(trace_reduce.total(trace_reduce.union(spans)) / 1e6 / CALLS, 3)
    return {"all": ms(every), **{scope: ms(spans) for scope, spans in by_scope.items()}}


def device_ms(run, *args) -> dict:
    """`scope_ms` of CALLS calls of `run`, compiled and run once before."""
    import jax

    jax.block_until_ready(run(*args))
    trace_dir = tempfile.mkdtemp(prefix="routing_sweep_")
    with jax.profiler.trace(trace_dir):
        jax.block_until_ready([run(*args) for _ in range(CALLS)])
    ms = scope_ms(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return ms


def timed_layer(cfg, params, inputs: dict, row: dict, rows_dim: int | None = None) -> None:
    """One whole routed layer forward and backward on each of `inputs`
    ({share: x}): the statistics, the host clock's time and the device time by
    scope, a JSON line each.  `rows_dim`: the experts read and write the
    input's first columns alone (a latent's width)."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops import moe

    def loss(params, x):
        latent = None if rows_dim is None else x[..., :rows_dim]
        y, stats = moe.routed_experts(cfg, params, x, expert_rows=latent)
        stats = {k: v for k, v in stats.items() if k != "selected"}
        return jnp.sum(y.astype(jnp.float32) ** 2), stats

    run = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    for share, x in inputs.items():
        _, stats = jax.block_until_ready(run(params, x))
        out = {**row, "share": share, **{k: int(v) for k, v in stats.items()}}
        t0 = time.perf_counter()
        jax.block_until_ready([run(params, x) for _ in range(CALLS)])
        out["forward_backward_ms"] = round(1e3 * (time.perf_counter() - t0) / CALLS, 3)
        out["device_ms"] = device_ms(run, params, x)
        print(json.dumps(out, allow_nan=False), flush=True)


def row_tile_sweep() -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops import moe

    cfg = moe.RoutedConfig(n_routed=N_ROUTED, top_k=TOP_K, held=(0, HELD))
    inputs = {share: routing_input(share) for share in ROUTING_SHARES}
    tiles = ROUTING_TILES if hasattr(moe, "ROW_TILE") else (None,)
    for width, tile in itertools.product(ROUTING_WIDTHS, tiles):
        params = moe.init_routed_params(cfg, jax.random.key(0), D, width)
        params["router"] = jnp.eye(D, N_ROUTED, dtype=jnp.float32)
        if tile:
            moe.ROW_TILE = tile
        timed_layer(cfg, params, inputs, {"routing": width, "row_tile": tile})


def rows_back_tiled(rows, slot, held, weight, tile):
    """ISSUE 48's step 2, swept and not taken (PERF.md section 6, PR 48): the
    token-major pass in a loop over tiles of `tile` tokens, a tile's gather,
    product, select and float32 sum written into the result once; where the
    tile does not divide the tokens the last one overlaps the one before it."""
    import jax
    import jax.numpy as jnp

    T = slot.shape[0]
    slot, weight = slot.T, weight.T
    bits = jnp.finfo(rows.dtype)

    def body(i, y):
        start = jnp.minimum(i * tile, T - tile)
        at = jax.lax.dynamic_slice_in_dim(slot, start, tile, 1)
        by = jax.lax.dynamic_slice_in_dim(weight, start, tile, 1)
        picked = rows[jnp.minimum(at, rows.shape[0] - 1)]  # [j, tile, d]
        picked = jax.lax.reduce_precision(picked * by[..., None], bits.nexp, bits.nmant)
        picked = jnp.where((at < held)[..., None], picked, 0)
        summed = jnp.sum(picked, axis=0, dtype=jnp.float32).astype(rows.dtype)
        return jax.lax.dynamic_update_slice_in_dim(y, summed, start, 0)

    return jax.lax.fori_loop(0, -(-T // tile), body, jnp.zeros((T, rows.shape[1]), rows.dtype))


def rows_back_inverse(rows, token, held, row_weight, n_tokens):
    """ISSUE 48's candidate (a): the live row tiles, each row times its weight
    rounded to the rows' type, added into a float32 [T, d] accumulator at the
    row's token and cast once."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops import moe

    tile, live_tiles = moe._live_tiles(rows.shape[0], held)
    bits = jnp.finfo(rows.dtype)

    def body(i, acc):
        start = i * tile
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, tile)
        product = jax.lax.reduce_precision(
            cut(rows) * cut(row_weight)[:, None], bits.nexp, bits.nmant)
        live = (start + jnp.arange(tile) < held)[:, None]
        return acc.at[cut(token)].add(jnp.where(live, product, 0).astype(jnp.float32))

    acc = jnp.zeros((n_tokens, rows.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, live_tiles, body, acc).astype(rows.dtype)


def token_major_sweep(name: str, tokens: int, k: int, j: int, d: int) -> None:
    """The token-major pass alone at one cell's shape, by form, tile and live
    share: a random permutation of the buffer's rows as the tokens' slots (a
    token's k - j choices that cannot be held here point past the buffer, in
    columns drawn anew for each token), device time a call."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops import moe

    keys = jax.random.split(jax.random.key(tokens + k), 5)
    n_rows = tokens * j
    rows = jax.random.normal(keys[0], (n_rows, d), jnp.bfloat16)
    narrow = jax.random.permutation(keys[1], n_rows).astype(jnp.int32).reshape(tokens, j)
    beyond = n_rows + jnp.arange(tokens * (k - j), dtype=jnp.int32).reshape(tokens, k - j)
    columns = jnp.argsort(jax.random.uniform(keys[2], (tokens, k)), axis=1)
    wide = jnp.take_along_axis(jnp.concatenate([narrow, beyond], axis=1), columns, axis=1)
    weight = jax.random.uniform(keys[3], (tokens, k), jnp.float32).astype(jnp.bfloat16)
    # the buffer's order: the row's token, and its weight
    token = (jnp.argsort(narrow.reshape(-1)) // j).astype(jnp.int32)
    row_weight = jax.random.uniform(keys[4], (n_rows,), jnp.float32).astype(jnp.bfloat16)
    row = {"token_major": name, "tokens": tokens, "choices": k, "slots": j, "width": d}

    def emit(form, run, *args, **more):
        """A row for each live share: `run(*args)` with the count where an
        argument is None."""
        for share in LIVE_SHARES:
            held = jnp.asarray(int(share * n_rows), jnp.int32)
            out = {**row, "form": form, "share": share, **more}
            try:
                out["device_ms"] = device_ms(run, *(held if a is None else a for a in args))["all"]
            except Exception as e:  # a form the compiler refuses is a row too
                out["error"] = str(e)[:300]
            print(json.dumps(out, allow_nan=False), flush=True)

    whole = jax.jit(moe._rows_back)
    emit("whole", whole, rows, wide, None, weight, slots_read=tokens * k)
    if not hasattr(moe, "_live_slots_first"):
        return
    slot, by = wide, weight
    if k > j:
        first = jax.jit(lambda s: moe._live_slots_first(s, j))
        emit("live_slots_first", first, wide)
        slot, by = first(wide), weight[:, :j]
        emit("whole_after_sort", whole, rows, slot, None, by, slots_read=tokens * j)
    for tile in TOKEN_TILES:
        emit("tiled", jax.jit(partial(rows_back_tiled, tile=min(tile, tokens))), rows, slot, None, by,
             token_tile=tile, slots_a_gather=tile * j, slots_read=tokens * j)
    inverse = jax.jit(rows_back_inverse, static_argnums=4)
    emit("inverse", inverse, rows, token, None, row_weight, tokens)


def routing_sweep() -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.manifest import Manifest
    from deeplearning_cfn_tpu.ops import moe

    manifest = Manifest()
    for name in ROUTED_CELLS:
        (cell,) = [w for w in manifest.data["workloads"] if w["name"] == name]
        config, traffic = manifest.config(cell["config"]), manifest.json("traffic", cell["traffic"])
        model = manifest.module("builders", config["kind"]).model_config(config)
        cfg, tokens = model.routed, traffic["global_batch"] * traffic["seq_len"]
        count = cfg.span[1]
        j = min(cfg.top_k, count)
        d = getattr(model, "latent_dim", model.dim)  # the rows the experts read and write
        token_major_sweep(name, tokens, cfg.top_k, j, d)
        # the layer alone: no shared expert, the held span from 0, an identity router
        cfg = moe.RoutedConfig(n_routed=cfg.n_routed, top_k=cfg.top_k, held=(0, count),
                               expert=cfg.expert)
        params = moe.init_routed_params(cfg, jax.random.key(0), model.dim, model.expert_dim,
                                        rows_dim=d)
        params["router"] = jnp.eye(model.dim, cfg.n_routed, dtype=jnp.float32)
        base = cfg.top_k * count / (cfg.n_routed * j)  # of the buffer, at a uniform router
        inputs = {share: routing_input(share, tokens, model.dim, cfg.n_routed, count, base)
                  for share in LIVE_SHARES}
        timed_layer(cfg, params, inputs, {"routing": name},
                    rows_dim=d if d != model.dim else None)


def pair_counts(seq: int, block_q: int, block_k: int, window: int | None = None) -> dict:
    """How many (q block, kv block) pairs of one causal (batch, head) are
    skipped, run under the mask and run without it: `_run_pair`'s rule at a
    sequence of whole blocks.  Under a window `grid_steps` is what the
    shortened grid walks (q blocks times the widest band's kv blocks)."""
    counts = {"skipped": 0, "masked": 0, "unmasked": 0}
    for q_start, k_start in itertools.product(
        range(0, seq, block_q), range(0, seq, block_k)
    ):
        behind = window is not None and k_start + block_k - 1 <= q_start - window
        crossed = window is not None and k_start <= q_start + block_q - 1 - window
        if k_start > q_start + block_q - 1 or behind:
            counts["skipped"] += 1
        elif k_start + block_k - 1 > q_start or crossed:
            counts["masked"] += 1
        else:
            counts["unmasked"] += 1
    if window is not None:
        from deeplearning_cfn_tpu.ops.pallas_attention import _kv_steps

        nq, nk = seq // block_q, seq // block_k
        counts["grid_steps"] = nq * _kv_steps(nq, nk, block_q, block_k, window)
    return counts


def forward_sweep(shape, window: int | None = None, blocks=FORWARD_BLOCKS) -> None:
    """The forward kernel by tile; under a window each row also says how many
    grid steps of a call run a pair, the time a step, and what share of the
    call's time its tile matmuls (both, every score of the tile) would take at
    the chip's peak.  Then the band step (`_window_flash_forward_band`, PR 36)
    by its rows a chunk, the same columns: one step a q block of `window` rows."""
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from deeplearning_cfn_tpu.ops import pallas_attention as pa

    B, S, H, KV, hd = shape
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (B, S, KV, hd), jnp.bfloat16) for kk in keys[1:])
    best = None
    kernel = r"^_flash_forward" if window is None else r"^_window_flash_forward"

    def timed(row, run, steps=None, tile_scores=None):
        """Print `row` with the kernel's device time a call; `steps`: the grid
        steps of a call that run a pair, `tile_scores`: the scores they compute."""
        nonlocal best
        try:
            jax.block_until_ready(run())
        except Exception as e:
            print(json.dumps({**row, "error": str(e)[:300]}, allow_nan=False), flush=True)
            return
        trace_dir = tempfile.mkdtemp(prefix="fwd_sweep_")
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready([run() for _ in range(CALLS)])
        rows = trace_reduce.load_events(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds, calls = trace_reduce.kernel_seconds(rows, trace_reduce.devices(rows)[0], kernel)
        row["forward_ms"] = 1e3 * seconds / calls if calls else None
        if calls and steps:
            row["live_steps"] = steps
            row["us_a_step"] = 1e6 * seconds / calls / steps
            row["matmuls_at_peak_share"] = 2 * 2 * hd * tile_scores / 197e12 / (seconds / calls)
        print(json.dumps(row, allow_nan=False), flush=True)
        if calls and (best is None or row["forward_ms"] < best["forward_ms"]):
            best = row

    for bq, bk in itertools.product(blocks, repeat=2):
        row = {"attention_forward": list(shape), "window": window, "block_q": bq, "block_k": bk}
        row.update(pair_counts(S, bq, bk, window))
        run = lambda: pa._flash_forward(q, k, v, True, hd**-0.5, bq, bk, False, window=window)
        steps = B * H * (row["masked"] + row["unmasked"]) if window else None
        timed(row, run, steps, steps and steps * bq * bk)
    if window and hasattr(pa, "_band_forward"):
        swept = pa._BAND_ROW_CHUNK
        for chunk in (128, 256, window):
            # the module's constant, read when the call is traced
            pa._BAND_ROW_CHUNK = chunk
            pa._band_forward.clear_cache()
            row = {"attention_forward": list(shape), "window": window, "band": True,
                   "block_q": window, "rows_a_chunk": chunk}
            steps = B * H * (S // window)
            # a step's chunks of C rows see W + C columns each, the first block's half of that
            scores = B * H * (S // window - 0.5) * window * (window + chunk)
            timed(row, lambda: pa._band_forward(q, k, v, hd**-0.5, window, False), steps, scores)
        pa._BAND_ROW_CHUNK = swept
        pa._band_forward.clear_cache()
    print(json.dumps({"best_forward": best}, allow_nan=False), flush=True)


def attention_sweep() -> None:
    import chip_attention_backward_sweep as backward

    for shape in ATTENTION_SHAPES:
        forward_sweep(shape)
    blocks = ((512, 512), (512, 1024), (1024, 512), (1024, 1024))
    backward.sweep(ATTENTION_SHAPES[1], blocks)


def window_sweep() -> None:
    import chip_attention_backward_sweep as backward

    forward_sweep(WINDOW_SHAPE, WINDOW, WINDOW_BLOCKS)
    backward.sweep(WINDOW_SHAPE, tuple(itertools.product(WINDOW_BLOCKS, repeat=2)), WINDOW)
    # the full layers' kernels at their own tiles, for the time beside them
    forward_sweep(WINDOW_FULL_SHAPE, None, (1024,))
    backward.sweep(WINDOW_FULL_SHAPE, ((1024, 1024),))


def main(argv: list[str]) -> int:
    import jax

    sweeps = {"experts": experts_sweep, "attention": attention_sweep, "routing": routing_sweep,
              "row_tile": row_tile_sweep, "window": window_sweep}
    if jax.devices()[0].platform != "tpu":
        print("chip_grouped_matmul_sweep: needs a TPU", file=sys.stderr)
        return 1
    for name in argv or ("experts", "attention"):
        sweeps[name]()
    print(json.dumps({"device": jax.devices()[0].device_kind}, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
