"""Block sweep of the flash attention's backward kernels on the chip: device
time of `_flash_backward_dkv` and `_flash_backward_dq` per call, and of the
fused kernel `_flash_backward_fused` beside them, from a `jax.profiler` capture
of each (q block, kv block), and the host clock's time of each form's whole
backward (the kernels, `delta`, the transposes around them).
`BWD_DKV_BLOCKS` / `BWD_DQ_BLOCKS` / `BWD_FUSED_BLOCKS` and the fused form's
rule (`_takes_fused_backward`) in `ops/pallas_attention.py` are picked from its
output.  Through chiprun; one JSON line per configuration, the last line is the
best of each kernel per shape.

    chiprun -- python3 scripts/chip_attention_backward_sweep.py [shape ...]
"""

from __future__ import annotations

import itertools
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# (batch, seq, q heads, kv heads, head dim): the decoder cells' full-causal
# calls, and the GQA 16/4 at d64 of chip_smoke.py's cases.
SHAPES = {
    "mistral": (2, 4096, 32, 8, 128),
    "ouro": (1, 8192, 16, 16, 128),
    "glm": (2, 8192, 20, 20, 256),
    "lfm2": (2, 8192, 32, 8, 64),
    "laguna-full": (2, 8192, 48, 8, 128),
    "nemotron": (1, 8192, 32, 2, 128),
    "d64-s2048": (1, 2048, 16, 4, 64),
}
# (q block, kv block); tiles of 256 ran the pair at half the speed (PR 25)
BLOCKS = tuple(itertools.product((512, 1024), repeat=2))
CALLS = 5
KERNELS = ("dkv", "dq", "fused")


def sweep(shape, blocks, window: int | None = None) -> list[dict]:
    """`window`: the windowed kernels (`_window_flash_backward_*`) under that
    window, their forward at `WINDOW_FWD_BLOCKS`."""
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from deeplearning_cfn_tpu.ops import pallas_attention as pa

    B, S, Hq, Hkv, D = shape
    keys = jax.random.split(jax.random.key(S + Hq), 4)
    q, dout = (jax.random.normal(k, (B, S, Hq, D), jnp.bfloat16) for k in keys[:2])
    k, v = (jax.random.normal(k, (B, S, Hkv, D), jnp.bfloat16) for k in keys[2:])
    scale = D**-0.5
    forward = (pa.DEFAULT_BLOCK_Q, pa.DEFAULT_BLOCK_K) if window is None else pa.WINDOW_FWD_BLOCKS
    out, lse = pa._flash_forward(
        q, k, v, True, scale, pa._clamp_block(forward[0], S),
        pa._clamp_block(forward[1], S), False, window=window,
    )
    prefix = "_flash_backward" if window is None else "_window_flash_backward"
    rows_out = []
    for bq, bk in blocks:
        tiles = (pa._clamp_block(bq, S), pa._clamp_block(bk, S))
        forms = {"backward_ms": lambda: pa._flash_backward(
            q, k, v, out, lse, dout, True, scale, tiles, tiles, False, window=window
        )}
        if window is None:  # the fused form at any shape, the rule's answer beside it
            forms["fused_backward_ms"] = lambda: pa._flash_backward_fused(
                q, k, v, out, lse, dout, True, scale, tiles, False
            )
        row = {
            "shape": list(shape), "window": window, "block_q": tiles[0], "block_k": tiles[1],
            "takes_fused": pa._takes_fused_backward(window, Hq // Hkv, S, D),
            **{f"{kernel}_ms": None for kernel in KERNELS},
        }
        for form, run in forms.items():
            try:
                jax.block_until_ready(run())
            except Exception as e:  # a tile Mosaic refuses is a row of the sweep too
                row[form.replace("_ms", "_error")] = str(e)[:300]
                continue
            t0 = time.perf_counter()
            jax.block_until_ready([run() for _ in range(CALLS)])
            row[form] = 1e3 * (time.perf_counter() - t0) / CALLS
            trace_dir = tempfile.mkdtemp(prefix="bwd_sweep_")
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready([run() for _ in range(CALLS)])
            rows = trace_reduce.load_events(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            device = trace_reduce.devices(rows)[0]
            for kernel in KERNELS:
                seconds, calls = trace_reduce.kernel_seconds(rows, device, rf"^{prefix}_{kernel}")
                if calls:
                    row[f"{kernel}_ms"] = 1e3 * seconds / calls
        if row.get("backward_ms") and row.get("fused_backward_ms"):
            # dq, dk, dv of the two forms on the chip: the largest difference
            row["fused_gap"] = [
                float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(forms["backward_ms"](), forms["fused_backward_ms"]())
            ]
        rows_out.append(row)
        print(json.dumps(row, allow_nan=False), flush=True)
    return rows_out


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("chip_attention_backward_sweep: needs a TPU", file=sys.stderr)
        return 1
    best = {}
    for name in sys.argv[1:] or SHAPES:
        rows = sweep(SHAPES[name], BLOCKS)
        best[name] = {
            kernel: min(
                ({"block_q": r["block_q"], "block_k": r["block_k"], "ms": r[f"{kernel}_ms"]}
                 for r in rows if r[f"{kernel}_ms"]),
                key=lambda r: r["ms"], default=None,
            )
            for kernel in KERNELS
        }
    print(json.dumps({"device": jax.devices()[0].device_kind, "best": best}, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
