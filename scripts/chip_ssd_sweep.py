"""Sweep of the state-space scan's kernels (`ops/pallas_ssd.py`) on the chip, at
the Nemotron cell's shapes: device time of `_ssd_forward` (with and without the
saved states) and `_ssd_backward` a call, from a `jax.profiler` capture, by
heads a grid step and chunks a grid step; beside them the XLA form
(`ops/ssd.ssd`) forward and backward on the host's clock, and how far the
kernels' value and gradients lie from the XLA form's on the chip.
`HEADS_A_STEP` / `CHUNKS_A_STEP` in `ops/pallas_ssd.py` are picked from its
output.  Through chiprun; one JSON line a row, the last line the best.

    chiprun -- python3 scripts/chip_ssd_sweep.py
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

# batch, sequence, heads, head size, groups, state, chunk: one `M` block of
# nemotron-3-super-120b-a12b.train-s8192x1.
SHAPE = (1, 8192, 128, 64, 8, 128, 128)
HEADS_A_STEP = (16, 8, 4)
CHUNKS_A_STEP = (1, 2)
CALLS = 5


def inputs(shape, seed: int = 0):
    """x, dt, A, B, C, D and dy as a trained block sees them: dt log-uniform in
    [1e-3, 1e-1], A in [-16, -1], the rest normal, bfloat16 operands."""
    import jax
    import jax.numpy as jnp

    b, S, H, P, G, N, _ = shape
    k = jax.random.split(jax.random.key(seed), 7)
    bf = jnp.bfloat16
    x, dy = (jax.random.normal(kk, (b, S, H, P), bf) for kk in (k[0], k[6]))
    dt = jnp.exp(jax.random.uniform(k[1], (b, S, H), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    A = -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0)
    B, C = (jax.random.normal(kk, (b, S, G, N), bf) for kk in (k[3], k[4]))
    return (x, dt, A, B, C, jnp.ones((H,), jnp.float32)), dy


def timed(run) -> float:
    import jax

    jax.block_until_ready(run())
    t0 = time.perf_counter()
    jax.block_until_ready([run() for _ in range(CALLS)])
    return 1e3 * (time.perf_counter() - t0) / CALLS


def kernel_ms(run, pattern: str) -> float | None:
    import jax

    from benchmarks import trace_reduce

    trace_dir = tempfile.mkdtemp(prefix="ssd_sweep_")
    with jax.profiler.trace(trace_dir):
        jax.block_until_ready([run() for _ in range(CALLS)])
    rows = trace_reduce.load_events(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    seconds, calls = trace_reduce.kernel_seconds(rows, trace_reduce.devices(rows)[0], pattern)
    return 1e3 * seconds / calls if calls else None


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("chip_ssd_sweep: needs a TPU", file=sys.stderr)
        return 1
    from deeplearning_cfn_tpu.ops import pallas_ssd as ps
    from deeplearning_cfn_tpu.ops.ssd import ssd as xla_ssd

    args, dy = inputs(SHAPE)
    chunk = SHAPE[-1]
    x, dt, A, B, C, D = args
    b, S, H, P = x.shape
    dt_rows = dt.transpose(0, 2, 1)
    cs = jnp.cumsum((dt_rows * A[:, None]).reshape(b, H, S // chunk, chunk), axis=-1).reshape(b, H, S)
    flat = (x.reshape(b, S, H * P), dt_rows, cs, B, C, D)
    f32 = jnp.float32

    # The XLA form and the kernels through `jax.vjp`, host clock; how far apart.
    pulled = lambda fn: jax.jit(lambda *a: (lambda y, pull: (y, *pull(dy)))(*jax.vjp(fn, *a)))
    both = {"xla": pulled(lambda *a: xla_ssd(*a, chunk)), "kernels": pulled(lambda *a: ps.ssd(*a, chunk))}
    forward = {"xla": jax.jit(lambda *a: xla_ssd(*a, chunk)), "kernels": jax.jit(lambda *a: ps.ssd(*a, chunk))}
    row = {"shape": list(SHAPE)}
    for name in both:
        row[f"{name}_forward_ms"] = timed(lambda: forward[name](*args))
        row[f"{name}_forward_backward_ms"] = timed(lambda: both[name](*args))
    want, got = both["xla"](*args), both["kernels"](*args)
    gap = lambda g, w: float(jnp.linalg.norm((g.astype(f32) - w.astype(f32)).ravel())
                             / jnp.linalg.norm(w.astype(f32).ravel()))
    row["gap_to_xla"] = {n: gap(g, w) for n, g, w in zip(("y", "x", "dt", "A", "B", "C", "D"), got, want)}
    print(json.dumps(row, allow_nan=False), flush=True)

    rows_out = []
    for hs, a_step in itertools.product(HEADS_A_STEP, CHUNKS_A_STEP):
        kw = dict(chunk=chunk, interpret=False, heads_a_step=hs, chunks_a_step=a_step)
        row = {"heads_a_step": hs, "chunks_a_step": a_step}
        try:
            _, before = ps._forward(*flat, save_states=True, **kw)
            row["forward_saving_ms"] = kernel_ms(
                lambda: ps._forward(*flat, save_states=True, **kw), r"^_ssd_forward"
            )
            row["forward_ms"] = kernel_ms(
                lambda: ps._forward(*flat, save_states=False, **kw)[0], r"^_ssd_forward"
            )
            run = lambda: ps._backward(*flat, before, dy.reshape(b, S, H * P), **kw)
            row["backward_ms"] = kernel_ms(run, r"^_ssd_backward")
            row["backward_host_ms"] = timed(run)  # with the XLA that sums its outputs
        except Exception as e:  # a tile Mosaic refuses is a row of the sweep too
            row["error"] = str(e)[:300]
        rows_out.append(row)
        print(json.dumps(row, allow_nan=False), flush=True)
    done = [r for r in rows_out if "error" not in r]
    # A block's step: the first forward pass, the rematerialised one, the backward.
    cost = lambda r: r["forward_ms"] + r["forward_saving_ms"] + r["backward_ms"]
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "best": min(done, key=cost) if done else None,
    }, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
