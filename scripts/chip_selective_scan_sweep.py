"""Sweep of the selective scan's kernels (`ops/pallas_selective_scan.py`) on the
chip, at the Jamba cell's shapes: device time of `_selective_scan_forward`
(with and without the saved states) and `_selective_scan_backward` a call, from
a `jax.profiler` capture, by channels a grid step, tokens a grid step and tokens
a loop iteration; beside them the two forms XLA offers, forward and backward on
the host's clock (`ops/selective_scan.selective_scan`, a `lax.scan` over the
tokens, and a `lax.associative_scan` inside chunks of 256 written here), and how
far the kernels' value and gradients lie from the `lax.scan` form's on the chip.
`CHANNELS_A_STEP` / `CHUNK` / `GROUP` in `ops/pallas_selective_scan.py` are
picked from its output.  Through chiprun; one JSON line a row, the last line
the best.

    chiprun -- python3 scripts/chip_selective_scan_sweep.py
"""

from __future__ import annotations

import itertools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from scripts.chip_ssd_sweep import kernel_ms, timed  # noqa: E402

# batch, sequence, channels, states: one Mamba layer of jamba2-3b.train-s8192x1.
SHAPE = (1, 8192, 5120, 16)
CHANNELS_A_STEP = (256, 640, 1280, 2560)
CHUNK = (128, 64)
GROUP = (16, 8)
ASSOCIATIVE_CHUNK = 256


def inputs(shape, seed: int = 0):
    """x, dt, A, B, C, D and dy as a trained layer sees them: dt log-uniform in
    [1e-3, 1e-1], A[c, n] = -(n + 1), the rest normal, bfloat16 operands."""
    import jax
    import jax.numpy as jnp

    b, S, I, N = shape
    k = jax.random.split(jax.random.key(seed), 6)
    bf = jnp.bfloat16
    x, dy = (jax.random.normal(kk, (b, S, I), bf) for kk in (k[0], k[5]))
    dt = jnp.exp(jax.random.uniform(k[1], (b, S, I), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (I, N))
    B, C = (jax.random.normal(kk, (b, S, N), bf) for kk in (k[2], k[3]))
    return (x, dt, A, B, C, jnp.ones((I,), jnp.float32)), dy


def associative(x, dt, A, B, C, D):
    """The recurrence by `lax.associative_scan` inside chunks of
    `ASSOCIATIVE_CHUNK` tokens, the chunks in a `lax.scan`: the other form XLA
    offers, written here to be read on the chip once (ISSUE 47)."""
    import jax
    import jax.numpy as jnp

    b, S, I = x.shape
    f32 = jnp.float32
    nc = S // ASSOCIATIVE_CHUNK
    parts = lambda a: jnp.moveaxis(a.astype(f32).reshape(b, nc, ASSOCIATIVE_CHUNK, -1), 1, 0)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    @jax.checkpoint
    def chunk(h, c):
        xc, dtc, Bc, Cc = c  # [b, Q, I], [b, Q, I], [b, Q, N], [b, Q, N]
        a = jnp.exp(dtc[..., None] * A)
        u = (dtc * xc)[..., None] * Bc[:, :, None, :]
        decay, own = jax.lax.associative_scan(combine, (a, u), axis=1)
        hs = decay * h[:, None] + own
        return hs[:, -1], jnp.einsum("bqin,bqn->bqi", hs, Cc) + D * xc

    _, y = jax.lax.scan(chunk, jnp.zeros((b, I, A.shape[1]), f32), tuple(parts(a) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, S, I).astype(x.dtype)


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("chip_selective_scan_sweep: needs a TPU", file=sys.stderr)
        return 1
    from deeplearning_cfn_tpu.ops import pallas_selective_scan as ps
    from deeplearning_cfn_tpu.ops.selective_scan import selective_scan as plain

    args, dy = inputs(SHAPE)
    x, dt, A, B, C, D = args
    f32 = jnp.float32

    # The two XLA forms and the kernels through `jax.vjp`, host clock; how far apart.
    forms = {"lax_scan": plain, "associative_scan": associative, "kernels": ps.selective_scan}
    pulled = lambda fn: jax.jit(lambda *a: (lambda y, pull: (y, *pull(dy)))(*jax.vjp(fn, *a)))
    row, results = {"shape": list(SHAPE)}, {}
    for name, fn in forms.items():
        try:
            forward, both = jax.jit(fn), pulled(fn)
            row[f"{name}_forward_ms"] = timed(lambda: forward(*args))
            row[f"{name}_forward_backward_ms"] = timed(lambda: both(*args))
            results[name] = both(*args)
        except Exception as e:  # a form that does not fit is a reading too
            row[f"{name}_error"] = str(e)[:300]
    gap = lambda g, w: float(jnp.linalg.norm((g.astype(f32) - w.astype(f32)).ravel())
                             / jnp.linalg.norm(w.astype(f32).ravel()))
    if "lax_scan" in results and "kernels" in results:
        row["gap_to_lax_scan"] = {
            n: gap(g, w)
            for n, g, w in zip(("y", "x", "dt", "A", "B", "C", "D"), results["kernels"], results["lax_scan"])
        }
    results.clear()
    print(json.dumps(row, allow_nan=False), flush=True)

    flat = (x, dt, A.T, B, C, D[None])
    rows_out = []
    for width, chunk, group in itertools.product(CHANNELS_A_STEP, CHUNK, GROUP):
        kw = dict(interpret=False, chunk=chunk, group=group, channels_a_step=width)
        row = {"channels_a_step": width, "chunk": chunk, "group": group}
        try:
            _, before = ps._forward(*flat, save_states=True, **kw)
            row["forward_saving_ms"] = kernel_ms(
                lambda: ps._forward(*flat, save_states=True, **kw), r"^_selective_scan_forward"
            )
            row["forward_ms"] = kernel_ms(
                lambda: ps._forward(*flat, save_states=False, **kw)[0], r"^_selective_scan_forward"
            )
            run = lambda: ps._backward(*flat, before, dy, **kw)
            row["backward_ms"] = kernel_ms(run, r"^_selective_scan_backward")
            row["backward_host_ms"] = timed(run)  # with the XLA that spreads B, C and sums
        except Exception as e:  # a tile Mosaic refuses is a row of the sweep too
            row["error"] = str(e)[:300]
        rows_out.append(row)
        print(json.dumps(row, allow_nan=False), flush=True)
    done = [r for r in rows_out if "error" not in r]
    # A layer's step: the first forward pass, the rematerialised one, the backward.
    cost = lambda r: r["forward_ms"] + r["forward_saving_ms"] + r["backward_ms"]
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "best": min(done, key=cost) if done else None,
    }, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
