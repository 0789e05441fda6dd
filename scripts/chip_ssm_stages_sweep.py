"""Sweep of the Mamba-2 block's two elementwise stages' kernels
(`ops/pallas_ssm_stages.py`) on the chip, at the Nemotron cell's shapes: device
time of `_conv_silu_forward` / `_conv_silu_backward` by row tile, column tile
and rows a chunk of the body, and of `_gate_norm_forward` /
`_gate_norm_backward` by row tile and chunk, from a `jax.profiler` capture,
each with its share of the byte roofline (the operands read and the results
written once, over the HBM's peak); beside them the jnp stages
(`models/ssm_attn_moe.py` `_conv_silu`, `_gate_norm`) forward and backward on
the host's clock, and how far the kernels' value and gradients lie from theirs
on the chip.  `CONV_TILE`, `CONV_CHUNK`, `GATE_NORM_ROWS`, `GATE_NORM_CHUNK` in
the module are picked from its output.  Through chiprun; one JSON line a row,
the last line the best of each stage.

    chiprun -- python3 scripts/chip_ssm_stages_sweep.py
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

# batch, sequence, inner (heads x head size), groups, state, taps: one `M` block
# of nemotron-3-super-120b-a12b.train-s8192x1.
SHAPE = (1, 8192, 8192, 8, 128, 4)
EPS = 1e-5
CONV_TILES = tuple(itertools.product((512, 1024, 2048), (256, 512, 1024)))
CONV_CHUNKS = (None, 128, 32)  # None: the tile whole
GATE_NORM_ROWS = (256, 512, 1024)
GATE_NORM_CHUNKS = (None, 64, 32)
CALLS = 5
HBM_BYTES_PER_S = 819e9  # benchmarks/peaks.json, TPU v5 lite


def inputs(shape, seed: int = 0):
    """xBC, taps, bias and a cotangent; y, z, the norm's weight and a
    cotangent: normal, bfloat16 activations and taps, float32 bias and weight,
    as the cell's block holds them."""
    import jax
    import jax.numpy as jnp

    b, S, inner, G, N, taps = shape
    C = inner + 2 * G * N
    k = jax.random.split(jax.random.key(seed), 9)
    bf = jnp.bfloat16
    conv = (
        jax.random.normal(k[0], (b, S, C), bf), (0.5 * jax.random.normal(k[1], (taps, C))).astype(bf),
        0.1 * jax.random.normal(k[2], (C,)),
    )
    norm = (
        jax.random.normal(k[4], (b, S, inner), bf), jax.random.normal(k[5], (b, S, inner), bf),
        1.0 + 0.1 * jax.random.normal(k[6], (inner,)),
    )
    return conv, jax.random.normal(k[3], (b, S, C), bf), norm, jax.random.normal(k[7], (b, S, inner), bf)


def timed(run) -> float:
    import jax

    jax.block_until_ready(run())
    t0 = time.perf_counter()
    jax.block_until_ready([run() for _ in range(CALLS)])
    return 1e3 * (time.perf_counter() - t0) / CALLS


def kernel_ms(run, pattern: str) -> float | None:
    import jax

    from benchmarks import trace_reduce

    jax.block_until_ready(run())
    trace_dir = tempfile.mkdtemp(prefix="ssm_stages_sweep_")
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
        print("chip_ssm_stages_sweep: needs a TPU", file=sys.stderr)
        return 1
    from deeplearning_cfn_tpu.models.ssm_attn_moe import _conv_silu, _gate_norm
    from deeplearning_cfn_tpu.ops import pallas_ssm_stages as stages

    conv, g_conv, norm, g_norm = inputs(SHAPE)
    groups = SHAPE[3]
    f32 = jnp.float32
    an_array = lambda a: a.size * a.dtype.itemsize
    # A pass's least bytes: conv reads xBC and writes one like it, backward
    # reads xBC and g and writes dx; the norm reads y and z and writes one,
    # backward reads three and writes two.
    least_ms = {
        "conv_forward": 2 * an_array(conv[0]), "conv_backward": 3 * an_array(conv[0]),
        "gate_norm_forward": 3 * an_array(norm[0]), "gate_norm_backward": 5 * an_array(norm[0]),
    }
    least_ms = {k: 1e3 * v / HBM_BYTES_PER_S for k, v in least_ms.items()}
    share = lambda name, ms: None if ms is None else round(100 * least_ms[name] / ms, 2)

    # The jnp stages as the model calls them (each rematerialised by itself) and
    # the kernels through `jax.vjp`, on the host's clock; how far apart.
    pulled = lambda fn, g: jax.jit(lambda *a: (lambda y, pull: (y, *pull(g)))(*jax.vjp(fn, *a)))
    jnp_norm = jax.checkpoint(partial(_gate_norm, groups=groups, eps=EPS))
    forms = {
        "conv": (jax.checkpoint(_conv_silu), stages.conv_silu, conv, g_conv),
        "gate_norm": (jnp_norm, lambda y, z, w: stages.gate_norm(y, z, w, groups, EPS), norm, g_norm),
    }
    gap = lambda got, want: float(
        jnp.linalg.norm((got.astype(f32) - want.astype(f32)).ravel()) / jnp.linalg.norm(want.astype(f32).ravel())
    )
    for stage, (jnp_form, kernels, args, g) in forms.items():
        row = {"stage": stage, "shape": list(SHAPE), "least_ms": {k: v for k, v in least_ms.items() if k.startswith(stage)}}
        for name, fn in (("jnp", jnp_form), ("kernels", kernels)):
            row[f"{name}_forward_ms"] = timed(partial(jax.jit(fn), *args))
            row[f"{name}_forward_backward_ms"] = timed(partial(pulled(fn, g), *args))
        want, got = pulled(jnp_form, g)(*args), pulled(kernels, g)(*args)
        row["gap_to_jnp"] = [gap(a, b) for a, b in zip(got, want)]
        print(json.dumps(row, allow_nan=False), flush=True)

    def swept(stage, kernel, tiles, forward, backward) -> dict:
        """A row of the sweep: a kernel pair's ms a call and shares at `tiles`;
        a tile Mosaic refuses is a row too."""
        row = {"stage": stage, **tiles}
        try:
            for name, run in (("forward", forward), ("backward", backward)):
                row[f"{name}_ms"] = kernel_ms(run, rf"^_{kernel}_{name}")
                row[f"{name}_roofline"] = share(f"{stage}_{name}", row[f"{name}_ms"])
        except Exception as e:
            row["error"] = str(e)[:300]
        print(json.dumps(row, allow_nan=False), flush=True)
        return row

    rows_of = {"conv": [], "gate_norm": []}
    for (rows, cols), chunk in itertools.product(CONV_TILES, CONV_CHUNKS):
        kw = dict(tile=(rows, cols), chunk=chunk or rows, interpret=False)
        rows_of["conv"].append(swept(
            "conv", "conv_silu", {"rows": rows, "cols": cols, "chunk": min(chunk or rows, rows)},
            lambda: stages._conv_forward(*conv, **kw), lambda: stages._conv_backward(*conv, g_conv, **kw),
        ))
    for rows, chunk in itertools.product(GATE_NORM_ROWS, GATE_NORM_CHUNKS):
        kw = dict(groups=groups, eps=EPS, rows=rows, chunk=chunk or rows, interpret=False)
        rows_of["gate_norm"].append(swept(
            "gate_norm", "gate_norm", {"rows": rows, "chunk": min(chunk or rows, rows)},
            lambda: stages._gate_norm_forward(*norm, **kw),
            lambda: stages._gate_norm_backward(*norm, g_norm, **kw),
        ))
    # A block's step: the first forward pass, the rematerialised one, the backward.
    cost = lambda r: 2 * r["forward_ms"] + r["backward_ms"]
    done = {s: [r for r in rows if r.get("forward_ms") and r.get("backward_ms")] for s, rows in rows_of.items()}
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "best": {s: min(rows, key=cost) if rows else None for s, rows in done.items()},
    }, allow_nan=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
