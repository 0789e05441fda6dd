"""Measure cross-iteration fusion at the ResNet-50 bench shape.

The last named lever against the documented HBM ceiling
(docs/BENCH_NOTES.md): put k consecutive training iterations inside ONE
compiled program (Trainer.multi_step_fn — the only form of
cross-iteration fusion XLA can express; separate dispatches are separate
executables) and compare per-step wallclock and cost-model bytes against
the single-step program.  Any cross-iteration reuse XLA can schedule
(param re-reads, optimizer-state traffic) shows up as fewer
bytes-per-step and/or faster steps; if bytes/step are identical the
lever is structurally dead for this workload.

Run on the real chip: PYTHONPATH=.:$PYTHONPATH python scripts/chip_resnet_multistep.py
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache
from deeplearning_cfn_tpu.models.resnet import ResNet50
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

enable_compile_cache()

BATCH = 128
SIZE = 224
WARM, MEAS = 3, 10


def make_trainer():
    mesh = build_mesh(MeshSpec.data_parallel(len(jax.devices())))
    return Trainer(
        ResNet50(dtype=jnp.bfloat16),
        mesh,
        TrainerConfig(
            strategy="dp", learning_rate=0.1, has_train_arg=True,
            label_smoothing=0.1,
        ),
    )


def measure(k: int) -> dict:
    trainer = make_trainer()
    rng = np.random.default_rng(0)
    x1 = jnp.asarray(
        rng.standard_normal((BATCH, SIZE, SIZE, 3)), jnp.bfloat16
    )
    y1 = jnp.asarray(rng.integers(0, 1000, size=BATCH), jnp.int32)
    state = trainer.init(jax.random.key(0), x1)
    with jax.set_mesh(trainer.mesh):
        if k == 1:
            fn = trainer.step_fn
            args = (
                jax.device_put(x1, trainer.batch_sharding),
                jax.device_put(y1, trainer.batch_sharding),
            )
        else:
            fn = trainer.multi_step_fn(k)
            # Distinct data per scan slice: identical slices could in
            # principle be exploited (aliased broadcast buffers), which
            # would flatter the measurement.
            xs = jnp.asarray(
                rng.standard_normal((k, BATCH, SIZE, SIZE, 3)), jnp.bfloat16
            )
            ys = jnp.asarray(
                rng.integers(0, 1000, size=(k, BATCH)), jnp.int32
            )
            args = (xs, ys)
        lowered = fn.lower(state, *args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        for _ in range(WARM):
            state, out = fn(state, *args)
        # The readback ends the warm-up before the timed window opens.
        float(np.asarray(jax.device_get(out))[-1] if k > 1 else out["loss"])
        t0 = time.perf_counter()
        for _ in range(MEAS):
            state, out = fn(state, *args)
        float(np.asarray(jax.device_get(out))[-1] if k > 1 else out["loss"])
        dt = time.perf_counter() - t0
    steps = MEAS * k
    return {
        "k": k,
        "ms_per_step": round(1000 * dt / steps, 2),
        "images_per_sec": round(BATCH * steps / dt, 1),
        # cost_analysis counts a scan BODY once regardless of trip count,
        # so for k>1 this is (approximately) the per-iteration traffic
        # directly — equal numbers across k mean XLA found no
        # cross-iteration byte reuse.
        "cost_bytes_per_iter": (
            round(cost["bytes accessed"] / 1e9, 2)
            if "bytes accessed" in cost
            else None
        ),
        "cost_flops_per_iter": (
            round(cost["flops"] / 1e12, 3) if "flops" in cost else None
        ),
    }


if __name__ == "__main__":
    for k in (1, 2, 4):
        print(json.dumps(measure(k), allow_nan=False))
