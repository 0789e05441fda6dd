"""Multi-process SPMD smoke — a CPU-only aid for the distributed backend.

It starts N OS processes on one machine, which is right for virtual CPU
devices and wrong for a TPU host: a chip belongs to one process at a
time, so N processes cannot share the chips of one host (one process
drives all of them — that is what ``chip_smoke.py`` runs).  On real
hardware the same contract applies with one process per worker VM.

The reference's distributed story is only exercised end-to-end by an
actual cluster run (mpirun over the hostfile, run.sh:70-95).  This module
is the framework's stand-in for that, runnable without a cluster: N OS
processes (one per "worker VM") join a `jax.distributed` cluster using
exactly the env contract the discovery agent publishes
(DEEPLEARNING_WORKERS_COUNT / DEEPLEARNING_COORDINATOR / DLCFN_PROCESS_ID,
contract.py:env), build ONE global mesh spanning every process's devices,
and run synchronous data-parallel training where the gradient psum crosses
the process boundary — the collective that NCCL ring-allreduce provided in
the reference.

Each process feeds only its local shard of the global batch
(`jax.make_array_from_process_local_data`), mirroring per-rank dataset
sharding.  All processes print the same loss sequence or the run is
broken; the caller (tests/test_multiprocess.py, or an operator on a real
slice) asserts agreement + decrease.

Run (per worker): JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4
  DEEPLEARNING_WORKERS_COUNT=2 DLCFN_PROCESS_ID=<i>
  DEEPLEARNING_COORDINATOR=127.0.0.1:9911
  python -m deeplearning_cfn_tpu.examples.multiprocess_smoke
"""

from __future__ import annotations

import json
import os

import numpy as np


def main() -> dict:
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from deeplearning_cfn_tpu.examples.common import maybe_init_distributed
    from deeplearning_cfn_tpu.models.lenet import LeNet
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import SyntheticDataset
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    pid = maybe_init_distributed()
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    n_proc = jax.process_count()

    steps = int(os.environ.get("DLCFN_SMOKE_STEPS", "10"))
    model_kind = os.environ.get("DLCFN_SMOKE_MODEL", "lenet")
    if model_kind == "llama-fsdp":
        # The flagship layout ACROSS process boundaries: params and
        # optimizer state sharded over an fsdp axis that spans both
        # processes (x tp within), so the per-step all-gathers /
        # reduce-scatters — not just the gradient psum — cross the
        # coordinator-established transport.  The BASELINE 8B config's
        # communication pattern, proven on OS processes.
        from deeplearning_cfn_tpu.models import llama

        if n_local < 2 or n_local % 2 or n_global % 2:
            raise SystemExit(
                "DLCFN_SMOKE_MODEL=llama-fsdp needs an EVEN number of "
                "devices per process, >= 2 (set XLA_FLAGS=--xla_force_"
                "host_platform_device_count): each tp pair must sit "
                "within one process and the fsdp axis must span the "
                "process boundary — the property this mode exists to prove"
            )
        mesh = build_mesh(MeshSpec(fsdp=n_global // 2, tp=2))
        cfg = llama.LlamaConfig.tiny(vocab_size=64, seq_len=16)
        trainer = llama.make_trainer(
            cfg,
            mesh,
            TrainerConfig(strategy="fsdp", optimizer="adamw", learning_rate=1e-2),
        )
        batch = 2 * (n_global // 2)
        local = batch // n_proc
        rng = np.random.default_rng(7)
        # One fixed batch, repeated: the smoke must show the loss
        # DECREASING within a handful of steps (memorization), which
        # fresh random tokens per step cannot.
        from deeplearning_cfn_tpu.train.data import Batch

        tokens = rng.integers(1, cfg.vocab_size, size=(batch, 16)).astype(np.int32)
        one = Batch(x=tokens, y=np.roll(tokens, -1, 1))
        batches = [one] * steps
        init_x = jnp.asarray(tokens[:1])
    else:
        mesh = build_mesh(MeshSpec.data_parallel(n_global))
        trainer = Trainer(
            LeNet(num_classes=10),
            mesh,
            TrainerConfig(learning_rate=0.02, matmul_precision="float32"),
        )
        batch = 8 * n_global
        local = batch // n_proc
        ds = SyntheticDataset(shape=(28, 28, 1), num_classes=10, batch_size=batch)
        batches = list(ds.batches(steps))
        init_x = jnp.asarray(batches[0].x[:1])

    def to_global(arr: np.ndarray) -> jax.Array:
        # Every process holds the same global batch (deterministic
        # dataset); hand the runtime only the local slice.
        return jax.make_array_from_process_local_data(
            trainer.batch_sharding, arr[pid * local : (pid + 1) * local]
        )

    state = trainer.init(jax.random.key(0), init_x)
    losses = []
    for b in batches:
        state, metrics = trainer.train_step(state, to_global(b.x), to_global(b.y))
        losses.append(round(float(metrics["loss"]), 6))
    result = {
        "process_id": pid,
        "processes": n_proc,
        "local_devices": n_local,
        "global_devices": n_global,
        "model": model_kind,
        "losses": losses,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
