"""True multi-process distributed training (examples/multiprocess_smoke).

Two OS processes join over jax.distributed using the cluster-contract env
triple, build one global mesh (2 processes x 4 CPU devices), and train
synchronously — the gradient psum crosses the process boundary over the
coordinator transport.  This is the framework's mpirun-equivalent proof
(the reference could only show it on a live cluster, run.sh:70-95).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_processes(model: str, steps: int = 8) -> list[dict]:
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            DEEPLEARNING_WORKERS_COUNT="2",
            DLCFN_PROCESS_ID=str(pid),
            DEEPLEARNING_COORDINATOR=f"127.0.0.1:{port}",
            DLCFN_SMOKE_STEPS=str(steps),
            DLCFN_SMOKE_MODEL=model,
            # Test isolation: no persistent compile cache in the checkout.
            JAX_ENABLE_COMPILATION_CACHE="false",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "deeplearning_cfn_tpu.examples.multiprocess_smoke"],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    for pid, res in enumerate(outs):
        assert res["process_id"] == pid
        assert res["processes"] == 2
        assert res["local_devices"] == 4
        assert res["global_devices"] == 8
    # SPMD: every process must observe the identical loss sequence.
    assert outs[0]["losses"] == outs[1]["losses"]
    losses = outs[0]["losses"]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    return outs


@pytest.mark.slow
def test_two_process_training_agrees_and_learns(tmp_path):
    _run_two_processes("lenet")


@pytest.mark.slow
def test_two_process_fsdp_tp_llama_shards_params_across_processes(tmp_path):
    """The flagship fsdp x tp layout with the fsdp axis SPANNING the two
    processes: per-step parameter all-gathers and gradient
    reduce-scatters cross the process boundary (the 8B communication
    pattern), not just a data-parallel psum."""
    outs = _run_two_processes("llama-fsdp")
    assert outs[0]["model"] == "llama-fsdp"
