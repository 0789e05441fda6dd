"""Sharding-quality regression tests for the Llama step.

The round-1 multichip dryrun compiled, but with two GSPMD "Involuntary
full rematerialization" warnings on the embedding-gather path under an
sp x tp mesh — silent collective bloat (the activation was replicated and
re-partitioned every step).  These tests pin the fix: the compiled
multichip step must produce ZERO such warnings.  XLA emits the warning
from C++ on stderr, so the assertion runs the compile in a subprocess.
"""

import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_cfn_tpu.models import llama
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.trainer import TrainerConfig

mesh = build_mesh(MeshSpec(dp=1, fsdp=2, sp=2, tp=2), jax.devices()[:8])
cfg = llama.LlamaConfig.tiny(vocab_size=128, seq_len=16)
trainer = llama.make_trainer(
    cfg, mesh, TrainerConfig(strategy="fsdp", optimizer="adamw", learning_rate=1e-3)
)
rng = np.random.default_rng(0)
tokens = rng.integers(1, cfg.vocab_size, size=(4, cfg.max_seq_len), dtype=np.int32)
x = jax.device_put(jnp.asarray(tokens), trainer.batch_sharding)
y = jax.device_put(jnp.asarray(np.roll(tokens, -1, 1)), trainer.batch_sharding)
state = trainer.init(jax.random.key(0), x)
with jax.set_mesh(mesh):
    trainer.step_fn.lower(state, x, y).compile()
print("COMPILED_OK")
"""


@pytest.mark.slow
def test_multichip_step_compiles_without_involuntary_remat():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "COMPILED_OK" in proc.stdout
    assert "Involuntary full rematerialization" not in proc.stderr, (
        "GSPMD fell back to replicate-and-reshard:\n" + proc.stderr[-3000:]
    )
