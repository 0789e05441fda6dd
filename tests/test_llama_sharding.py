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


def test_fused_qkv_matches_unfused():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_cfn_tpu.models import llama
    """cfg.fused_qkv packs wq|wk|wv and w_gate|w_up into single wider
    matmuls; same weights must give identical logits (pure layout
    change — the measured-perf lever of BENCH_NOTES round 4)."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128, seq_len=32)
    cfg_f = llama.LlamaConfig.tiny(vocab_size=128, seq_len=32, fused_qkv=True)
    params = llama.init_params(cfg, jax.random.key(0))
    layers_fused = dict(params["layers"])
    layers_fused["wqkv"] = jnp.concatenate(
        [layers_fused.pop("wq"), layers_fused.pop("wk"), layers_fused.pop("wv")],
        axis=-1,
    )
    layers_fused["w_gate_up"] = jnp.concatenate(
        [layers_fused.pop("w_gate"), layers_fused.pop("w_up")], axis=-1
    )
    fused_params = {**params, "layers": layers_fused}
    # Shapes agree with a natively-initialized fused tree.
    native = jax.eval_shape(
        lambda k: llama.init_params(cfg_f, k), jax.random.key(0)
    )
    assert jax.tree_util.tree_map(lambda a: a.shape, fused_params) == (
        jax.tree_util.tree_map(lambda a: a.shape, native)
    )
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 128)
    ref = llama.forward(cfg, params, tokens)
    got = llama.forward(cfg_f, fused_params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-2)


def test_fused_qkv_param_specs_cover_tree():
    import jax

    from deeplearning_cfn_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(fused_qkv=True)
    params = jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.key(0)
    )
    specs = llama.param_specs(cfg)
    # Same tree structure: every fused param has a spec.
    jax.tree_util.tree_map(lambda p, s: None, params, specs)
