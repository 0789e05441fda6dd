"""Every decoder cell's train step, lowered for the TPU from shapes alone at
the cell's own batch and sequence, held to one table of sha256: what "no other
cell's program changed" means, in one place.  The rules that choose a kernel
(`_takes_fused_backward`, `_takes_band_step`, `takes_kernel`, ...) decide as
they do on the chip: `jax.default_backend` is the only thing patched."""

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.manifest import Manifest
from tests.kernel_text import text_without_kernel_locations

# configuration -> sha256 of its cell's step, each Mosaic kernel's body replaced
# by the sha256 of its MLIR without source locations.  Taken at 2ecc1c6 (PR 45's
# tree, PR 46's parent) before PR 46 edited any file under `models/`: PR 46 moved
# what the decoders share into `models/decoder_stack.py` and had to leave all six
# as they were.  A PR that means to change a cell's step replaces that cell's row
# and says why; a row that moves in a PR that does not mean it is a fault.
# PR 48 re-took the four routed rows (glm-4.7-flash, lfm2-8b-a1b, laguna-xs.2,
# nemotron-3-super-120b-a12b) and meant to: `ops/moe.routed_experts` counts
# `slots_read`, reads `dropped` from the sort's own keys and, in the Nemotron
# row, gathers the rows' cotangent back over a token's live slots, no wider
# than the buffer.  The three rows without a routed layer did not move.
STEPS = {
    "mistral-7b-v0.3": "44e7a8f13d410b187f5495093044228525242162611407fe3fa14d707f08605f",
    "glm-4.7-flash": "d1ce4dcb68465d51c3032071be0376872f75210e434ad6118511a3b3078fbd8b",
    "lfm2-8b-a1b": "d0da684a89090c5dda85fa7fe7010680c4f31a1b13d5d754cb22a18aaeadae8f",
    "laguna-xs.2": "6a4822a3815a1b1f47d0a912508765f868fde5a1701f8c71fa890a90fa4a8e3f",
    "nemotron-3-super-120b-a12b": "979557c6defbfe2f5e1e441c3b539c4d3ff28827450e5d4f01eaccbd8bf764f6",
    "ouro-2.6b": "173cda54d3db1119256c9ed06402770e2279f876085e09d45da6f0d86920315b",
    # PR 47's own cell, taken on PR 47's tree: the six rows above did not move.
    "jamba2-3b": "9684fd2cf07f76fa6d1a0bb7af4b1e60a6c34e939749723465c9dc16f73b0b30",
}


def cell_trainer(name: str):
    """The trainer of the configuration's cell as its builder makes it, and the
    cell's batch and sequence length."""
    from deeplearning_cfn_tpu.models import (
        conv_attn_moe, llama, looped_decoder, mamba_attn, mla_moe, ssm_attn_moe, window_attn_moe)
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    manifest = Manifest()
    (cell,) = [w for w in manifest.data["workloads"] if w["config"] == name]
    traffic = manifest.json("traffic", cell["traffic"])
    config = manifest.config(name)
    if config["kind"] == "decoder":
        module, cfg = llama, llama.LlamaConfig(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"], mlp_dim=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"], rope_theta=float(config["rope_theta"]),
            norm_eps=config["rms_norm_eps"], dtype=jnp.dtype(config["torch_dtype"]), remat=True,
            remat_policy=config["remat_policy"], tied_embeddings=config["tie_word_embeddings"],
            use_flash_attention=config["use_flash_attention"],
        )
    else:
        module = {"mla_moe": mla_moe, "conv_attn_moe": conv_attn_moe,
                  "window_attn_moe": window_attn_moe, "ssm_attn_moe": ssm_attn_moe,
                  "looped_decoder": looped_decoder, "mamba_attn": mamba_attn}[config["kind"]]
        cfg = manifest.module("builders", config["kind"]).model_config(config)
    mesh = build_mesh(MeshSpec.fsdp_parallel(1), jax.devices()[:1])
    trainer = module.make_trainer(cfg, mesh, TrainerConfig(
        strategy="fsdp", optimizer="adamw", learning_rate=config["learning_rate"],
        weight_decay=config["weight_decay"], grad_clip_norm=config["grad_clip_norm"],
        log_every=traffic["log_every"]))
    return trainer, mesh, traffic["global_batch"], traffic["seq_len"]


def lowered_step_without_locations(name: str) -> str:
    trainer, mesh, batch, seq_len = cell_trainer(name)
    tokens = jax.ShapeDtypeStruct((batch, seq_len), np.int32)
    state = jax.eval_shape(partial(trainer.init, jax.random.key(0)), tokens)
    with jax.set_mesh(mesh):
        text = trainer.step_fn.trace(state, tokens, tokens).lower(lowering_platforms=("tpu",)).as_text()
    return text_without_kernel_locations(text)


@pytest.mark.parametrize("name", STEPS)
def test_the_cells_step_lowers_to_the_tables_text(name, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = lowered_step_without_locations(name)
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS[name]
