"""8B-scale feasibility evidence (round-1 verdict next-step #9): the
eval_shape memory report, the AOT lower check at full 8B shapes over a
virtual v5p-32-shaped mesh, and the HF import contract verified at 8B
geometry — all without touching a chip or materializing a tensor."""

import jax
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import llama, llama_import, llama_memory
from deeplearning_cfn_tpu.models.llama import LlamaConfig


def test_memory_report_param_accounting_exact():
    """Params GiB must equal the analytic 8B bf16 size divided over the
    mesh (every weight is 2D-sharded by fsdp x tp in param_specs)."""
    cfg = LlamaConfig.llama3_8b()
    rep = llama_memory.memory_report(
        cfg, {"fsdp": 8, "tp": 2}, batch_global=16
    )
    n_params = llama.param_count(cfg)
    assert 7.9e9 < n_params < 8.1e9  # it really is the 8B geometry
    # Norm weights are f32, everything else bf16; norms are ~1e-5 of the
    # total so 2 bytes/param is accurate to well under 1%.
    expected_gib = n_params * 2 / 16 / 1024**3
    assert abs(rep.params_gib - expected_gib) / expected_gib < 0.01
    assert rep.optimizer_gib == pytest.approx(2 * rep.params_gib)
    assert rep.gradients_gib == pytest.approx(rep.params_gib, rel=0.01)


def test_8b_fits_v5p_with_headroom():
    cfg = LlamaConfig.llama3_8b()
    for mesh_axes in ({"fsdp": 16, "tp": 1}, {"fsdp": 8, "tp": 2}):
        rep = llama_memory.memory_report(cfg, mesh_axes, batch_global=16)
        assert rep.fits("v5p"), f"{mesh_axes}: {rep.total_gib:.1f} GiB/chip"
        assert rep.total_gib < 40  # generous headroom, not a squeeze
    # The same config does NOT fit a v5e chip — the report must say so,
    # or it is not measuring anything.
    rep = llama_memory.memory_report(cfg, {"fsdp": 4, "tp": 1}, batch_global=8)
    assert not rep.fits("v5litepod")


def test_shard_factor_handles_tuple_axes():
    from jax.sharding import PartitionSpec as P

    axes = {"dp": 2, "fsdp": 4, "tp": 2}
    assert llama_memory._shard_factor(P(("dp", "fsdp"), None), axes) == 8
    assert llama_memory._shard_factor(P(None, "tp"), axes) == 2
    assert llama_memory._shard_factor(P(), axes) == 1


@pytest.mark.slow
def test_8b_step_lowers_over_virtual_v5p32_mesh():
    """AOT-lower the FULL 8B train step (real shapes, real shardings) on a
    16-device virtual mesh: tracing, sharding propagation, and shape
    checks all run; no buffers are allocated.  Subprocess because the
    suite's conftest pins an 8-device mesh for this process."""
    import subprocess
    import sys

    script = (
        "import os;"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=16';"
        "import jax;"
        "from deeplearning_cfn_tpu.models.llama_memory import compile_check;"
        "from deeplearning_cfn_tpu.models.llama import LlamaConfig;"
        "out = compile_check(LlamaConfig.llama3_8b(), {'fsdp': 8, 'tp': 2},"
        " batch_global=16, seq_len=8192);"
        "assert out['lowered'];"
        "print('LOWERED_OK', round(out['lower_seconds'], 1))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=540
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "LOWERED_OK" in proc.stdout


def test_8b_single_chip_memory_lean_program_lowers():
    """The exact program docs/MEMORY_8B prices at 51.5 GiB on ONE v5p —
    8B, adafactor, grad_accum=8, fsdp1 — traces and lowers: the
    feasibility claim is backed by an expressible program, not just the
    analytic table.  Fast: lowering allocates no buffers."""
    from deeplearning_cfn_tpu.models.llama_memory import compile_check

    out = compile_check(
        LlamaConfig.llama3_8b(), {"fsdp": 1}, batch_global=8, seq_len=8192,
        optimizer="adafactor", grad_accum=8,
    )
    assert out["lowered"]


def test_hf_import_contract_at_8b_shapes():
    """The importer's expected HF state-dict geometry at 8B matches the
    published Llama-3-8B checkpoint shapes, and importing zero-stride
    views of exactly those shapes yields the framework's init_params
    tree — shape-verified import without 16 GB of RAM."""
    cfg = LlamaConfig.llama3_8b()
    shapes = llama_import.expected_hf_shapes(cfg)
    # Published Llama-3-8B geometry (HF meta-llama/Meta-Llama-3-8B).
    assert shapes["model.embed_tokens.weight"] == (128256, 4096)
    assert shapes["model.layers.0.self_attn.q_proj.weight"] == (4096, 4096)
    assert shapes["model.layers.0.self_attn.k_proj.weight"] == (1024, 4096)
    assert shapes["model.layers.31.mlp.gate_proj.weight"] == (14336, 4096)
    assert shapes["lm_head.weight"] == (128256, 4096)
    assert len([k for k in shapes if ".layers." in k]) == 32 * 9

    # Tiny config: run the REAL importer over broadcast-zero views shaped
    # by expected_hf_shapes and check the output tree matches init_params.
    tiny = LlamaConfig.tiny(vocab_size=64, seq_len=16)
    fake_sd = {
        k: np.broadcast_to(np.float32(0.0), shape)
        for k, shape in llama_import.expected_hf_shapes(tiny).items()
    }
    params = llama_import.from_hf_state_dict(tiny, fake_sd)
    ref_shapes = jax.eval_shape(
        lambda key: llama.init_params(tiny, key), jax.random.key(0)
    )
    got = jax.tree_util.tree_map(lambda x: x.shape, params)
    want = jax.tree_util.tree_map(lambda x: x.shape, ref_shapes)
    assert got == want


def test_adafactor_memory_term_is_factored():
    """The memory model's adafactor term must be O(rows+cols), not
    O(params): the analytic basis for the >2B on-chip ladder rung."""
    from deeplearning_cfn_tpu.models.llama import LlamaConfig
    from deeplearning_cfn_tpu.models.llama_memory import memory_report

    cfg = LlamaConfig.b3(seq_len=1024)
    adamw = memory_report(
        cfg, {"fsdp": 1}, batch_global=4, seq_len=1024, optimizer="adamw"
    )
    ada = memory_report(
        cfg, {"fsdp": 1}, batch_global=4, seq_len=1024, optimizer="adafactor"
    )
    # Factored state is < 1% of adamw's moment bytes at this scale.
    assert ada.optimizer_gib < 0.01 * adamw.optimizer_gib
    # The headline consequence: b3 cannot fit a 16 GiB chip under adamw
    # but fits with margin under adafactor.
    assert not adamw.fits("v5litepod")
    assert ada.fits("v5litepod")
    # Everything except the optimizer term is identical.
    assert ada.params_gib == adamw.params_gib
    assert ada.gradients_gib == adamw.gradients_gib


def test_grad_accum_memory_terms_match_chip_observations():
    """The accumulation terms, bracketed by four real-chip outcomes
    (BENCH_NOTES r5): activations/logits scale with the MICROBATCH,
    the gradient term doubles (param-sized sum buffer).  1.1B at
    effective batch 128 trains only under accum=4, and the 2.9B rung —
    fitting precisely because nothing param-sized is spare — cannot
    afford that doubled gradient buffer."""
    from deeplearning_cfn_tpu.models.llama import LlamaConfig
    from deeplearning_cfn_tpu.models.llama_memory import memory_report

    mesh = {"dp": 1, "fsdp": 1}
    b1 = LlamaConfig.b1(seq_len=1024)
    b3 = LlamaConfig.b3(seq_len=1024)
    one_shot = memory_report(b1, mesh, 128, optimizer="adafactor")
    accum = memory_report(b1, mesh, 128, optimizer="adafactor", grad_accum=4)
    assert not one_shot.fits("v5litepod")  # chip: OOM, 31.6 G used
    assert accum.fits("v5litepod")  # chip: trains at MFU 0.447
    # Activations and logits shrink with the microbatch; grads double.
    assert accum.activations_gib < one_shot.activations_gib / 3
    assert accum.logits_gib == one_shot.logits_gib / 4
    assert accum.gradients_gib == 2 * one_shot.gradients_gib
    # The top rung has no param-sized slack: accumulation cannot help.
    top = memory_report(b3, mesh, 32, optimizer="adafactor", grad_accum=4)
    assert not top.fits("v5litepod")  # chip: OOM, 20.6 G used
    assert abs(top.total_gib - 20.6) < 2.0  # and the magnitude agrees
    # Distinct messages for the two failure modes (mirroring Trainer's):
    with pytest.raises(ValueError, match="not divisible"):
        memory_report(b1, mesh, 10, grad_accum=3)
    with pytest.raises(ValueError, match="must be >= 1"):
        memory_report(b1, mesh, 10, grad_accum=0)


def test_the_kept_pair_is_priced_where_the_block_runs_the_flash_kernel():
    """`llama.remat_keeps` holds every layer's flash `out` and `lse` from the
    forward to the backward pass: the report's activations carry that term at
    and above the crossover, and nothing below it, without remat, under ring
    attention, or for a config that does not ask for the kernel (the 8B
    preset: docs/MEMORY_8B.md's table is unchanged)."""
    import dataclasses

    from deeplearning_cfn_tpu.ops.pallas_attention import FLASH_CROSSOVER_SEQ

    cfg = LlamaConfig.b1(seq_len=4096)
    pair = llama_memory.kept_pair_bytes(cfg, 4, 2048)
    # 20 layers x 4 x 2048 x 16 heads x (128 bf16 + one float32)
    assert pair == 20 * 4 * 2048 * 16 * (128 * 2 + 4) == 681_574_400
    assert llama_memory.kept_pair_bytes(cfg, 4, 2048, tp=2) == pair // 2
    assert llama_memory.kept_pair_bytes(cfg, 4, FLASH_CROSSOVER_SEQ - 1) == 0
    assert llama_memory.kept_pair_bytes(dataclasses.replace(cfg, remat=False), 4, 2048) == 0
    ring = dataclasses.replace(cfg, use_ring_attention=True)
    assert llama_memory.kept_pair_bytes(ring, 4, 2048, sp=2) == 0
    assert llama_memory.kept_pair_bytes(ring, 4, 2048, sp=1) == pair
    assert llama_memory.kept_pair_bytes(LlamaConfig.llama3_8b(), 1, 8192) == 0

    def activations(c):
        return llama_memory.memory_report(c, {"fsdp": 1}, batch_global=4, seq_len=2048).activations_gib

    without = activations(dataclasses.replace(cfg, use_flash_attention=False))
    assert activations(cfg) - without == pytest.approx(pair / 1024**3)
