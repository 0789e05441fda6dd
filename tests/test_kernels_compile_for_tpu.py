"""The flash attention's kernels, the routed experts' grouped matmuls, the
state-space scan's kernels, the selective scan's and the Mamba-2 block's two elementwise stages'
compiled for a TPU v5e that is described, not attached, at the widths the chip
runs them: what the interpreter cannot show
(a tile Mosaic refuses, more VMEM than a kernel may use).  Nothing runs, so
nothing here says anything about results or times; chip_smoke.py does, on the
chip.  All of these in this one file: the worker that gets it loads the TPU's
compiler, and only that one may."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning_cfn_tpu.ops import pallas_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (batch, seq, q heads, kv heads, head dim, dtype): the decoder cell's
# attention, the latent-attention cell's (20 heads of 192 + 64 = 256, the value
# head 256 too), the convolution-attention cell's (32/8 heads of 64 at S 8192),
# the looped decoder's (groups of 1), the state-space hybrid's one attention
# block (groups of 16: its dq does not fit the fused backward's budget),
# the ragged case of chip_smoke.py (2100 pads to 2176 in blocks of 128), GQA
# 16/4 at d64, and float32.
CASES = {
    "cell-s4096": (2, 4096, 32, 8, 128, jnp.bfloat16),
    "mla-cell-s8192": (2, 8192, 20, 20, 256, jnp.bfloat16),
    "conv-attn-cell-s8192": (2, 8192, 32, 8, 64, jnp.bfloat16),
    "looped-cell-s8192": (1, 8192, 16, 16, 128, jnp.bfloat16),
    "ssm-hybrid-cell-s8192-groups-of-16": (1, 8192, 32, 2, 128, jnp.bfloat16),
    "ragged-s2100": (1, 2100, 32, 8, 128, jnp.bfloat16),
    "d64-s2048": (1, 2048, 16, 4, 64, jnp.bfloat16),
    "float32-s2048": (1, 2048, 8, 8, 128, jnp.float32),
}


@pytest.mark.parametrize("case", CASES)
def test_forward_and_backward_compile_for_v5e(case, one_chip, monkeypatch):
    B, S, Hq, Hkv, D, dtype = CASES[case]
    q = jax.ShapeDtypeStruct((B, S, Hq, D), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), dtype, sharding=one_chip)

    def compile_grads():
        def grads(q, k, v):  # a function of its own each time: nothing traced is reused
            loss = lambda q, k, v: pa.flash_attention(q, k, v).astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        return jax.jit(grads).lower(q, kv, kv).compile()

    compiled = compile_grads()
    text = compiled.as_text()
    fused = pa._takes_fused_backward(None, Hq // Hkv, S, D)
    assert fused == (case != "ssm-hybrid-cell-s8192-groups-of-16")
    backward = {"_flash_backward_fused"} if fused else {"_flash_backward_dkv", "_flash_backward_dq"}
    assert set(re.findall(r"_flash_(?:forward|backward_\w+)", text)) == {"_flash_forward"} | backward
    # No score-sized tensor outside the kernels: all temporaries together
    # stay under one float32 [B, Hq, S, 512] slab of the old XLA backward.
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < B * Hq * S * 512 * 4
    if fused:
        # Beside the pair at the same shape: the fused call's temporaries are
        # no larger except by the float32 dq (the pair's two lane-replicated
        # [B, Hq, S, 128] float32 arrays go, and its bfloat16 dq).
        monkeypatch.setattr(pa, "_BWD_FUSED_DQ_BUDGET", 0)
        pair = compile_grads()
        assert "_flash_backward_fused" not in pair.as_text()
        assert temporaries <= pair.memory_analysis().temp_size_in_bytes + B * Hq * S * D * 4


# The attention of `laguna-xs.2.train-s8192` (PR 33): the full layers' 48
# query heads over 8 key/value heads (groups of 6) through the full-causal
# kernels, the window layers' 64 (groups of 8) under a window of 512 at the
# tiles the sweep chose (the module's constants), and a ragged length with a
# window.  (batch, seq, q heads, kv heads, head dim, window)
WINDOW_CASES = {
    "laguna-full-48-heads": (2, 8192, 48, 8, 128, None),
    "laguna-window-512-64-heads": (2, 8192, 64, 8, 128, 512),
    "ragged-s2100-window-512": (1, 2100, 64, 8, 128, 512),
    "window-300-not-a-multiple": (1, 4096, 12, 2, 128, 300),
}


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_windowed_kernels_compile_for_v5e(case, one_chip):
    B, S, Hq, Hkv, D, window = WINDOW_CASES[case]
    q = jax.ShapeDtypeStruct((B, S, Hq, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        loss = lambda q, k, v: pa.flash_attention(q, k, v, window=window).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, kv, kv).compile()
    text = compiled.as_text()
    kernels = (
        ("_flash_forward", "_flash_backward_fused") if window is None
        else ("_window_flash_forward", "_window_flash_backward_dkv", "_window_flash_backward_dq")
    )
    for kernel in kernels:
        assert kernel in text, kernel
    assert ("_window_flash" in text) == (window is not None)
    assert compiled.memory_analysis().temp_size_in_bytes < B * Hq * S * 512 * 4


def test_routed_experts_layer_compiles_for_v5e_without_a_scatter(one_chip):
    """One expert layer of `glm-4.7-flash.train-s8192`, forward and backward:
    16,384 tokens, top 4 of 64, 16 experts of 2048 x 1536 held and a shared
    one.  The grouped matmuls are the Pallas kernel at the tiles of
    `GROUPED_MATMUL_TILES` (forward, and both of its transposes), and neither
    pass scatters rows: dispatch and combine are gathers both ways (the
    scatters left are of scalars: a token's four weights, the kernel's own
    tile tables)."""
    import re

    from deeplearning_cfn_tpu.ops import moe

    cfg = moe.RoutedConfig(
        n_routed=64, top_k=4, held=(0, 16), selection_bias=True, scale=1.8, shared_dim=1536
    )
    shapes = jax.eval_shape(lambda: moe.init_routed_params(cfg, jax.random.key(0), 2048, 1536))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, shapes)
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=one_chip)
    assert cfg.buffer_rows(2 * 8192) == 65536

    def grads(params, x):
        loss = lambda p, x: moe.routed_experts(cfg, p, x, kind="pallas")[0].astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1))(params, x)

    compiled = jax.jit(grads).lower(params, x).compile()
    text = compiled.as_text()
    assert text.count("tgmm") >= 3 and text.count("gmm") - text.count("tgmm") >= 6
    # Each matmul once forward and once each way backward: the passes beside
    # them (ops/moe._over_live_rows) bring no third one.
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 6 and len(re.findall(r"%tgmm[.\d]* = ", text)) == 3
    assert "ragged-dot" not in text
    assert not re.search(r"= \w+\[\d+,\d+\]\S* scatter\(", text)
    # The backward passes overwrite what they read: 1.36 GB of temporaries,
    # where a result written tile by tile into zeros is a buffer more each
    # (1.59 GB with every pass over the whole buffer, before PR 32).
    assert compiled.memory_analysis().temp_size_in_bytes < 1.45e9


# The scan of `nemotron-3-super-120b-a12b.train-s8192x1` (PR 42): 128 heads of
# 64 in 8 groups, a state of 128, chunks of 128; and a head as wide as a lane
# tile, a group a head, in float32.  (batch, seq, heads, head, groups, dtype)
SSD_CASES = {
    "nemotron-cell-s8192": (1, 8192, 128, 64, 8, jnp.bfloat16),
    "float32-heads-of-128": (2, 1024, 4, 128, 4, jnp.float32),
}


@pytest.mark.parametrize("case", SSD_CASES)
def test_the_state_space_scans_kernels_compile_for_v5e(case, one_chip):
    from deeplearning_cfn_tpu.ops import pallas_ssd

    b, S, H, P, G, dtype = SSD_CASES[case]
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    args = (
        on_chip((b, S, H, P), dtype), on_chip((b, S, H), f32), on_chip((H,), f32),
        on_chip((b, S, G, 128), dtype), on_chip((b, S, G, 128), dtype), on_chip((H,), f32),
    )
    assert pallas_ssd.takes_kernel(args[0], args[3], 128, backend="tpu")

    def grads(*args):
        loss = lambda *a: pallas_ssd.ssd(*a, 128).astype(f32).sum()
        return jax.grad(loss, argnums=tuple(range(6)))(*args)

    compiled = jax.jit(grads).lower(*args).compile()
    text = compiled.as_text()
    assert "_ssd_forward" in text and "_ssd_backward" in text
    # Nothing of a chunk's matrix form outside the kernels: the temporaries are
    # the saved states and copies of x's size, under half of one pass's L.
    states, an_x = b * S // 128 * 128 * H * P * 4, b * S * H * P * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < states + 4 * an_x


# The selective scan of the Jamba cell's Mamba-1 layers (PR 47) and float32 at a
# small size.  (batch, seq, channels, states, dtype)
SELECTIVE_SCAN_CASES = {
    "jamba-cell-s8192": (1, 8192, 5120, 16, jnp.bfloat16),
    "float32-s1024": (2, 1024, 512, 16, jnp.float32),
}


@pytest.mark.parametrize("case", SELECTIVE_SCAN_CASES)
def test_the_selective_scans_kernels_compile_for_v5e(case, one_chip):
    from deeplearning_cfn_tpu.ops import pallas_selective_scan as kernels

    b, S, I, N, dtype = SELECTIVE_SCAN_CASES[case]
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    args = (
        on_chip((b, S, I), dtype), on_chip((b, S, I), f32), on_chip((I, N), f32),
        on_chip((b, S, N), dtype), on_chip((b, S, N), dtype), on_chip((I,), f32),
    )
    assert kernels.takes_kernel(args[0], args[2], backend="tpu")

    def grads(*args):
        loss = lambda *a: kernels.selective_scan(*a).astype(f32).sum()
        return jax.grad(loss, argnums=tuple(range(6)))(*args)

    compiled = jax.jit(grads).lower(*args).compile()
    text = compiled.as_text()
    assert "_selective_scan_forward" in text and "_selective_scan_backward" in text
    # No [S, I, N] array outside the kernels: the temporaries are the chunks'
    # boundary states, B and C spread over a lane tile with their gradients'
    # partial sums, and copies of x's size; a state a token would be 16 of dt's.
    a_dt = b * S * I * 4
    states = b * S // kernels.CHUNK * N * I * 4
    columns = b * S * N * 128 * (2 * jnp.dtype(dtype).itemsize + 2 * 4)
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < states + columns + 1.5 * a_dt
    assert case != "jamba-cell-s8192" or temporaries < N * a_dt / 4


# The two elementwise stages of that cell's `M` block (PR 45): the convolution
# over x, B and C side by side (8192 + 2 x 8 x 128 channels, 4 taps) and the
# gated norm over 8 groups of 1024; and float32 at a small size, three taps, one
# group.  (batch, seq, inner, groups, state, taps, dtype)
SSM_STAGE_CASES = {
    "nemotron-cell-s8192": (1, 8192, 8192, 8, 128, 4, jnp.bfloat16),
    "float32-one-group-three-taps": (2, 1024, 512, 1, 128, 3, jnp.float32),
}


@pytest.mark.parametrize("stage", ["conv_silu", "gate_norm"])
@pytest.mark.parametrize("case", SSM_STAGE_CASES)
def test_the_ssm_blocks_elementwise_stages_kernels_compile_for_v5e(case, stage, one_chip):
    from deeplearning_cfn_tpu.ops import pallas_ssm_stages as stages

    b, S, inner, groups, state, taps, dtype = SSM_STAGE_CASES[case]
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    if stage == "conv_silu":
        C = inner + 2 * groups * state
        args = (on_chip((b, S, C), dtype), on_chip((taps, C), dtype), on_chip((C,), f32))
        assert stages.takes_conv_kernel(*args[:2], backend="tpu")
        call = stages.conv_silu
    else:
        args = (on_chip((b, S, inner), dtype), on_chip((b, S, inner), dtype), on_chip((inner,), f32))
        assert stages.takes_gate_norm_kernel(*args[:2], groups, backend="tpu")
        call = lambda y, z, w: stages.gate_norm(y, z, w, groups, 1e-5)

    def grads(*args):
        return jax.grad(lambda *a: call(*a).astype(f32).sum(), argnums=(0, 1, 2))(*args)

    compiled = jax.jit(grads).lower(*args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"_(?:conv_silu|gate_norm)_(?:forward|backward)", text))
    # The gradient alone needs no forward call: the residuals are the inputs.
    assert kernels == {f"_{stage}_backward"}
    # Nothing float32 of the array's size outside the kernel: the temporaries
    # are the cotangent and the partial sums, under one float32 copy of the array.
    assert compiled.memory_analysis().temp_size_in_bytes < args[0].size * 4
    both = jax.jit(lambda *a: (call(*a), grads(*a))).lower(*args).compile()
    assert set(re.findall(r"_(?:conv_silu|gate_norm)_(?:forward|backward)", both.as_text())) == {
        f"_{stage}_forward", f"_{stage}_backward"
    }
    assert both.memory_analysis().temp_size_in_bytes < args[0].size * 4


def _mistral_cell_step(one_chip):
    """The step of `mistral-7b-v0.3.train-s4096` as the benchmark's builder
    makes it (5 layers at the published widths, adamw, fsdp over one chip, 2 x
    4096 tokens), compiled from shapes alone."""
    from functools import partial

    import numpy as np

    from deeplearning_cfn_tpu.models import llama
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=5, n_heads=32, n_kv_heads=8, mlp_dim=14336,
        max_seq_len=32768, rope_theta=1e6, use_flash_attention=True,
    )
    mesh = build_mesh(MeshSpec.fsdp_parallel(1), list(one_chip.device_set))
    trainer = llama.make_trainer(cfg, mesh, TrainerConfig(
        strategy="fsdp", optimizer="adamw", learning_rate=3e-4, weight_decay=0.1,
        grad_clip_norm=1.0,
    ))
    tokens = jax.ShapeDtypeStruct((2, 4096), np.int32, sharding=trainer.batch_sharding)
    state = jax.eval_shape(
        partial(trainer.init, jax.random.key(0)), jax.ShapeDtypeStruct((1, 4096), np.int32)
    )
    with jax.set_mesh(mesh):
        return cfg, trainer.step_fn.lower(state, tokens, tokens).compile()


def test_the_kept_pair_costs_the_mistral_cells_step_its_shapes_once(one_chip, monkeypatch):
    """`llama.remat_keeps` in the whole compiled step, beside the same step
    under the parent's policy (nothing kept): the forward kernel is in the
    program once and not twice, XLA's own peak rises by the pair's shapes to
    the byte (`llama_memory.kept_pair_bytes`), and `temp_size_in_bytes`, which
    the benchmark's `device.memory_peak_bytes` reads, by twice that."""
    import re

    from deeplearning_cfn_tpu.models import llama, llama_memory

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, kept = _mistral_cell_step(one_chip)
    monkeypatch.setattr(llama, "remat_keeps", lambda also=None: also)
    _, recomputed = _mistral_cell_step(one_chip)

    calls = lambda compiled: len(set(re.findall(r"%(_flash_forward[.\d]*) = ", compiled.as_text())))
    assert (calls(kept), calls(recomputed)) == (1, 2)
    pair = llama_memory.kept_pair_bytes(cfg, 2, 4096)
    assert pair == 5 * (2 * 4096 * 32 * 128 * 2 + 2 * 32 * 4096 * 4) == 340_787_200
    after, before = kept.memory_analysis(), recomputed.memory_analysis()
    assert after.peak_memory_in_bytes - before.peak_memory_in_bytes == pair
    # to the byte under the pair of backward kernels; with the fused one (PR 44)
    # the two programs' small buffers differ by 32,256 bytes beside it
    assert after.temp_size_in_bytes - before.temp_size_in_bytes == pytest.approx(2 * pair, rel=1e-4)
