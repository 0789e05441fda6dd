"""The flash attention's kernels compiled for a TPU v5e that is described, not
attached, at the widths the chip runs them: what the interpreter cannot show
(a tile Mosaic refuses, more VMEM than a kernel may use).  Nothing runs, so
nothing here says anything about results or times; chip_smoke.py does, on the
chip.  All of these in this one file: the worker that gets it loads the TPU's
compiler, and only that one may."""

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
# attention, the ragged case of chip_smoke.py (2100 pads to 2176 in blocks of
# 128), GQA 16/4 at d64, and float32.
CASES = {
    "cell-s4096": (2, 4096, 32, 8, 128, jnp.bfloat16),
    "ragged-s2100": (1, 2100, 32, 8, 128, jnp.bfloat16),
    "d64-s2048": (1, 2048, 16, 4, 64, jnp.bfloat16),
    "float32-s2048": (1, 2048, 8, 8, 128, jnp.float32),
}


@pytest.mark.parametrize("case", CASES)
def test_forward_and_backward_compile_for_v5e(case, one_chip):
    B, S, Hq, Hkv, D, dtype = CASES[case]
    q = jax.ShapeDtypeStruct((B, S, Hq, D), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), dtype, sharding=one_chip)

    def grads(q, k, v):
        loss = lambda q, k, v: pa.flash_attention(q, k, v).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, kv, kv).compile()
    text = compiled.as_text()
    for kernel in ("_flash_forward", "_flash_backward_dkv", "_flash_backward_dq"):
        assert kernel in text, kernel
    # No score-sized tensor outside the kernels: all temporaries together
    # stay under one float32 [B, Hq, S, 512] slab of the old XLA backward.
    assert compiled.memory_analysis().temp_size_in_bytes < B * Hq * S * 512 * 4
