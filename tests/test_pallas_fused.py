"""Pallas fused dense: parity with the XLA reference and with
``nn.Dense`` at the shapes models use, gradients, the int8-weights
variant, tree quantization and profitability dispatch.

The kernel runs in the Pallas interpreter here, asked for by name
(``interpret=True``); chip_smoke.py checks the compiled kernel on the
MXU.  The parity contract is a few ulp of the dtype, not
bit-identity: kernel and reference contract the same operands in one
``dot_general`` each, but the order a backend sums in is its own.
Comparisons are against ``jax.jit(fused_dense_reference)`` — the eager
gelu differs from its jitted self by ~5e-7, which is XLA fusion, not us.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops import pallas_fused
from deeplearning_cfn_tpu.ops.pallas_fused import (
    _quant_reference,
    fused_dense_bytes,
    fused_dense_profitable,
    fused_dense_reference,
)
from deeplearning_cfn_tpu.ops.quant import (
    dequantize_tree,
    quantize_tree,
    quantized_nbytes,
    quantize_weight,
    tree_nbytes,
)


fused_dense = functools.partial(pallas_fused.fused_dense, interpret=True)
fused_dense_quantized = functools.partial(
    pallas_fused.fused_dense_quantized, interpret=True
)


def assert_within_ulps(got, want, ulps=4):
    """|got - want| <= ulps * eps(dtype) * max(1, |want|), elementwise."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype
    eps = float(jnp.finfo(got.dtype).eps)
    g, w = got.astype(np.float64), want.astype(np.float64)
    bound = ulps * eps * np.maximum(1.0, np.abs(w))
    worst = float(np.max(np.abs(g - w) / bound)) if g.size else 0.0
    assert worst <= 1.0, f"off by {worst * ulps:.2f} ulp (allowed {ulps})"


def _operands(m, k, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.1, dtype)
    b = jnp.asarray(rng.standard_normal((n,)) * 0.1, dtype)
    return x, w, b


# --- forward parity -----------------------------------------------------------


@pytest.mark.parametrize("activation", [None, "relu", "gelu"])
@pytest.mark.parametrize(
    "m,k,n",
    [
        (16, 128, 128),   # exactly one tile
        (48, 96, 200),    # every dim needs padding, two N tiles
        (3, 7, 5),        # tiny, heavily padded
        (16, 256, 128),   # two K lanes, one reduction chunk
    ],
)
def test_forward_matches_jitted_reference(m, k, n, activation):
    for dtype in (jnp.float32, jnp.bfloat16):
        x, w, b = _operands(m, k, n, dtype)
        got = jax.jit(
            lambda x, w, b: fused_dense(x, w, b, activation=activation)
        )(x, w, b)
        want = jax.jit(
            lambda x, w, b: fused_dense_reference(x, w, b, activation=activation)
        )(x, w, b)
        assert got.dtype == want.dtype == dtype
        assert_within_ulps(got, want)


def test_forward_close_at_thread_partitioned_shapes():
    """At shapes big enough for XLA's CPU backend to partition the dot
    across its intra-op thread pool (partitioning depends on the virtual
    device count, so this shifts under --xla_force_host_platform_device_count),
    the REFERENCE's own f32 summation order changes.  The kernel must
    still agree to f32 accumulation tolerance."""
    x, w, b = _operands(64, 256, 384, jnp.float32)
    got = jax.jit(lambda x, w, b: fused_dense(x, w, b, activation="gelu"))(x, w, b)
    want = jax.jit(
        lambda x, w, b: fused_dense_reference(x, w, b, activation="gelu")
    )(x, w, b)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_compiled_kernel_does_not_fall_back_off_tpu():
    """No backend guessing: without interpret=True the Mosaic kernel is
    what runs, and on a CPU that is an error, not a silent interpreter."""
    x, w, b = _operands(8, 16, 4, jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        pallas_fused.fused_dense(x, w, b)
    wq, scale = quantize_weight(w)
    with pytest.raises(ValueError, match="interpret mode"):
        pallas_fused.fused_dense_quantized(x, wq, scale, b)


def test_input_validation():
    x, w, b = _operands(8, 16, 4, jnp.float32)
    with pytest.raises(ValueError, match="unknown activation"):
        fused_dense(x, w, b, activation="swish")
    with pytest.raises(ValueError, match="wants x"):
        fused_dense(x[None], w, b)


# --- gradients ----------------------------------------------------------------


@pytest.mark.parametrize("activation", [None, "relu", "gelu"])
def test_grads_match_reference(activation):
    x, w, b = _operands(16, 64, 32, jnp.float32, seed=1)

    def loss_fused(x, w, b):
        return jnp.sum(fused_dense(x, w, b, activation=activation) ** 2)

    def loss_ref(x, w, b):
        return jnp.sum(fused_dense_reference(x, w, b, activation=activation) ** 2)

    g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(x, w, b)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(x, w, b)
    for a, r in zip(g_fused, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=1e-5, atol=1e-6
        )


# --- int8-weights variant -----------------------------------------------------


@pytest.mark.parametrize("activation", [None, "gelu"])
def test_quantized_matches_reference(activation):
    x, w, b = _operands(24, 96, 48, jnp.float32, seed=2)
    wq, scale = quantize_weight(w)
    got = fused_dense_quantized(x, wq, scale, b, activation=activation)
    want = jax.jit(
        lambda x, wq, s, b: _quant_reference(x, wq, s, b, activation, x.dtype)
    )(x, wq, scale, b)
    assert_within_ulps(got, want)


def test_quantized_rejects_float_weights():
    x, w, b = _operands(8, 16, 4, jnp.float32)
    with pytest.raises(ValueError, match="int8"):
        fused_dense_quantized(x, w, jnp.ones((4,)), b)


def test_quantize_weight_roundtrip_error_bounded():
    _, w, _ = _operands(8, 64, 32, jnp.float32, seed=3)
    wq, scale = quantize_weight(w)
    assert wq.dtype == jnp.int8 and scale.shape == (32,)
    back = np.asarray(wq, np.float32) * np.asarray(scale)
    # Symmetric int8: error bounded by half a quantization step per channel.
    np.testing.assert_allclose(
        back, np.asarray(w), atol=float(np.asarray(scale).max()) * 0.51
    )
    # Zero-range channels round-trip exactly (scale forced to 1).
    wq0, s0 = quantize_weight(jnp.zeros((4, 4)))
    assert np.asarray(s0).tolist() == [1.0] * 4
    assert np.asarray(wq0).sum() == 0


# --- tree quantization --------------------------------------------------------


def _param_tree():
    rng = np.random.default_rng(4)
    return {
        "dense": {
            "kernel": jnp.asarray(rng.standard_normal((32, 16)), jnp.float32),
            "bias": jnp.zeros((16,), jnp.float32),
        },
        "norm": {"scale": jnp.ones((16,), jnp.float32)},
    }


def test_quantize_tree_roundtrip_and_structure():
    params = _param_tree()
    quantized, passthrough = quantize_tree(params)
    # Kernel positions carry the int8 record; everything else passes through.
    assert quantized["dense"]["kernel"]["wq"].dtype == jnp.int8
    assert quantized["dense"]["bias"] is None
    assert passthrough["dense"]["kernel"] is None
    assert passthrough["norm"]["scale"] is params["norm"]["scale"]
    back = dequantize_tree(quantized, passthrough)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    # Non-kernel leaves come back exactly; kernels within quantization error.
    np.testing.assert_array_equal(
        np.asarray(back["dense"]["bias"]), np.asarray(params["dense"]["bias"])
    )
    np.testing.assert_allclose(
        np.asarray(back["dense"]["kernel"]),
        np.asarray(params["dense"]["kernel"]),
        atol=0.05,
    )
    assert back["dense"]["kernel"].dtype == params["dense"]["kernel"].dtype


def test_quantize_tree_crosses_jit_boundary():
    """The quantized tree must be a valid jit argument (the bench jits
    quantize_tree and the int8 forward): no strings, no Python scalars —
    the dtype rides in a zero-size "like" array."""
    params = _param_tree()
    quantized, passthrough = jax.jit(quantize_tree)(params)
    back = jax.jit(dequantize_tree)(quantized, passthrough)
    assert back["dense"]["kernel"].dtype == jnp.float32


def test_quantized_nbytes_is_compact():
    params = _param_tree()
    quantized, _ = quantize_tree(params)
    q = quantized_nbytes(quantized)
    total = tree_nbytes(params)
    kernel_f32 = 32 * 16 * 4
    # int8 kernel + f32 scales + empty "like": ~1/4 the float kernel.
    assert q == 32 * 16 + 16 * 4
    assert q < kernel_f32
    assert total == kernel_f32 + 16 * 4 + 16 * 4


# --- profitability dispatch ---------------------------------------------------


def test_profitability_returns_bool_and_bytes_formula():
    verdict = fused_dense_profitable(256, 512, 512)
    assert isinstance(verdict, bool)
    # Analytic traffic: read x + w + b once, write out once.
    assert fused_dense_bytes(4, 8, 16, 2) == 2 * (4 * 8 + 8 * 16 + 16 + 4 * 16)


# --- the shapes models use, against nn.Dense ------------------------------------


@pytest.mark.parametrize(
    "m,k,n,activation,dtype,ulps",
    [
        # BertConfig.tiny's MLP at 2 x 16 tokens, in BertConfig's own bfloat16.
        pytest.param(32, 64, 128, "gelu", jnp.bfloat16, 4, id="bert_tiny_mlp_in"),
        pytest.param(32, 128, 64, None, jnp.bfloat16, 4, id="bert_tiny_mlp_out"),
        # ResNet-50's classifier head: pooled C5 features to 1000 classes.
        # 2,048 float32 terms summed in two different orders: kernel and
        # nn.Dense each sit 55-100 ulp from the float64 answer here and
        # 36-112 ulp from each other (five seeds, 2 to 128 rows).
        pytest.param(8, 2048, 1000, None, jnp.float32, 256, id="resnet50_head"),
    ],
)
def test_forward_matches_nn_dense(m, k, n, activation, dtype, ulps):
    """The kernel gives ``nn.Dense``'s numbers (float32 parameters cast to
    the layer's dtype, then ``nn.gelu`` where the layer has it) on the
    same parameters: a kernel/bias pair trained under one runs under the
    other."""
    import flax.linen as nn

    x, w, b = _operands(m, k, n, jnp.float32, seed=5)
    x = x.astype(dtype)
    dense = nn.Dense(n, dtype=dtype)

    def layer(w, b, x):
        out = dense.apply({"params": {"kernel": w, "bias": b}}, x)
        return nn.gelu(out) if activation == "gelu" else out

    want = jax.jit(layer)(w, b, x)
    got = jax.jit(
        lambda w, b, x: fused_dense(
            x, w.astype(dtype), b.astype(dtype), activation=activation
        )
    )(w, b, x)
    assert got.dtype == want.dtype == dtype
    assert_within_ulps(got, want, ulps=ulps)
