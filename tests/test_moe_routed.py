"""Routed experts without dropped tokens (ops/moe.routed_experts): the sorted,
grouped path against a plain mask over experts, on both grouped matmuls (XLA's
`ragged_dot` and the Pallas kernel in interpret mode), at any imbalance, and
under the chip's share."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops import moe
from deeplearning_cfn_tpu.ops.moe import RoutedConfig, init_routed_params, route, routed_experts

D, WIDTH = 16, 32
KINDS = {"xla": dict(kind="xla"), "pallas-interpret": dict(kind="pallas", interpret=True)}


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def dense_masked(cfg: RoutedConfig, p: dict, x: jax.Array, shared: bool = True) -> jax.Array:
    """sum over the held experts of w_i E_i(x) by a mask, every expert on
    every token: no sort, no gather, nothing to drop."""
    xt = x.reshape(-1, x.shape[-1])
    experts, weights = route(cfg, p, xt)
    first, count = cfg.span
    y = jnp.zeros_like(xt)
    for j in range(count):
        share = jnp.sum(jnp.where(experts == first + j, weights, 0.0), axis=-1)
        y = y + share[:, None] * _swiglu(xt, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    if shared and cfg.shared_dim:
        y = y + _swiglu(xt, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(x.shape)


def _layer(held=(2, 4), bias=None, **kw):
    cfg = RoutedConfig(
        n_routed=8, top_k=2, held=held, selection_bias=True, scale=1.8, shared_dim=24, **kw
    )
    p = init_routed_params(cfg, jax.random.key(0), D, WIDTH, jnp.float32)
    if bias is not None:
        p["router_bias"] = jnp.asarray(bias, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 64, D), jnp.float32)
    return cfg, p, x


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_matches_the_mask_over_experts_forward_and_gradients(kind, score):
    cfg, p, x = _layer(score=score)
    y, stats = routed_experts(cfg, p, x, **KINDS[kind])
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_masked(cfg, p, x)), atol=2e-5)
    assert int(stats["dropped"]) == 0 and int(stats["assignments"]) == 2 * 64 * 2
    loss = lambda f: (lambda p, x: jnp.sum(f(p, x) ** 2))
    got = jax.grad(loss(lambda p, x: routed_experts(cfg, p, x, **KINDS[kind])[0]), (0, 1))(p, x)
    want = jax.grad(loss(lambda p, x: dense_masked(cfg, p, x)), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)
    assert float(jnp.max(jnp.abs(got[0]["router_bias"]))) == 0.0  # a buffer: no gradient


@pytest.mark.parametrize("kind", KINDS)
def test_a_skewed_bias_sends_every_token_to_one_held_expert_and_none_is_dropped(kind):
    """Expert 3 is every token's first choice: its group holds all 128 tokens,
    the others share what the second choices bring, nothing is dropped and
    the counters say so."""
    bias = np.zeros(8, np.float32)
    bias[3] = 5.0
    cfg, p, x = _layer(bias=bias)
    y, stats = routed_experts(cfg, p, x, **KINDS[kind])
    experts, _ = route(cfg, p, x.reshape(-1, D))
    assert bool(jnp.all(jnp.any(experts == 3, axis=-1)))
    held = int(jnp.sum((experts >= 2) & (experts < 6)))
    assert int(stats["load_max"]) == 128 and int(stats["assignments_held"]) == held
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_masked(cfg, p, x)), atol=2e-5)


def test_every_token_to_held_experts_fills_the_whole_buffer():
    """The worst case the buffer is sized for: both choices of every token
    are held here."""
    bias = np.zeros(8, np.float32)
    bias[[4, 5]] = 5.0
    cfg, p, x = _layer(held=(4, 2), bias=bias)
    assert cfg.buffer_rows(128) == 128 * 2
    y, stats = routed_experts(cfg, p, x, kind="xla")
    assert int(stats["assignments_held"]) == 256 and int(stats["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_masked(cfg, p, x)), atol=2e-5)


def test_fewer_held_experts_than_choices_bound_the_buffer():
    cfg = RoutedConfig(n_routed=8, top_k=4, held=(6, 2))
    assert cfg.buffer_rows(100) == 200  # a token's four choices are distinct experts
    p = init_routed_params(cfg, jax.random.key(0), D, WIDTH, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 32, D), jnp.float32)
    y, stats = routed_experts(cfg, p, x, kind="xla")
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_masked(cfg, p, x)), atol=2e-5)


# The router's variants of the two configurations that use the layer: GLM's
# (a shared expert, scale 1.8, the default epsilon) and LFM2's (32 experts of
# which a chip holds 8, top 4, no shared expert, scale 1, epsilon 1e-6).
SHARED_ROUTERS = {
    "shared-expert-scale-1.8": dict(n_routed=8, top_k=2, scale=1.8, shared_dim=24),
    "no-shared-expert-eps-1e-6": dict(n_routed=32, top_k=4, scale=1.0, renormalize_eps=1e-6),
}


@pytest.mark.parametrize("router", SHARED_ROUTERS)
def test_the_shares_add_up_to_the_whole_layer(router):
    """Four chips hold a quarter of the experts each: their routed parts, with
    the shared expert (where there is one) counted once, are the layer that
    holds all of them."""
    variant = dict(selection_bias=True, **SHARED_ROUTERS[router])
    n = variant["n_routed"] // 4
    whole_cfg = RoutedConfig(held=None, **variant)
    p = init_routed_params(whole_cfg, jax.random.key(0), D, WIDTH, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 64, D), jnp.float32)
    whole, _ = routed_experts(whole_cfg, p, x, kind="xla")
    parts = 0
    for rank in range(4):
        cfg = RoutedConfig(held=(n * rank, n), **variant)
        share = {**p, **{k: p[k][n * rank : n * (rank + 1)] for k in ("w_gate", "w_up", "w_down")}}
        y, stats = routed_experts(cfg, share, x, kind="xla")
        parts = parts + y
        assert int(stats["dropped"]) == 0
    if whole_cfg.shared_dim:
        xt = x.reshape(-1, D)
        shared = _swiglu(xt, p["shared_gate"], p["shared_up"], p["shared_down"]).reshape(x.shape)
        parts = parts - 3 * shared
    else:
        assert "shared_gate" not in p
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=5e-5)


def test_the_epsilon_is_data_and_its_default_keeps_the_program_text():
    """The renormalisation's epsilon as a field: left out, the lowered layer
    is letter for letter the one with 1e-20 written in (GLM's); 1e-6 (LFM2's)
    is another program, and the weights it gives sum to just under the scale."""
    cfg, p, x = _layer()
    text = lambda c: jax.jit(lambda p, x: routed_experts(c, p, x, kind="xla")[0]).lower(p, x).as_text()
    assert cfg.renormalize_eps == 1e-20
    assert text(cfg) == text(dataclasses.replace(cfg, renormalize_eps=1e-20))
    other = dataclasses.replace(cfg, renormalize_eps=1e-6)
    assert text(cfg) != text(other)
    _, weights = route(other, p, x.reshape(-1, D))
    sums = np.asarray(jnp.sum(weights, axis=-1))
    assert np.all(sums < 1.8) and np.all(sums > 1.8 * (1 - 1e-5))


def test_rows_past_the_last_group_are_zero_and_pass_no_gradient():
    """The Pallas grouped matmul leaves what it does not visit uninitialised,
    in its result and in its gradient with respect to the rows."""
    rows = jax.random.normal(jax.random.key(0), (256, D), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (3, D, WIDTH), jnp.float32)
    sizes = jnp.asarray([40, 0, 90], jnp.int32)
    f = lambda rows, w: moe.grouped_matmul(rows, w, sizes, "pallas", interpret=True)
    out = f(rows, w)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(moe.grouped_matmul(rows, w, sizes, "xla")), atol=1e-5
    )
    assert float(jnp.max(jnp.abs(out[130:]))) == 0.0
    d_rows, d_w = jax.grad(lambda rows, w: jnp.sum(f(rows, w) ** 2), (0, 1))(rows, w)
    assert np.all(np.isfinite(np.asarray(d_rows))) and float(jnp.max(jnp.abs(d_rows[130:]))) == 0.0
    assert float(jnp.max(jnp.abs(d_w[1]))) == 0.0  # an empty group's weights


def test_config_refuses_what_is_not_a_span():
    with pytest.raises(ValueError, match="span"):
        RoutedConfig(n_routed=8, top_k=2, held=(6, 4))
    with pytest.raises(ValueError, match="top_k"):
        RoutedConfig(n_routed=2, top_k=4)
    assert moe.grouped_matmul_kind("cpu") == "xla" and moe.grouped_matmul_kind("tpu") == "pallas"
