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


def _relu2(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def dense_masked(cfg: RoutedConfig, p: dict, x: jax.Array, shared: bool = True) -> jax.Array:
    """sum over the held experts of w_i E_i(x) by a mask, every expert on
    every token: no sort, no gather, nothing to drop."""
    xt = x.reshape(-1, x.shape[-1])
    experts, weights = route(cfg, p, xt)
    first, count = cfg.span
    y = jnp.zeros_like(xt)
    for j in range(count):
        share = jnp.sum(jnp.where(experts == first + j, weights, 0.0), axis=-1)
        if cfg.expert == "relu2":
            out = _relu2(xt, p["w_up"][j], p["w_down"][j])
        else:
            out = _swiglu(xt, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
        y = y + share[:, None] * out
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


TILE = 32  # rows of a pass's tile in these tests: the layer's 256 rows are eight of them


@pytest.fixture
def row_tile(monkeypatch):
    monkeypatch.setattr(moe, "ROW_TILE", TILE)


def _layer_holding(held_rows: int):
    """`_layer`'s 128 tokens and 256 rows with the router made an identity on
    the input's first eight columns, which hold the logits: the first
    `held_rows // 2` tokens choose two held experts, one more a held and an
    absent one where the count is odd, the rest two absent ones."""
    cfg, p, x = _layer()
    held_of, absent_of = (2, 3, 4, 5), (0, 1, 6, 7)
    logits = np.asarray(jax.random.normal(jax.random.key(2), (128, 8), jnp.float32)) * 0.3
    for t in range(128):
        n_held = min(2, max(0, held_rows - 2 * t))
        chosen = [held_of[(t + i) % 4] for i in range(n_held)]
        chosen += [absent_of[(t + i) % 4] for i in range(2 - n_held)]
        logits[t, chosen] += 4.0
    x = x.reshape(128, D).at[:, :8].set(jnp.asarray(logits)).reshape(x.shape)
    p["router"] = jnp.eye(D, 8, dtype=jnp.float32)
    return cfg, p, x


def _assert_layer_matches_the_mask(cfg, p, x, **kw):
    """Forward and every gradient (x, the router, the experts' three
    matrices, the shared expert) against `dense_masked`; the statistics."""
    y, stats = routed_experts(cfg, p, x, **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_masked(cfg, p, x)), atol=2e-5)
    loss = lambda f: (lambda p, x: jnp.sum(f(p, x) ** 2))
    got = jax.grad(loss(lambda p, x: routed_experts(cfg, p, x, **kw)[0]), (0, 1))(p, x)
    want = jax.grad(loss(lambda p, x: dense_masked(cfg, p, x)), (0, 1))(p, x)
    for name in ("router", "w_up", "w_down") + (("w_gate",) if cfg.expert == "swiglu" else ()):
        assert name in got[0]
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)
    return stats


# No row, one, a tile less one, a tile, a tile and one, the whole buffer.
HELD_ROWS = (0, 1, TILE - 1, TILE, TILE + 1, 256)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("held_rows", HELD_ROWS)
def test_the_passes_stop_at_the_counted_tiles_and_nothing_changes(row_tile, kind, held_rows):
    cfg, p, x = _layer_holding(held_rows)
    stats = _assert_layer_matches_the_mask(cfg, p, x, **KINDS[kind])
    assert int(stats["assignments_held"]) == held_rows and int(stats["dropped"]) == 0
    assert int(stats["rows_run"]) == -(-held_rows // TILE) * TILE
    assert int(stats["assignments"]) == cfg.buffer_rows(128) == 256


def test_a_buffer_the_tile_does_not_divide_runs_in_tiles_that_do(monkeypatch):
    monkeypatch.setattr(moe, "ROW_TILE", 96)  # 256 rows: tiles of 32
    cfg, p, x = _layer_holding(70)
    stats = _assert_layer_matches_the_mask(cfg, p, x, kind="xla")
    assert int(stats["rows_run"]) == 96 and int(stats["dropped"]) == 0


def _poisoned_matmul(rows, weights, group_sizes, kind, interpret=False):
    """A stand-in for the TPU kernel: it reads no row past the last group's,
    and leaves NaN there in its result and in its gradient with respect to
    `rows`, where the kernel leaves what the memory held."""
    valid = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]

    own = lambda a: jnp.where(valid, a, 0)
    dot = lambda rows, weights: jax.lax.ragged_dot(own(rows), weights, group_sizes)

    @jax.custom_vjp
    def f(rows, weights):
        return jnp.where(valid, dot(rows, weights), jnp.nan)

    def bwd(res, g):
        d_rows, d_weights = jax.vjp(dot, *res)[1](own(g))
        return jnp.where(valid, d_rows, jnp.nan), d_weights

    f.defvjp(lambda rows, weights: (f(rows, weights), (rows, weights)), bwd)
    return f(rows, weights)


def test_rows_past_the_last_group_are_zero_and_pass_no_gradient(row_tile, monkeypatch):
    """The Pallas grouped matmul leaves what it does not visit uninitialised,
    in its result and in its gradient with respect to the rows: its own rows
    are `ragged_dot`'s, and the layer reads neither past the counted rows."""
    rows = jax.random.normal(jax.random.key(0), (256, D), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (3, D, WIDTH), jnp.float32)
    sizes = jnp.asarray([40, 0, 90], jnp.int32)
    f = lambda rows, w: moe.grouped_matmul(rows, w, sizes, "pallas", interpret=True)
    np.testing.assert_allclose(
        np.asarray(f(rows, w)[:130]),
        np.asarray(moe.grouped_matmul(rows, w, sizes, "xla")[:130]), atol=1e-5,
    )
    # read through the passes' tiles: zeros from row 130 on, whatever the kernel left
    held = jnp.sum(sizes)
    (out,) = moe._over_live_rows(lambda a: (a,), held, (f(rows, w),))
    assert np.all(np.isfinite(np.asarray(out))) and float(jnp.max(jnp.abs(out[130:]))) == 0.0
    d_w = jax.grad(lambda w: jnp.sum(moe._over_live_rows(lambda a: (a,), held, (f(rows, w),))[0]))
    assert float(jnp.max(jnp.abs(d_w(w)[1]))) == 0.0  # an empty group's weights
    # The layer over a kernel that poisons every row it does not own, in
    # dead tiles and in the tile at the boundary: finite, and the reference's.
    monkeypatch.setattr(moe, "grouped_matmul", _poisoned_matmul)
    for held_rows in (0, TILE + 5, 3 * TILE):
        cfg, p, x = _layer_holding(held_rows)
        stats = _assert_layer_matches_the_mask(cfg, p, x, kind="xla")
        assert int(stats["rows_run"]) == -(-held_rows // TILE) * TILE


# --- the token-major gathers: backward over a token's live slots, no wider than the buffer

WIDE = 32  # the input's width where the router is an identity over 32 experts

# (experts, choices, the span held): a token's choices as many as fit the held
# experts and more of them than are held (Nemotron's 22 of 512 with 8 held,
# scaled down: the gather of the rows' cotangent is then two slots wide, not six).
ROUTERS = {
    "choices-fit-the-held": dict(n_routed=8, top_k=2, held=(2, 4)),
    "more-choices-than-held": dict(n_routed=32, top_k=6, held=(4, 2)),
}
# tokens, and how many held experts token t chooses (of two at most).
CHOICES = {
    "held-0": (64, lambda t: 0),
    "every-slot-live": (64, lambda t: 2),
    "a-count-that-ends-inside-a-row-tile": (64, lambda t: 2 if t < 21 else 0),
    "live-slots-among-dead-ones": (64, lambda t: t % 3),
    "tokens-the-row-tile-does-not-divide": (72, lambda t: (t + 1) % 3),
}


def _layer_choosing(router: str, expert: str, choices: str):
    """A layer whose router is an identity on the input's first columns, which
    hold the logits: token t chooses as many held experts as `CHOICES` says
    and absent ones for the rest of its choices."""
    cfg = RoutedConfig(scale=1.8, expert=expert, **ROUTERS[router])
    tokens, n_held = CHOICES[choices]
    first, count = cfg.span
    absent = [e for e in range(cfg.n_routed) if not first <= e < first + count]
    logits = np.asarray(jax.random.normal(jax.random.key(2), (tokens, cfg.n_routed))) * 0.3
    for t in range(tokens):
        chosen = [first + (t + i) % count for i in range(n_held(t))]
        chosen += [absent[(t + i) % len(absent)] for i in range(cfg.top_k - n_held(t))]
        logits[t, chosen] += 4.0
    p = init_routed_params(cfg, jax.random.key(0), WIDE, WIDTH, jnp.float32)
    p["router"] = jnp.eye(WIDE, cfg.n_routed, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (tokens, WIDE), jnp.float32)
    x = x.at[:, : cfg.n_routed].set(jnp.asarray(logits)).reshape(1, tokens, WIDE)
    return cfg, p, x, sum(n_held(t) for t in range(tokens))


@pytest.mark.parametrize("choices", CHOICES)
@pytest.mark.parametrize("expert", ["swiglu", "relu2"])
@pytest.mark.parametrize("router", ROUTERS)
def test_the_token_major_pass_is_the_mask_over_experts(row_tile, router, expert, choices):
    """Forward and the gradients with respect to x, the router and the
    experts' matrices, with the gather of the rows' cotangent, where a token
    has more choices than experts are held, over its two live slots alone."""
    cfg, p, x, held = _layer_choosing(router, expert, choices)
    tokens = x.shape[1]
    stats = _assert_layer_matches_the_mask(cfg, p, x, kind="xla")
    assert int(stats["assignments"]) == tokens * cfg.top_k
    assert int(stats["assignments_held"]) == held and int(stats["dropped"]) == 0
    assert int(stats["slots_read"]) == tokens * cfg.top_k + cfg.buffer_rows(tokens) == tokens * (cfg.top_k + 2)


def test_the_counters_sum_the_slots_a_pass_reads_over_the_blocks(row_tile):
    cfg, p, x, held = _layer_choosing("more-choices-than-held", "relu2", "live-slots-among-dead-ones")
    stats = [routed_experts(cfg, p, x, kind="xla")[1], routed_experts(cfg, p, 2 * x, kind="xla")[1]]
    counted = {k: float(v) for k, v in moe.routing_counters(cfg, stats).items()}
    assert counted["moe.slots_read"] == 2 * 64 * (6 + 2) and counted["moe.assignments"] == 2 * 64 * 6
    assert counted["moe.assignments_held"] == 2 * held and counted["moe.dropped"] == 0.0


def _rows_back_by_token(rows, slot, held, weight=None):
    """The pass token by token: the gather of every slot, the product rounded
    by a cast, the select, the sum."""
    picked = rows[jnp.minimum(slot, rows.shape[0] - 1)].astype(jnp.float32)  # [T, j, d]
    if weight is not None:
        picked = (picked * weight[..., None]).astype(rows.dtype).astype(jnp.float32)
    return jnp.sum(jnp.where((slot < held)[..., None], picked, 0), axis=1).astype(rows.dtype)


@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
@pytest.mark.parametrize("tokens", [8, 64, 77])
def test_rows_back_is_the_gather_product_select_and_sum(tokens, weighted):
    """Bit for bit in bfloat16, at a count of none, one, some and every row."""
    keys = jax.random.split(jax.random.key(tokens), 3)
    rows = jax.random.normal(keys[0], (tokens * 2, D), jnp.bfloat16)
    slot = jax.random.permutation(keys[1], tokens * 2).astype(jnp.int32).reshape(tokens, 2)
    weight = jax.random.uniform(keys[2], (tokens, 2)).astype(jnp.bfloat16) if weighted else None
    for held in (0, 1, tokens // 2 + 3, tokens * 2):
        got = moe._rows_back(rows, slot, jnp.asarray(held, jnp.int32), weight)
        want = _rows_back_by_token(rows, slot, held, weight)
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_live_slots_first_keeps_the_smallest_slots():
    slot = jnp.asarray([[40, 3, 55, 7, 41], [9, 50, 60, 2, 70], [80, 81, 82, 83, 84]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(moe._live_slots_first(slot, 2)), [[3, 7], [2, 9], [80, 81]])


def test_where_the_choices_fit_the_held_experts_no_sort_is_added():
    """`k <= count`: the layer's text holds the two sorts it had and no other."""
    sorts = lambda r: jax.jit(
        lambda p, x: routed_experts(RoutedConfig(**ROUTERS[r]), p, x, kind="xla")[0]
    ).lower(*_layer_choosing(r, "swiglu", "held-0")[1:3]).as_text().count("stablehlo.sort")
    assert sorts("more-choices-than-held") == sorts("choices-fit-the-held") + 1


def test_config_refuses_what_is_not_a_span():
    with pytest.raises(ValueError, match="span"):
        RoutedConfig(n_routed=8, top_k=2, held=(6, 4))
    with pytest.raises(ValueError, match="top_k"):
        RoutedConfig(n_routed=2, top_k=4)
    assert moe.grouped_matmul_kind("cpu") == "xla" and moe.grouped_matmul_kind("tpu") == "pallas"
