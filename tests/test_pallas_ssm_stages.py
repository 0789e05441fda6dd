"""`ops/pallas_ssm_stages.py`: the Mamba-2 block's two elementwise stages'
kernels in the Pallas interpreter against the jnp stages of
`models/ssm_attn_moe.py` (`_conv_silu`, `_gate_norm`), value and every
gradient; the halo between row tiles both ways; and the rules that say who
takes the kernels."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import ssm_attn_moe as model
from deeplearning_cfn_tpu.models.ssm_attn_moe import SsmAttnMoeConfig, _conv_silu, _gate_norm
from deeplearning_cfn_tpu.ops import pallas_ssm_stages as stages

ROWS = 32  # a row tile of these tests: S 128 is four of them
EPS = 1e-5
f32 = jnp.float32


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Tiles of 32 rows by 128 channels, the body in chunks of 16: several
    tiles and several chunks a tile at the tests' sizes."""
    monkeypatch.setattr(stages, "CONV_TILE", (ROWS, 128))
    monkeypatch.setattr(stages, "CONV_CHUNK", 16)
    monkeypatch.setattr(stages, "GATE_NORM_ROWS", ROWS)
    monkeypatch.setattr(stages, "GATE_NORM_CHUNK", 16)


def far(a, w) -> float:
    a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
    return float(np.linalg.norm(a - w) / max(np.linalg.norm(w), 1e-30))


def conv_inputs(seed: int, dtype, b: int = 2, S: int = 128, C: int = 256, taps: int = 4):
    k = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(k[0], (b, S, C), dtype), (0.5 * jax.random.normal(k[1], (taps, C))).astype(dtype),
        0.1 * jax.random.normal(k[2], (C,)),
    ), jax.random.normal(k[3], (b, S, C), dtype)


def norm_inputs(seed: int, dtype, b: int = 2, S: int = 128, inner: int = 1024):
    k = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(k[0], (b, S, inner), dtype), jax.random.normal(k[1], (b, S, inner), dtype),
        1.0 + 0.1 * jax.random.normal(k[2], (inner,)),
    ), jax.random.normal(k[3], (b, S, inner), dtype)


def judged(names, got, pull_got, jnp_form, args, g):
    """A kernel's value and gradients beside the jnp form's: float32 equal to
    rounding; bfloat16 no further from the jnp form on float32 operands than
    the jnp form in bfloat16 is, in each operand's own type."""
    want, pull_want = jax.vjp(jnp_form, *args)
    rows = list(zip(names, (got, *pull_got(g)), (want, *pull_want(g))))
    for (name, a, w), operand in zip(rows, (args[0], *args)):
        assert a.dtype == w.dtype == operand.dtype and a.shape == w.shape, name
    if g.dtype == f32:
        for name, a, w in rows:
            np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w))), err_msg=name)
        return
    exact, pull_exact = jax.vjp(jnp_form, *(a.astype(f32) for a in args))
    for (name, a, w), e in zip(rows, (exact, *pull_exact(g.astype(f32)))):
        assert far(a, e) < max(1e-4, 1.5 * far(w, e)), name


# --- the convolution with its SiLU ------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [4, 3, 1, 16])
def test_the_convolutions_kernels_are_the_jnp_stage_value_and_every_gradient(taps, dtype):
    args, g = conv_inputs(taps, dtype, taps=taps)
    got, pull = jax.vjp(partial(stages.conv_silu, interpret=True), *args)
    judged(("value", "xBC", "w", "bias"), got, pull, _conv_silu, args, g)


# A spike at one (row, channel) of xBC or of the cotangent, by its row: the last
# and the first rows of a tile and of a chunk, of the sequence's first and last tile.
SPIKES = [0, 15, 16, ROWS - 1, ROWS, 2 * ROWS - 3, 3 * ROWS + 1, 4 * ROWS - 1]


@pytest.mark.parametrize("row", SPIKES)
def test_a_spike_crosses_the_tiles_edges_both_ways_and_nothing_comes_from_before_the_start(row):
    """xBC zero but for one row: the value's rows after it (the halo forward)
    and, with the cotangent zero but for one row, dx's rows before it (the
    halo backward) are the jnp stage's, and a second sequence, all zeros,
    reads the bias alone: nothing of the first reaches it."""
    (x, w, bias), _ = conv_inputs(row, f32)
    spike = jnp.zeros_like(x).at[0, row, 5].set(3.0)
    got = stages.conv_silu(spike, w, bias, interpret=True)
    np.testing.assert_allclose(got, _conv_silu(spike, w, bias), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[1], jnp.broadcast_to(jax.nn.silu(bias), got[1].shape), rtol=1e-6)
    reached = np.flatnonzero(np.abs(np.asarray(got[0, :, 5] - jax.nn.silu(bias[5]))) > 1e-6)
    assert reached.tolist() == [r for r in range(row, row + 4) if r < x.shape[1]]
    # dx of a real xBC under a one-row cotangent: rows `row - 3 .. row` alone.
    g = jnp.zeros_like(x).at[0, row, 5].set(1.0)
    want = jax.vjp(_conv_silu, x, w, bias)[1](g)
    got = jax.vjp(partial(stages.conv_silu, interpret=True), x, w, bias)[1](g)
    for name, a, e in zip(("xBC", "w", "bias"), got, want):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6, err_msg=name)
    touched = np.flatnonzero(np.asarray(got[0][0, :, 5]))
    assert touched.tolist() == [r for r in range(row - 3, row + 1) if r >= 0]


@pytest.mark.parametrize("tile, chunk", [((128, 256), 128), ((64, 128), 32), ((32, 256), 16), ((16, 128), 16)])
def test_the_convolutions_tiles_and_chunks_change_nothing(tile, chunk):
    (x, w, bias), g = conv_inputs(7, f32)
    run = lambda tile, chunk: (
        stages._conv_forward(x, w, bias, tile=tile, chunk=chunk, interpret=True),
        *stages._conv_backward(x, w, bias, g, tile=tile, chunk=chunk, interpret=True),
    )
    for name, a, e in zip(("value", "dx", "dw", "dbias"), run(tile, chunk), run((128, 128), 128)):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5, err_msg=name)


# --- the gated group norm ----------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 8, 2])
def test_the_gated_norms_kernels_are_the_jnp_stage_value_and_every_gradient(groups, dtype):
    args, g = norm_inputs(groups, dtype)
    got, pull = jax.vjp(lambda y, z, w: stages.gate_norm(y, z, w, groups, EPS, interpret=True), *args)
    judged(("value", "y", "z", "w"), got, pull, partial(_gate_norm, groups=groups, eps=EPS), args, g)


def test_a_group_is_normalised_by_its_own_mean_square_alone():
    """One group's y scaled a thousandfold moves that group's result by the
    rounding of eps alone and no other group's at all."""
    (y, z, w), _ = norm_inputs(3, f32)
    run = lambda y: stages.gate_norm(y, z, w, 8, EPS, interpret=True)
    moved = run(y.at[:, :, 128:256].multiply(1000.0))
    np.testing.assert_allclose(moved[:, :, 128:256], run(y)[:, :, 128:256], rtol=1e-3, atol=1e-3)
    assert bool(jnp.all(moved[:, :, :128] == run(y)[:, :, :128]))
    assert bool(jnp.all(moved[:, :, 256:] == run(y)[:, :, 256:]))


@pytest.mark.parametrize("rows, chunk", [(128, 128), (64, 16), (16, 16)])
def test_the_gated_norms_tiles_and_chunks_change_nothing(rows, chunk):
    (y, z, w), g = norm_inputs(9, f32)
    kw = dict(groups=4, eps=EPS, interpret=True)
    run = lambda rows, chunk: (
        stages._gate_norm_forward(y, z, w, rows=rows, chunk=chunk, **kw),
        *stages._gate_norm_backward(y, z, w, g, rows=rows, chunk=chunk, **kw),
    )
    for name, a, e in zip(("value", "dy", "dz", "dw"), run(rows, chunk), run(32, 32)):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5, err_msg=name)


# --- the rules ---------------------------------------------------------------------------

CELL = SsmAttnMoeConfig()  # nemotron-3-super-120b-a12b.train-s8192x1's widths
TINY = SsmAttnMoeConfig.tiny()


def conv_shapes(S=8192, C=CELL.ssm_conv_dim, taps=CELL.conv_taps, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((1, S, C), dtype), jax.ShapeDtypeStruct((taps, C), dtype)


def norm_shapes(S=8192, inner=CELL.ssm_inner, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((1, S, inner), dtype), jax.ShapeDtypeStruct((1, S, inner), dtype)


@pytest.fixture
def the_modules_tiles(monkeypatch):
    """The rules at the tiles the module ships, not at this file's small ones."""
    monkeypatch.undo()


CONV_RULE = {
    "the cell's shapes on a TPU": (conv_shapes(), "tpu", True),
    "float32 on a TPU": (conv_shapes(dtype=f32), "tpu", True),
    "the cell's shapes on the CPU": (conv_shapes(), "cpu", False),
    "the tests' own backend": (conv_shapes(), None, False),
    "tiny": (conv_shapes(20, TINY.ssm_conv_dim, dtype=f32), "tpu", False),
    "channels that are no lane multiple": (conv_shapes(C=10240 + 64), "tpu", False),
    "a ragged sequence": (conv_shapes(S=8192 + 5), "tpu", False),
    "float16": (conv_shapes(dtype=jnp.float16), "tpu", False),
    "as many taps as the halo holds": (conv_shapes(taps=16), "tpu", True),
    "more taps than the halo holds": (conv_shapes(taps=17), "tpu", False),
}


@pytest.mark.parametrize("why", CONV_RULE)
def test_the_shapes_and_the_backend_choose_the_convolutions_kernels(why, the_modules_tiles):
    arrays, backend, takes = CONV_RULE[why]
    assert stages.takes_conv_kernel(*arrays, backend=backend) == takes


NORM_RULE = {
    "the cell's shapes on a TPU": (norm_shapes(), 8, "tpu", True),
    "one group of 1024 in float32": (norm_shapes(512, 1024, f32), 1, "tpu", True),
    "the cell's shapes on the CPU": (norm_shapes(), 8, "cpu", False),
    "the tests' own backend": (norm_shapes(), 8, None, False),
    "tiny": (norm_shapes(20, TINY.ssm_inner, f32), TINY.ssm_groups, "tpu", False),
    "a group that is no lane multiple": (norm_shapes(inner=8 * 192), 8, "tpu", False),
    "a group wider than a tile holds": (norm_shapes(), 1, "tpu", False),
    "a ragged sequence": (norm_shapes(S=8192 + 5), 8, "tpu", False),
    "float16": (norm_shapes(dtype=jnp.float16), 8, "tpu", False),
    "a gate of another type": ((norm_shapes()[0], norm_shapes(dtype=f32)[1]), 8, "tpu", False),
}


@pytest.mark.parametrize("why", NORM_RULE)
def test_the_shapes_and_the_backend_choose_the_gated_norms_kernels(why, the_modules_tiles):
    arrays, groups, backend, takes = NORM_RULE[why]
    assert stages.takes_gate_norm_kernel(*arrays, groups, backend=backend) == takes


@pytest.mark.parametrize("S", [133, 20])
def test_a_refused_shape_reaches_the_jnp_stages_with_equal_results(S, monkeypatch):
    """The model's mixer asks the rules: a ragged sequence on a TPU backend is
    `_conv_silu`'s and `_gate_norm`'s, each under its own `jax.checkpoint` as
    at the parent, value and gradients to the bit, and no kernel is entered."""
    cfg = SsmAttnMoeConfig.tiny(ssm_heads=2, ssm_head_dim=64, ssm_groups=1, ssm_state=128, dim=32)
    lp = model._block_params(cfg, jax.random.key(0), "M")
    n = jax.random.normal(jax.random.key(1), (1, S, cfg.dim))
    both = jax.value_and_grad(lambda lp, n: model._ssm_mixer(cfg, lp, n).sum(), argnums=(0, 1))
    want = both(lp, n)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("conv_silu", "gate_norm"):
        monkeypatch.setattr(stages, name, lambda *a, **k: pytest.fail("a kernel was entered"))
    got = both(lp, n)
    for a, e in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
        assert bool(jnp.all(a == e))
