"""`ops/selective_scan.py` against a hand-written loop in NumPy float64;
`ops/pallas_selective_scan.py`'s kernel pair in the Pallas interpreter against
it, value and every input's gradient, over several time chunks and channel tiles
with a state that is still alive at the end; and the rule that says who takes
the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops import pallas_selective_scan as kernels
from deeplearning_cfn_tpu.ops.selective_scan import selective_scan


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Chunks of 16 tokens in groups of 8 (16 under bfloat16 rows), a lane
    tile a grid step: four chunks and two channel tiles at the tests' sizes."""
    monkeypatch.setattr(kernels, "CHUNK", 16)
    monkeypatch.setattr(kernels, "GROUP", 8)
    monkeypatch.setattr(kernels, "CHANNELS_A_STEP", 128)


def inputs(seed, b=2, S=64, I=256, N=16, dtype=jnp.float32, dt_low=1e-3, dt_high=1e-1):
    """x, dt, A, B, C, D and dy: dt log-uniform in [dt_low, dt_high],
    A[c, n] = -(n + 1) times a factor a channel, the rest normal."""
    k = jax.random.split(jax.random.key(seed), 8)
    x, dy = (jax.random.normal(kk, (b, S, I)).astype(dtype) for kk in (k[0], k[6]))
    dt = jnp.exp(jax.random.uniform(k[1], (b, S, I), minval=np.log(dt_low), maxval=np.log(dt_high)))
    A = -jnp.arange(1, N + 1, dtype=jnp.float32) * jax.random.uniform(k[2], (I, 1), minval=0.5, maxval=2.0)
    B, C = (jax.random.normal(kk, (b, S, N)).astype(dtype) for kk in (k[3], k[4]))
    return (x, dt, A, B, C, jax.random.normal(k[5], (I,))), dy


def loop(x, dt, A, B, C, D):
    """The recurrence as it is written down, float64."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, D))
    b, S, I = x.shape
    h, y = np.zeros((b, I, A.shape[1])), np.zeros((b, S, I))
    for t in range(S):
        h = np.exp(dt[:, t, :, None] * A) * h + (dt[:, t] * x[:, t])[:, :, None] * B[:, t, None, :]
        y[:, t] = (h * C[:, t, None, :]).sum(-1) + D * x[:, t]
    return y, h


@pytest.mark.parametrize("S", [64, 70], ids=["whole-chunks", "a-ragged-tail"])
def test_the_plain_form_is_the_recurrence_as_written(S):
    args, _ = inputs(S, S=S, I=24, N=5)
    want, _ = loop(*args)
    np.testing.assert_allclose(np.asarray(selective_scan(*args)), want, rtol=2e-5, atol=2e-5)
    low = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a for i, a in enumerate(args))
    assert selective_scan(*low).dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernels_are_the_plain_form_value_and_every_gradient(dtype, monkeypatch):
    if dtype == jnp.bfloat16:
        monkeypatch.setattr(kernels, "GROUP", 16)
    args, dy = inputs(7, dtype=dtype)
    # the state is alive at the end: the slowest state of the slowest channel keeps
    # most of what the first chunk gave it over the four chunks, and the last token reads it
    _, h = loop(*args)
    decay = np.exp(np.asarray(args[1], np.float64).sum(1)[..., None] * np.asarray(args[2], np.float64))
    assert decay.max() > 0.2 and np.abs(h).max() > 0.1
    moved = (args[0].at[:, 0].add(4.0),) + args[1:]
    run = lambda a: kernels.selective_scan(*a, interpret=True).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(run(moved)[:, -1] - run(args)[:, -1]))) > 1e-2

    want, pull_want = jax.vjp(selective_scan, *args)
    got, pull_got = jax.vjp(lambda *a: kernels.selective_scan(*a, interpret=True), *args)
    assert got.dtype == dtype and got.shape == want.shape
    f32 = jnp.float32
    if dtype == jnp.bfloat16:
        far = lambda a, w: float(jnp.linalg.norm((a.astype(f32) - w.astype(f32)).ravel())
                                 / jnp.linalg.norm(w.astype(f32).ravel()))
        assert far(got, want) < 0.005
        for name, g, w in zip("x dt A B C D".split(), pull_got(dy), pull_want(dy)):
            assert g.dtype == w.dtype and g.shape == w.shape, name
            assert far(g, w) < 0.01, name
        return
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    for name, g, w in zip("x dt A B C D".split(), pull_got(dy), pull_want(dy)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5 * scale, err_msg=name)


def test_who_takes_the_kernels(monkeypatch):
    monkeypatch.undo()  # the module's own constants
    shaped = lambda S, I, N, dtype=jnp.bfloat16: (
        jax.ShapeDtypeStruct((1, S, I), dtype), jax.ShapeDtypeStruct((I, N), jnp.float32))
    cell = shaped(8192, 5120, 16)  # jamba2-3b.train-s8192x1
    assert kernels.takes_kernel(*cell, backend="tpu")
    assert not kernels.takes_kernel(*cell, backend="cpu")
    assert not kernels.takes_kernel(*cell)  # the tests' own backend
    assert kernels.takes_kernel(*shaped(256, 128, 32, jnp.float32), backend="tpu")
    refused = {
        "a ragged tail": shaped(8192 + 5, 5120, 16),
        "channels that are no whole lane tiles": shaped(8192, 5120 + 64, 16),
        "a state of 8": shaped(8192, 5120, 8),
        "float16": shaped(8192, 5120, 16, jnp.float16),
    }
    for why, arrays in refused.items():
        assert not kernels.takes_kernel(*arrays, backend="tpu"), why
    # the channels a grid step: the widest whole lane tiles that divide and fit
    assert kernels._width(5120, kernels.CHANNELS_A_STEP) == kernels.CHANNELS_A_STEP
    assert [kernels._width(5120, w) for w in (128, 300, 640, 1000, 5120)] == [128, 256, 640, 640, 5120]


def test_a_refused_shape_reaches_the_plain_form_with_equal_results(monkeypatch):
    """The model's mixer asks the rule: a ragged sequence on a TPU backend is
    `ops/selective_scan`'s, to the bit, and the kernels are never entered."""
    from deeplearning_cfn_tpu.models import mamba_attn as model

    cfg = model.MambaAttnConfig.tiny(ssm_inner=128, ssm_state=16)
    lp = jax.tree_util.tree_map(lambda a: a[0], model.init_params(cfg, jax.random.key(0))["runs"][0])
    n = jax.random.normal(jax.random.key(1), (1, 133, cfg.dim))
    want, _ = model._ssm_mixer(cfg, lp, n)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        kernels, "selective_scan", lambda *a, **k: pytest.fail("the kernels were entered"))
    assert bool(jnp.all(model._ssm_mixer(cfg, lp, n)[0] == want))


def test_the_plain_form_holds_none_of_the_kernel_language():
    from deeplearning_cfn_tpu.ops import selective_scan as plain

    assert "pallas" not in open(plain.__file__).read().lower()
