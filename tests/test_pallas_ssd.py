"""`ops/pallas_ssd.py`: the scan's kernels in the Pallas interpreter against the
recurrence a token at a time and against the XLA form (`ops/ssd.ssd`), value
and all six gradients; and the rule that says who takes the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models.ssm_attn_moe import SsmAttnMoeConfig
from deeplearning_cfn_tpu.ops import pallas_ssd
from deeplearning_cfn_tpu.ops.ssd import ssd as xla_ssd
from tests.test_ssd import inputs, recurrence


@pytest.fixture(autouse=True)
def highest_precision():
    """Float32 products in full for this file's tests, and for no other's."""
    with jax.default_matmul_precision("highest"):
        yield


CHUNK, STATE = 128, 128
# S, heads, head size, groups, the scale of dt, operand type
CASES = {
    "whole-chunks": (256, 4, 64, 2, 0.1, jnp.float32),
    "many-chunks-carry-the-state": (1024, 2, 64, 1, 0.01, jnp.float32),
    "a-group-a-head": (256, 2, 128, 2, 0.1, jnp.float32),
    "a-group-of-several-heads": (256, 4, 64, 1, 0.1, jnp.float32),
    "bfloat16-operands": (256, 4, 64, 2, 0.1, jnp.bfloat16),
    "a-strong-decay": (256, 2, 64, 1, 10.0, jnp.float32),
}


def case_inputs(case):
    S, H, P, G, dt_scale, dtype = CASES[case]
    weak = dict(a_scale=0.01) if case.startswith("many-chunks") else {}  # e^-0.6 over the sequence
    args, dy = inputs(len(case), S, H=H, P=P, G=G, N=STATE, b=1, dt_scale=dt_scale, **weak)
    args = {k: v.astype(dtype) if k in ("x", "B", "C") else v for k, v in args.items()}
    return args, dy.astype(dtype)


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_are_the_recurrence_and_the_xla_form_value_and_every_gradient(case):
    args, dy = case_inputs(case)
    f32 = jnp.float32
    exact = [v.astype(f32) for v in args.values()]
    want, pull_want = jax.vjp(recurrence, *exact)
    xla, pull_xla = jax.vjp(lambda *a: xla_ssd(*a, CHUNK), *args.values())
    got, pull_got = jax.vjp(lambda *a: pallas_ssd.ssd(*a, CHUNK, interpret=True), *args.values())
    assert got.dtype == dy.dtype and got.shape == want.shape
    grads = list(zip(args, pull_got(dy), pull_xla(dy), pull_want(dy.astype(f32))))
    if case == "a-strong-decay":
        assert float(jnp.min(args["dt"] * args["A"])) < -100.0
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in (got, *(g for _, g, _, _ in grads)))
    if dy.dtype == jnp.bfloat16:
        # No further from the float32 recurrence than the XLA form in bfloat16.
        far = lambda a, w: float(jnp.linalg.norm((a.astype(f32) - w).ravel()) / jnp.linalg.norm(w.ravel()))
        assert far(got, want) < 0.01
        for name, g, x, w in grads:
            assert g.dtype == x.dtype, name
            assert far(g, w) < max(0.01, 1.5 * far(x, w)), name
        return
    # The strong decay's gradients pass 1e3, and over a chunk of 128 its
    # cumulative sums pass -1e4, where a float32 differs by 1e-3 from the next:
    # both chunked forms then lie that far from the recurrence, and together.
    scale = lambda w: max(1.0, float(jnp.max(jnp.abs(w))))
    loose = 10.0 if case == "a-strong-decay" else 1.0
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * scale(want))
    np.testing.assert_allclose(got, xla, rtol=2e-5, atol=2e-5 * scale(want))
    for name, g, x, w in grads:
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * loose * scale(w), err_msg=name)
        np.testing.assert_allclose(g, x, rtol=2e-4, atol=2e-4 * scale(w), err_msg=name)


def test_the_state_reaches_the_last_token_from_the_first_over_eight_chunks():
    args, _ = case_inputs("many-chunks-carry-the-state")
    run = lambda a: pallas_ssd.ssd(*a.values(), CHUNK, interpret=True)
    moved = dict(args, x=args["x"].at[:, 0].add(1.0))
    assert float(jnp.max(jnp.abs(run(moved)[:, -1] - run(args)[:, -1]))) > 1e-3


@pytest.mark.parametrize("heads_a_step, chunks_a_step", [(2, 1), (4, 2), (2, 4)])
def test_a_part_of_a_group_a_step_and_several_chunks_a_step_change_nothing(heads_a_step, chunks_a_step):
    args, dy = inputs(5, 512, H=8, P=64, G=2, N=STATE, b=1, dt_scale=0.1)
    x, dt, A, B, C, D = args.values()
    dt = dt.transpose(0, 2, 1)
    cs = jnp.cumsum((dt * A[:, None]).reshape(1, 8, 4, CHUNK), axis=-1).reshape(1, 8, 512)
    flat = (x.reshape(1, 512, 512), dt, cs, B, C, D)

    def run(**tiles):
        kw = dict(chunk=CHUNK, interpret=True, **tiles)
        y, before = pallas_ssd._forward(*flat, save_states=True, **kw)
        unsaved, none = pallas_ssd._forward(*flat, save_states=False, **kw)
        assert none is None and bool(jnp.all(unsaved == y))
        return (y, before, *pallas_ssd._backward(*flat, before, dy.reshape(1, 512, 512), **kw))

    want = run(heads_a_step=4, chunks_a_step=1)  # a whole group, a chunk
    for name, g, w in zip("y before dx ddt dcs dB dC dD".split(), run(
        heads_a_step=heads_a_step, chunks_a_step=chunks_a_step
    ), want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-4, err_msg=name)


def test_the_shapes_and_the_backend_choose_the_kernels():
    bf = jnp.bfloat16
    shaped = lambda S, H, P, G, N, dtype=bf: (
        jax.ShapeDtypeStruct((1, S, H, P), dtype), jax.ShapeDtypeStruct((1, S, G, N), dtype)
    )
    cell = shaped(8192, 128, 64, 8, 128)  # nemotron-3-super-120b-a12b.train-s8192x1
    assert pallas_ssd.takes_kernel(*cell, 128, backend="tpu")
    assert not pallas_ssd.takes_kernel(*cell, 128, backend="cpu")
    assert not pallas_ssd.takes_kernel(*cell, 128)  # the tests' own backend
    assert pallas_ssd.takes_kernel(*shaped(256, 2, 128, 2, 128, jnp.float32), 128, backend="tpu")
    tiny = SsmAttnMoeConfig.tiny()
    assert not pallas_ssd.takes_kernel(
        *shaped(20, tiny.ssm_heads, tiny.ssm_head_dim, tiny.ssm_groups, tiny.ssm_state),
        tiny.chunk, backend="tpu",
    )
    refused = {
        "a ragged tail": (shaped(8192 + 5, 128, 64, 8, 128), 128),
        "a chunk of 64": (shaped(8192, 128, 64, 8, 128), 64),
        "a state of 64": (shaped(8192, 128, 64, 8, 64), 128),
        "a group of one head of 64": (shaped(8192, 8, 64, 8, 128), 128),
        "heads of 96": (shaped(8192, 32, 96, 8, 128), 128),
        "float16": (shaped(8192, 128, 64, 8, 128, jnp.float16), 128),
    }
    for why, (arrays, chunk) in refused.items():
        assert not pallas_ssd.takes_kernel(*arrays, chunk, backend="tpu"), why


def test_a_refused_shape_reaches_the_xla_form_with_equal_results(monkeypatch):
    """The model's mixer asks the rule: a ragged sequence on a TPU backend is
    `ops/ssd.ssd`'s, to the bit, and the kernels are never entered."""
    from deeplearning_cfn_tpu.models import ssm_attn_moe as model

    cfg = SsmAttnMoeConfig.tiny(
        ssm_heads=2, ssm_head_dim=64, ssm_groups=1, ssm_state=128, chunk=128, dim=32
    )
    lp = model._block_params(cfg, jax.random.key(0), "M")
    n = jax.random.normal(jax.random.key(1), (1, 133, cfg.dim))
    want = model._ssm_mixer(cfg, lp, n)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_ssd, "ssd", lambda *a, **k: pytest.fail("the kernels were entered"))
    assert bool(jnp.all(model._ssm_mixer(cfg, lp, n) == want))
