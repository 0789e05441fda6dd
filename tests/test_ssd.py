"""`ops/ssd.py`: the chunked state-space scan against the recurrence it stands
for, a token at a time, value and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops.ssd import ssd


@pytest.fixture(autouse=True)
def highest_precision():
    """Float32 products in full for this file's tests, and for no other's."""
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(x, dt, A, B, C, D):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (B, C))  # a head reads its group's

    def step(h, t):
        xt, dtt, Bt, Ct = t
        h = jnp.exp(dtt * A)[..., None, None] * h + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, Ct) + D[:, None] * xt

    _, y = jax.lax.scan(
        step, jnp.zeros((b, H, P, N)), tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch))
    )
    return jnp.moveaxis(y, 0, 1)


def inputs(seed, S, H=4, P=3, G=2, N=5, b=2, dt_scale=1.0, a_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (b, S, H, P)),
        dt=dt_scale * jax.nn.softplus(jax.random.normal(k[1], (b, S, H))),
        A=-a_scale * jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0),
        B=jax.random.normal(k[3], (b, S, G, N)),
        C=jax.random.normal(k[4], (b, S, G, N)),
        D=jax.random.normal(k[5], (H,)),
    ), jax.random.normal(k[6], (b, S, H, P))


@pytest.mark.parametrize(
    "S, chunk, G",
    [(16, 4, 2), (13, 4, 2), (3, 8, 1), (24, 8, 4)],
    ids=["whole-chunks", "a-ragged-tail", "shorter-than-a-chunk", "a-group-a-head"],
)
def test_the_chunked_scan_is_the_recurrence_value_and_every_gradient(S, chunk, G):
    args, dy = inputs(S, S, G=G, dt_scale=0.1)
    names = list(args)
    want, pull_want = jax.vjp(lambda *a: recurrence(*a), *args.values())
    got, pull_got = jax.vjp(lambda *a: ssd(*a, chunk), *args.values())
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, g, w in zip(names, pull_got(dy), pull_want(dy)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4, err_msg=name)


def test_a_strong_decay_overflows_nothing_because_the_mask_is_inside_the_exponent():
    """dt A down to -160 a token: over a chunk of 8 the upper triangle's
    cs_i - cs_j passes +1000, whose exp is inf in float32; times a mask's 0
    that is NaN, forward or backward.  Inside the exponent it never exists."""
    args, dy = inputs(7, 16, dt_scale=10.0)
    assert float(jnp.min(args["dt"] * args["A"])) < -100.0
    want, pull_want = jax.vjp(lambda *a: recurrence(*a), *args.values())
    got, pull_got = jax.vjp(lambda *a: ssd(*a, 8), *args.values())
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, g, w in zip(args, pull_got(dy), pull_want(dy)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4, err_msg=name)


def test_the_state_is_carried_over_many_chunks():
    """A weak decay (dt A near -0.01) and 32 chunks: the last token still sees
    the first, so a scan that dropped or reset the carried state would show."""
    args, _ = inputs(11, 64, dt_scale=0.01, a_scale=0.1)
    got = ssd(*args.values(), 2)
    np.testing.assert_allclose(got, recurrence(*args.values()), rtol=2e-5, atol=2e-5)
    moved = dict(args, x=args["x"].at[:, 0].add(1.0))
    assert float(jnp.max(jnp.abs(ssd(*moved.values(), 2)[:, -1] - got[:, -1]))) > 1e-3


def test_bfloat16_operands_accumulate_in_float32_and_come_back_in_bfloat16():
    args, _ = inputs(3, 16, dt_scale=0.1)
    low = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v for k, v in args.items()}
    got = ssd(*low.values(), 4)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(v.astype(jnp.float32) for v in low.values()))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.05 * float(jnp.max(jnp.abs(want)))


def test_nothing_in_the_module_is_a_kernel_or_a_hand_written_backward_pass():
    import inspect

    from deeplearning_cfn_tpu.ops import ssd as module

    source = inspect.getsource(module)
    assert "custom_vjp" not in source and "pallas" not in source and "defvjp" not in source
