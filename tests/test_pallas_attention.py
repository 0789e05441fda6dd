"""Flash attention (Pallas kernel, interpret mode asked for by name) vs XLA
attention.

Covers: causal/non-causal, GQA, non-divisible sequence lengths (padding +
masking), and gradients through the custom VJP, whose backward pass is one
fused Pallas kernel where the shapes allow it and a pair of kernels
elsewhere.  The compiled kernels' numerics are checked on the chip by
chip_smoke.py.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops.attention import dot_product_attention
from deeplearning_cfn_tpu.ops import pallas_attention
from tests.kernel_text import (
    equations as _equations,
    kernels_without_locations as _kernels_without_locations,
    named as _named,
)

# The kernel compiles through Mosaic unless told otherwise; on the CPU mesh
# every call here asks for the interpreter.
flash_attention = functools.partial(pallas_attention.flash_attention, interpret=True)


def _qkv(b=2, s=64, hq=4, hkv=2, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_matches_xla_attention(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_gqa_head_mapping():
    q, k, v = _qkv(hq=8, hkv=2)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ragged_seq_len_padding():
    # 50 is not a multiple of any block size → exercises padding + kv mask.
    q, k, v = _qkv(s=50)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_io():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


def _reference_lse(q, k, causal, window=None):
    """log-sum-exp of each query's valid scores, [B, Hq, Sq], in plain XLA."""
    k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        seen = jnp.tril(jnp.ones(s.shape[-2:], bool))
        if window is not None:  # t - window < j <= t
            seen &= ~jnp.tril(seen, -window)
        s = jnp.where(seen, s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


# The forward kernel (interpret mode): output and log-sum-exp against XLA.
# Blocks of 16 over 48 positions are 3 skipped, 3 masked (the diagonal) and 3
# unmasked pairs a (batch, head) under causal; where S is ragged the last q
# block's pairs hold padded rows though they lie inside the triangle, and with
# fewer keys than queries a padded kv block does.
FORWARD_CASES = {
    "mha": dict(hq=4, hkv=4),
    "gqa-4-2": dict(hq=4, hkv=2),
    "gqa-8-2": dict(hq=8, hkv=2),
    "non-causal": dict(causal=False),
    "ragged-50": dict(s=50),
    "ragged-50-non-causal": dict(s=50, causal=False),
    "wide-q-blocks": dict(s=64, block_q=32, block_k=16),
    "wide-k-blocks": dict(s=64, block_q=16, block_k=32),
    "more-keys-than-queries": dict(s=30, sk=50),
    "more-queries-than-keys": dict(s=64, sk=40),
    "no-lse": dict(need_lse=False),
}


@pytest.mark.parametrize("case", FORWARD_CASES)
def test_forward_output_and_lse_match(case):
    kw = dict(s=48, sk=None, hq=4, hkv=2, causal=True, block_q=16, block_k=16, need_lse=True)
    kw.update(FORWARD_CASES[case])
    q, k, v = _qkv(s=kw["s"], hq=kw["hq"], hkv=kw["hkv"])
    if kw["sk"]:
        _, k, v = _qkv(s=kw["sk"], hq=kw["hq"], hkv=kw["hkv"], seed=1)
    out, lse = pallas_attention._flash_forward(
        q, k, v, kw["causal"], q.shape[-1] ** -0.5, kw["block_q"], kw["block_k"],
        True, need_lse=kw["need_lse"],
    )
    ref = dot_product_attention(q, k, v, causal=kw["causal"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    if not kw["need_lse"]:
        assert lse is None
        return
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1])
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(_reference_lse(q, k, kw["causal"])), atol=2e-5, rtol=2e-5
    )


def _forward_kernel_call(s=64, block_q=16, block_k=16, hq=4, hkv=2):
    """The forward's `pallas_call` equation, causal, as it is traced."""
    q, k, v = _qkv(b=1, s=s, hq=hq, hkv=hkv)
    traced = jax.make_jaxpr(
        lambda q, k, v: pallas_attention._flash_forward(q, k, v, True, 0.25, block_q, block_k, True)
    )(q, k, v)
    (call,) = _named(traced.jaxpr, "pallas_call")
    assert call.params["name"] == "_flash_forward"
    return call


def test_forward_kernel_has_a_branch_that_builds_no_mask():
    """A pair wholly inside the triangle runs both matmuls and the softmax
    with no iota, compare or select over the tile; the masked branch keeps
    its iotas and both selects."""
    kernel = _forward_kernel_call().params["jaxpr"]
    branches = [  # primitives by name, through the `jit`s `jnp.where` leaves
        [e.primitive.name for e in _equations(branch.jaxpr)]
        for cond in _named(kernel, "cond")
        for branch in cond.params["branches"]
    ]
    pairs = [names for names in branches if "dot_general" in names]
    assert len(pairs) == 2
    masked, unmasked = sorted(pairs, key=lambda names: "iota" not in names)
    assert masked.count("iota") == 2 and masked.count("dot_general") == 2
    assert masked.count("select_n") >= 4  # s, p and the guards on shift and alpha
    assert unmasked.count("dot_general") == 2 and unmasked.count("exp") == 2
    assert not {"iota", "select_n", "lt", "le", "and"} & set(unmasked)


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 16), (16, 32)])
def test_a_step_above_the_diagonal_names_the_last_kv_block_that_runs(block_q, block_k):
    """The k and v index maps of the forward's grid (batch, head, q block, kv
    block): a step whose pair lies above the diagonal returns the block index
    of the q block's last running pair, so the pipeline fetches nothing for
    it; a running step returns its own; the kv head is the query head's."""
    call = _forward_kernel_call(s=64, block_q=block_q, block_k=block_k)
    mapping = call.params["grid_mapping"]
    nq, nk = mapping.grid[2:]
    assert (nq, nk) == (64 // block_q, 64 // block_k)
    clamped = 0
    for operand in (1, 2):  # k, v
        index_map = mapping.block_mappings[operand].index_map_jaxpr
        for i in range(nq):
            last = ((i + 1) * block_q - 1) // block_k  # holds the block's last query
            for j in range(nk):
                got = jax.core.eval_jaxpr(index_map.jaxpr, index_map.consts, 0, 3, i, j)
                assert [int(x) for x in got] == [0, 1, min(j, last), 0]
                clamped += j > last
    assert clamped > 0


def _grads(attention, q, k, v, jit=False):
    """(dq, dk, dv) of sum(attention(q, k, v)**2) in float32."""

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v).astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    return (jax.jit(grad) if jit else grad)(q, k, v)


@pytest.fixture
def backward_blocks(monkeypatch):
    """Set the backward kernels' (q block, kv block): module constants sized
    for the chip, which `flash_attention`'s block arguments (the forward's
    tiles) do not reach."""

    def set_blocks(block_q: int, block_k: int):
        for name in ("BWD_DKV_BLOCKS", "BWD_DQ_BLOCKS", "BWD_FUSED_BLOCKS"):
            monkeypatch.setattr(pallas_attention, name, (block_q, block_k))

    set_blocks(16, 16)
    return set_blocks


# The backward kernels (interpret mode) against the XLA gradient.  Blocks of
# 16 make every case several (q block, kv block) pairs: skipped, masked and
# unmasked ones under causal, a padded last block where S is ragged.
GRADIENT_CASES = {
    "mha": dict(hq=4, hkv=4),
    "gqa-4-2": dict(hq=4, hkv=2),
    "gqa-8-2": dict(hq=8, hkv=2),
    "non-causal-mha": dict(hq=4, hkv=4, causal=False),
    "non-causal-gqa-8-2": dict(hq=8, hkv=2, causal=False),
    "ragged-50": dict(s=50),
    "ragged-50-non-causal": dict(s=50, causal=False),
    "ragged-50-gqa-8-2": dict(s=50, hq=8, hkv=2),
    "uneven-blocks": dict(s=64, block_q=32, block_k=16),
    "uneven-blocks-wide-k": dict(s=64, block_q=16, block_k=32),
    "one-block": dict(s=48, block_q=1024, block_k=1024),  # the defaults, clamped
    "jit": dict(jit=True),
}


@pytest.mark.parametrize("case", GRADIENT_CASES)
def test_gradients_match(case, backward_blocks):
    kw = dict(s=48, hq=4, hkv=2, causal=True, block_q=16, block_k=16, jit=False)
    kw.update(GRADIENT_CASES[case])
    backward_blocks(kw["block_q"], kw["block_k"])
    q, k, v = _qkv(s=kw["s"], hq=kw["hq"], hkv=kw["hkv"])
    flash = lambda q, k, v: flash_attention(
        q, k, v, kw["causal"], None, kw["block_q"], kw["block_k"]
    )
    ref = lambda q, k, v: dot_product_attention(q, k, v, causal=kw["causal"])
    gf, gr = _grads(flash, q, k, v, jit=kw["jit"]), _grads(ref, q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_bf16_gradients_match_the_float32_xla_gradient(hq, hkv, backward_blocks):
    """bf16 operands on the MXU, float32 statistics and accumulators: the
    forward test's tolerance, relative to the largest gradient."""
    q, k, v = _qkv(s=50, hq=hq, hkv=hkv, dtype=jnp.bfloat16)
    gf = _grads(lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16), q, k, v)
    gr = _grads(
        lambda q, k, v: dot_product_attention(q, k, v, causal=True),
        *(x.astype(jnp.float32) for x in (q, k, v)),
    )
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b) / scale, atol=3e-2
        )


def test_keys_after_every_query_get_exact_zeros(backward_blocks):
    """Causal with more keys than queries: the kv blocks past the last query
    are wholly masked in every row block, so their dk and dv are the
    accumulators' zeros, and padded rows add nothing (no NaN from
    exp(NEG_INF - NEG_INF), no inf * 0)."""
    q, _, _ = _qkv(s=30)
    _, k, v = _qkv(s=50, seed=1)
    flash = lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16)
    ref = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    gf, gr = _grads(flash, q, k, v), _grads(ref, q, k, v)
    for a, b in zip(gf, gr):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    assert not np.asarray(gf[1][:, 30:]).any() and not np.asarray(gf[2][:, 30:]).any()


@pytest.fixture
def the_pair_everywhere(monkeypatch):
    """No dq fits: every backward pass is the pair of kernels, as before PR 44
    and as a call runs today whose resident dq passes the budget."""
    monkeypatch.setattr(pallas_attention, "_BWD_FUSED_DQ_BUDGET", 0)


@pytest.mark.parametrize("form", ["fused", "pair"])
def test_backward_kernels_are_named_apart_from_the_forward(form, request):
    """Lowered for the TPU (no chip needed to lower): the step's text carries
    the scope `attn_bwd`, which attention_backward_ms_per_step reads, and
    only the forward kernel's name begins `_flash_forward`, which
    attention_roofline_share matches and divides by the forward's FLOPs; the
    backward's names begin `_flash_backward`, one kernel or two."""
    import re

    if form == "pair":
        request.getfixturevalue("the_pair_everywhere")

    def loss(q, k, v):
        return pallas_attention.flash_attention(q, k, v).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)
    )
    text = lowered.as_text(debug_info=True)
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    backward = {
        "fused": {"_flash_backward_fused"}, "pair": {"_flash_backward_dkv", "_flash_backward_dq"}
    }[form]
    assert kernels == {"_flash_forward"} | backward
    assert [n for n in kernels if n.startswith("_flash_forward")] == ["_flash_forward"]
    assert all(re.search(r"^_flash_backward", n) for n in backward)
    assert "attn_bwd" in text
    assert not hasattr(pallas_attention, "_blockwise_backward")


# The fused kernel against the pair on the same inputs, both in the interpreter
# at tiles of 16: the file's cases (groups of 1, 4 and 6, heads of 64, 128 and
# 256, a ragged length, non-causal, more keys than queries).
FUSED_CASES = {
    "groups-of-1": dict(hq=4, hkv=4),
    "groups-of-4": dict(hq=8, hkv=2),
    "groups-of-6": dict(hq=6, hkv=1),
    "heads-of-64": dict(d=64),
    "heads-of-128": dict(d=128),
    "heads-of-256": dict(hq=2, hkv=2, d=256),
    "ragged-50": dict(s=50),
    "ragged-2100-tiles-of-256": dict(b=1, s=2100, hq=2, hkv=1, blocks=(256, 256)),
    "non-causal": dict(causal=False),
    "non-causal-ragged-50-groups-of-4": dict(s=50, hq=8, hkv=2, causal=False),
    "uneven-blocks": dict(s=64, blocks=(32, 16)),
    "uneven-blocks-wide-k": dict(s=64, blocks=(16, 32)),
    "one-block": dict(blocks=(48, 48)),
    "keys-after-every-query": dict(s=30, keys=50),
}


def _both_backward_forms(case, dtype):
    kw = dict(b=2, s=48, hq=4, hkv=2, d=16, causal=True, blocks=(16, 16), keys=None)
    kw.update(FUSED_CASES[case])
    q, _, _ = _qkv(b=kw["b"], s=kw["s"], hq=kw["hq"], hkv=kw["hkv"], d=kw["d"], dtype=dtype)
    dout, k, v = _qkv(
        b=kw["b"], s=kw["keys"] or kw["s"], hq=kw["hq"], hkv=kw["hkv"], d=kw["d"], seed=1, dtype=dtype
    )
    dout = dout[:, : kw["s"]]
    scale, blocks = kw["d"] ** -0.5, kw["blocks"]
    out, lse = pallas_attention._flash_forward(q, k, v, kw["causal"], scale, *blocks, True)
    pair = pallas_attention._flash_backward(
        q, k, v, out, lse, dout, kw["causal"], scale, blocks, blocks, True
    )
    fused = pallas_attention._flash_backward_fused(
        q, k, v, out, lse, dout, kw["causal"], scale, blocks, True
    )
    return kw, pair, fused


@pytest.mark.parametrize("case", FUSED_CASES)
def test_fused_backward_is_the_pairs_in_float32(case):
    """dk and dv are the dk/dv kernel's own operations on the same tiles in the
    same order: bit for bit.  dq is the dq kernel's sums in its order, a q
    block's rows over the kv blocks from zeros, by a matmul that contracts the
    first axis of both operands where the dq kernel's contracts the second of
    its left one: the interpreter's CPU routine for it may round a sum
    otherwise (it does at some tiles and not at others), so float32 rounding
    is the bound there."""
    kw, (dq, dk, dv), (fdq, fdk, fdv) = _both_backward_forms(case, jnp.float32)
    assert fdq.dtype == fdk.dtype == fdv.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(fdk), np.asarray(dk))
    np.testing.assert_array_equal(np.asarray(fdv), np.asarray(dv))
    np.testing.assert_allclose(np.asarray(fdq), np.asarray(dq), rtol=1e-5, atol=1e-5)
    if kw["keys"]:  # the keys past the last query: the accumulators' zeros
        assert not np.asarray(fdk[:, kw["s"]:]).any() and not np.asarray(fdv[:, kw["s"]:]).any()
        assert np.isfinite(np.asarray(fdq)).all()


@pytest.mark.parametrize("case", ["groups-of-1", "groups-of-4", "heads-of-128", "ragged-50"])
def test_fused_backward_is_the_pairs_in_bfloat16(case):
    """bfloat16 into the MXU, float32 sums, `ds` cast once: dk and dv bit for
    bit, dq (which leaves in float32 and is scaled and cast by XLA, as the dq
    kernel does as it writes) within the bfloat16 tests' tolerance."""
    _, (dq, dk, dv), (fdq, fdk, fdv) = _both_backward_forms(case, jnp.bfloat16)
    assert fdq.dtype == fdk.dtype == fdv.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(fdk, np.float32), np.asarray(dk, np.float32))
    np.testing.assert_array_equal(np.asarray(fdv, np.float32), np.asarray(dv, np.float32))
    scale = float(jnp.max(jnp.abs(dq.astype(jnp.float32))))
    np.testing.assert_allclose(
        np.asarray(fdq, np.float32) / scale, np.asarray(dq, np.float32) / scale, atol=3e-2
    )


# (window, group, S, head dim) -> whether the backward pass is the fused
# kernel: the six decoder cells' full-causal calls, the Laguna cell's window
# layers, and a sequence whose dq passes the budget.
FUSED_RULE = {
    "ouro-16-16-heads-of-128": ((None, 1, 8192, 128), True),
    "glm-20-20-heads-of-256": ((None, 1, 8192, 256), True),
    "mistral-32-8-heads-of-128-s4096": ((None, 4, 4096, 128), True),
    "lfm2-32-8-heads-of-64": ((None, 4, 8192, 64), True),
    "laguna-full-48-8-heads-of-128": ((None, 6, 8192, 128), True),
    "nemotron-32-2-groups-of-16": ((None, 16, 8192, 128), False),
    "laguna-window-512": ((512, 8, 8192, 128), False),
    "a-window-as-long-as-the-sequence": ((8192, 1, 8192, 128), False),
    "s-65536-groups-of-1": ((None, 1, 65536, 128), False),
    "s-32768-groups-of-1": ((None, 1, 32768, 128), True),
    "s-32768-groups-of-4": ((None, 4, 32768, 128), False),
    "ragged-s2100": ((None, 4, 2100, 128), True),
}


@pytest.mark.parametrize("case", FUSED_RULE)
def test_the_shapes_choose_the_backward_form(case):
    """What the call can see decides: no window, and the float32 dq of a kv
    head's group, both of Pallas's buffers, within `_BWD_FUSED_DQ_BUDGET`;
    the call's VMEM limit is `_BWD_VMEM_LIMIT` and that, under a v5e core's
    128 MiB."""
    args, takes = FUSED_RULE[case]
    assert pallas_attention._takes_fused_backward(*args) is takes
    window, group, seq, head_dim = args
    held = pallas_attention._fused_dq_bytes(group, seq, head_dim)
    assert held == 2 * group * seq * max(head_dim, 128) * 4
    if takes:
        assert pallas_attention._BWD_VMEM_LIMIT + held <= 120 * 1024 * 1024


def test_twenty_heads_of_256_forward_and_gradients(backward_blocks):
    """The latent attention's core as models/mla_moe.py runs it: 20 query and
    20 key/value heads of 256 (192 un-rotated + 64 rotary; the value head is
    256 too, so nothing is padded), scores scaled by 256 ** -0.5, the
    forward's and the backward's tiles clamped from the defaults."""
    backward_blocks(*pallas_attention.BWD_DKV_BLOCKS)
    q, k, v = _qkv(b=1, s=160, hq=20, hkv=20, d=256, seed=3)
    flash = lambda q, k, v: flash_attention(q, k, v, True, 256**-0.5)
    ref = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5
    )
    for a, b in zip(_grads(flash, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_tiles_at_the_decoder_cells_shapes_are_the_sweeps():
    """Head size is not an input of the tile choice: at S 4096 (heads of 128)
    and S 8192 (heads of 256) all three kernels run 1024 x 1024, the best of
    the sweeps on the chip at both shapes (PERF.md, PR 25, 26 and 29)."""
    from deeplearning_cfn_tpu.ops.pallas_attention import _clamp_block

    for seq in (4096, 8192):
        for blocks in (
            (pallas_attention.DEFAULT_BLOCK_Q, pallas_attention.DEFAULT_BLOCK_K),
            pallas_attention.BWD_DKV_BLOCKS,
            pallas_attention.BWD_DQ_BLOCKS,
        ):
            assert [_clamp_block(b, seq) for b in blocks] == [1024, 1024]


def test_block_picker_balances_padding_against_block_size():
    """Effective block selection: keep the big (fast) block for aligned
    sequences, step down for ragged ones instead of paying up to 2.5x in
    padded attention FLOPs (512-block on S=600 would pad to 1024) — but
    never chase the last few percent of padding down to a tiny block:
    round-2 advisor flagged S=600 picking 32 (padded 608) over 128
    (padded 640), trading ~5% padding for a ~40% MXU-efficiency loss."""
    from deeplearning_cfn_tpu.ops.pallas_attention import _clamp_block

    assert _clamp_block(512, 2048) == 512  # aligned: biggest block wins
    assert _clamp_block(1024, 2048) == 1024  # measured-best default
    assert _clamp_block(512, 4096) == 512
    assert _clamp_block(512, 128) == 128  # short seq: clamp to length
    assert _clamp_block(128, 8) == 16  # sublane floor
    # Ragged: 128 pads to 640, within tolerance of the 608 minimum; the
    # tiny 32 block is NOT chosen for its ~5% padding saving.
    assert _clamp_block(512, 600) == 128
    assert _clamp_block(512, 640) == 128  # 640 = 5*128: zero padding
    # Far-from-aligned: 512 pads 600->1024 (+68%), rightly rejected.
    assert _clamp_block(512, 520) == 128  # 128 pads to 640 vs min 528 @16
    # Tolerance respects genuinely large savings: stepping to 16 saves
    # >12.5% only when no bigger block comes close.
    assert _clamp_block(16, 600) == 16
    # Non-power-of-two caller blocks still consider the 128 floor: 384's
    # halving ladder (384, 192, 96...) must not skip over it.
    assert _clamp_block(384, 600) == 128


def test_llama_attention_dispatch_crossover():
    """use_flash_attention means "fastest memory-safe attention": below
    the measured v5e crossover XLA's fused attention wins (3.74 vs
    4.69 ms at S=2048 with round-2 blocks, BENCH_NOTES), so the llama
    path must fall back to XLA there instead of dispatching to the
    Pallas kernel unconditionally."""
    from deeplearning_cfn_tpu.models.llama import LlamaConfig, attention_kind
    from deeplearning_cfn_tpu.ops.pallas_attention import FLASH_CROSSOVER_SEQ

    cfg = LlamaConfig.tiny(vocab_size=64, seq_len=FLASH_CROSSOVER_SEQ)
    cfg = dataclasses.replace(cfg, use_flash_attention=True)
    assert attention_kind(cfg, None, FLASH_CROSSOVER_SEQ, backend="tpu") == "flash"
    assert attention_kind(cfg, None, FLASH_CROSSOVER_SEQ - 1, backend="tpu") == "xla"
    assert attention_kind(cfg, None, FLASH_CROSSOVER_SEQ, backend="cpu") == "xla"
    off = dataclasses.replace(cfg, use_flash_attention=False)
    assert attention_kind(off, None, FLASH_CROSSOVER_SEQ, backend="tpu") == "xla"


def test_compiled_kernel_does_not_fall_back_off_tpu():
    """No backend guessing: without interpret=True the Mosaic kernel is
    what runs, and on a CPU that is an error, not a silent interpreter."""
    q, k, v = _qkv(s=16)
    with pytest.raises(ValueError, match="interpret mode"):
        pallas_attention.flash_attention(q, k, v)


def test_bad_gqa_ratio_raises():
    q, k, v = _qkv(hq=6, hkv=4)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_mesh_shard_map_path(causal, backward_blocks):
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh, virtual_cpu_devices

    mesh = build_mesh(MeshSpec(dp=2, tp=2), virtual_cpu_devices(4))
    q, k, v = _qkv(b=4, s=32, hq=4, hkv=2)
    ref = dot_product_attention(q, k, v, causal=causal)

    def loss_mesh(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16, mesh=mesh)
        return jnp.sum(out**2), out

    (val, out), grads = jax.value_and_grad(loss_mesh, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    gr = _grads(lambda q, k, v: dot_product_attention(q, k, v, causal=causal), q, k, v)
    for a, b in zip(grads, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("spec_kw", [{"dp": 2, "sp": 2}, {"sp": 4}])
def test_mesh_sp_sharding_rejected(spec_kw):
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh, virtual_cpu_devices

    mesh = build_mesh(MeshSpec(**spec_kw), virtual_cpu_devices(4))
    q, k, v = _qkv(s=32)
    with pytest.raises(ValueError, match="ring_attention"):
        flash_attention(q, k, v, mesh=mesh)


def test_jit_and_value_and_grad():
    q, k, v = _qkv(s=32)

    @jax.jit
    def step(q, k, v):
        def loss(q):
            return jnp.mean(flash_attention(q, k, v, True, None, 16, 16))

        return jax.value_and_grad(loss)(q)

    val, grad = step(q, k, v)
    assert np.isfinite(float(val))
    assert np.isfinite(np.asarray(grad)).all()


# --- the sliding window (PR 33) -------------------------------------------------
#
# Query t sees the keys t - W < j <= t.  The three kernels share one rule for
# which pairs run and which build a mask, and a windowed call walks a shorter
# grid, counted from its band's first block; each case below is checked against
# XLA attention with the same window, output and all three gradients.

WINDOW_CASES = {
    "a-multiple-of-the-tile": dict(s=96, window=32),
    "not-a-multiple": dict(s=96, window=20),
    "wider-than-two-tiles": dict(s=96, window=41),
    "one-key": dict(s=48, window=1),
    "ragged-50": dict(s=50, window=20),
    "ragged-50-window-of-a-tile": dict(s=50, window=16),
    "longer-window-than-sequence": dict(s=40, window=64),
    "group-of-6": dict(s=64, hq=6, hkv=1, window=24),
    "group-of-8": dict(s=64, hq=8, hkv=1, window=24),
    "group-of-6-two-kv-heads": dict(s=48, hq=12, hkv=2, window=17),
    "wide-q-block": dict(s=96, window=20, block_q=32, block_k=16),
    "wide-kv-block": dict(s=96, window=20, block_q=16, block_k=32),
    "tiles-of-128": dict(b=1, s=400, hq=2, hkv=1, window=150, block_q=128, block_k=128),
    "tiles-of-128-and-64": dict(b=1, s=300, hq=2, hkv=1, window=128, block_q=128, block_k=64),
    "jit": dict(s=96, window=20, jit=True),
}


@pytest.fixture
def window_blocks(monkeypatch):
    """The windowed backward kernels' tiles, module constants like the
    full-causal ones'."""

    def set_blocks(block_q: int, block_k: int):
        for name in ("WINDOW_BWD_DKV_BLOCKS", "WINDOW_BWD_DQ_BLOCKS"):
            monkeypatch.setattr(pallas_attention, name, (block_q, block_k))

    return set_blocks


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_windowed_kernels_match_xla_attention_with_the_same_window(case, window_blocks):
    kw = dict(b=2, s=48, hq=4, hkv=2, block_q=16, block_k=16, jit=False)
    kw.update(WINDOW_CASES[case])
    window_blocks(kw["block_q"], kw["block_k"])
    q, k, v = _qkv(b=kw["b"], s=kw["s"], hq=kw["hq"], hkv=kw["hkv"])
    flash = lambda q, k, v: flash_attention(
        q, k, v, True, None, kw["block_q"], kw["block_k"], window=kw["window"]
    )
    ref = lambda q, k, v: dot_product_attention(q, k, v, causal=True, window=kw["window"])
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5
    )
    for a, b in zip(_grads(flash, q, k, v, jit=kw["jit"]), _grads(ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_xla_attention_window_is_the_band_by_hand():
    """`t - W < j <= t`: with W = 3, query 5 sees keys 3, 4, 5 and no other."""
    q, k, v = _qkv(b=1, s=8, hq=1, hkv=1)
    out = dot_product_attention(q, k, v, causal=True, window=3)
    scores = np.einsum("d,jd->j", np.asarray(q[0, 5, 0]), np.asarray(k[0, 3:6, 0])) * 16**-0.5
    p = np.exp(scores - scores.max())
    want = (p / p.sum()) @ np.asarray(v[0, 3:6, 0])
    np.testing.assert_allclose(np.asarray(out[0, 5, 0]), want, rtol=1e-5, atol=1e-6)
    # query 1 has only keys 0 and 1 to see; a window is causal
    np.testing.assert_allclose(
        np.asarray(out[0, :2]), np.asarray(dot_product_attention(q, k, v)[0, :2]), rtol=1e-6
    )
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, causal=False, window=3)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=3)


def test_a_window_that_holds_every_key_is_bit_equal_to_no_window(
    backward_blocks, window_blocks, the_pair_everywhere
):
    """Another grid and another mask, the same numbers: with W = S no key is
    hidden, and the output and the three gradients equal the full-causal
    kernels' bit for bit (the pair's: a windowed call runs no other, and the
    fused form's dq is held to the pair's above)."""
    window_blocks(16, 16)
    q, k, v = _qkv(s=50, hq=6, hkv=1)
    full = lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16)
    windowed = lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16, window=50)
    np.testing.assert_array_equal(np.asarray(full(q, k, v)), np.asarray(windowed(q, k, v)))
    for a, b in zip(_grads(full, q, k, v), _grads(windowed, q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# The three full-causal kernels as the parent of PR 33 lowered them (commit
# da80100), by `_kernels_without_locations(None)` in that tree.  A PR that
# changes those kernels on purpose reads the new hashes the same way.
KERNELS_BEFORE_THE_WINDOW = {
    "_flash_forward": "4950a89ec7c3568dc58abfabea58218a2c1a39e6d62751dccdb82ae82b03e938",
    "_flash_backward_dkv": "a507b27a6719320d968be0f4436ac66e7ba2d728b5f430256fdabac029b5b7c2",
    "_flash_backward_dq": "ba1a0df13c479cedb5fd007e166001900b16d8586f25a08c6d4059ec37ce5478",
}


def test_without_a_window_the_kernels_are_the_ones_before_it(the_pair_everywhere):
    """`window=None` traces no operation more or fewer: the Mosaic modules are
    the parent's, operation for operation (their serialized form also carries
    source lines, which moved, so the cells' lowered text differs there and
    nowhere else: PERF.md, PR 33).  Since PR 44 the pair is what a call runs
    whose dq does not fit; the dk/dv kernel's body, which the fused form
    shares, traces to the same module without a dq."""
    assert _kernels_without_locations(None) == KERNELS_BEFORE_THE_WINDOW


# The fused backward kernel as PR 44 lowered it, beside the forward kernel it
# left alone, by `_kernels_without_locations(None)` in that tree.
KERNELS_WITH_THE_FUSED_BACKWARD = {
    "_flash_forward": KERNELS_BEFORE_THE_WINDOW["_flash_forward"],
    "_flash_backward_fused": "3b9d5c15de344466a08a816635758b3356e9650ccca8dfe97e05ff79037a2a85",
}


def test_a_full_causal_call_is_the_forward_kernel_and_the_fused_backward():
    assert _kernels_without_locations(None) == KERNELS_WITH_THE_FUSED_BACKWARD


def test_windowed_kernels_carry_names_the_full_causal_readers_do_not_match():
    """`attention_roofline_share` and `attention_backward_roofline_share` find
    their kernels by `^_flash_forward` / `^_flash_backward` and divide by a
    full-causal cost; a windowed call is another program under another name."""
    windowed = _kernels_without_locations(64)
    assert set(windowed) == {
        "_window_flash_forward", "_window_flash_backward_dkv", "_window_flash_backward_dq"
    }
    # at a window of 128 (S 256) the forward is the band step (PR 36), under a
    # name the window's readers find by the same prefix; the backward pair stays
    band = _kernels_without_locations(128)
    assert set(band) == {
        "_window_flash_forward_band", "_window_flash_backward_dkv", "_window_flash_backward_dq"
    }
    for kernels in (windowed, band):
        assert not any(n.startswith(("_flash_forward", "_flash_backward")) for n in kernels)
        assert not set(kernels.values()) & set(KERNELS_BEFORE_THE_WINDOW.values())


# The three windowed kernels at a window of 64 as the parent of PR 36 lowered
# them (commit 78316ca), by `_kernels_without_locations(64)` in that tree.
WINDOWED_KERNELS_BEFORE_THE_BAND_STEP = {
    "_window_flash_forward": "890baebf7c6291b91f3a9e00a22a948c7412cb5b704fa87ba4a8c19131ca5946",
    "_window_flash_backward_dkv": "5dd0575139bb1c57972bddc34693870b3d63df216135e9982a20f250c477e47f",
    "_window_flash_backward_dq": "6420b71f9905714d7834ea58097debbbd525caba459cfe244ee929408ff1c50c",
}


def test_outside_the_band_steps_shapes_a_windowed_call_is_the_one_before_it():
    """A window the band step does not take (64: not whole lane tiles) lowers
    to the parent's three Mosaic modules, operation for operation: the tiled
    forward's grid and body are unchanged, and the backward pair is untouched."""
    assert _kernels_without_locations(64) == WINDOWED_KERNELS_BEFORE_THE_BAND_STEP


# The band step and the backward pair beside it at a window of 128 as the
# parent of PR 39 lowered them (commit 6f99621), by
# `_kernels_without_locations(128)` in that tree: with the two tables above,
# every kernel of this module.
KERNELS_AT_THE_BAND_STEP = {
    "_window_flash_forward_band": "cf04d82adf930ed940531396153661f33b4d602c2d9c3bfd6bd30415fa1acbef",
    "_window_flash_backward_dkv": "c275b17873893cdd5cb902cd1927a83e38afeab195e3e1019eb1ecd6257490e9",
    "_window_flash_backward_dq": "b9aa81dd95dfc534a061ac9d459afbbcbd9c7c8f0359cec30cc20b3916c08d9e",
}


def test_naming_out_and_lse_changed_no_kernel():
    """PR 39 put `checkpoint_name` on the forward's results in `_core_fwd`,
    outside every kernel: the band step's call lowers to the parent's modules
    (the full-causal and the tiled windowed calls are held by the two tests
    above, which PR 39 left as they were)."""
    assert _kernels_without_locations(128) == KERNELS_AT_THE_BAND_STEP


@pytest.mark.parametrize("seq,block_q,block_k,window,steps", [
    (8192, 512, 512, 512, 2),    # the band of an aligned q block: its own kv block and the one before
    (8192, 1024, 1024, 512, 2),
    (8192, 1024, 512, 512, 3),   # 1,535 keys from an aligned start: three blocks of 512
    (8192, 512, 1024, 512, 2),
    (8192, 256, 256, 512, 3),
    (96, 16, 16, 20, 3),         # 35 keys from position 16 i - 19: three blocks of 16
    (48, 16, 16, 1, 1),
    (40, 16, 16, 64, 3),         # a window longer than the sequence: the causal triangle
])
def test_a_windowed_grid_has_as_many_kv_steps_as_the_widest_band(seq, block_q, block_k, window, steps):
    nq, nk = -(-seq // block_q), -(-seq // block_k)
    assert pallas_attention._kv_steps(nq, nk, block_q, block_k, window) == steps
    if seq > 128:
        return
    # and by the rule itself: the kv blocks that hold a key some query of the block sees
    most = max(
        len({key // block_k for t in range(i * block_q, min((i + 1) * block_q, seq))
             for key in range(max(0, t - window + 1), t + 1)})
        for i in range(nq)
    )
    assert most == steps


def test_ring_attention_refuses_a_window_by_name():
    from deeplearning_cfn_tpu.models.llama import attend
    from deeplearning_cfn_tpu.parallel.ring_attention import ring_attention

    q, k, v = _qkv(s=16)
    with pytest.raises(NotImplementedError, match="sliding window"):
        ring_attention(q, k, v, mesh=None, window=8)
    with pytest.raises(NotImplementedError, match="sliding window"):
        attend("ring", q, k, v, None, window=8)
    np.testing.assert_array_equal(
        np.asarray(attend("xla", q, k, v, None, window=8)),
        np.asarray(dot_product_attention(q, k, v, causal=True, window=8)),
    )


def test_windowed_tiles_at_the_cells_shape_are_the_sweeps():
    """S 8192 under a window of 512 (PERF.md, PR 33 and 36): the forward is the
    band step, one grid step a q block of 512 rows with its two kv blocks of
    512; both backward kernels run 512 x 512, not clamped.  A windowed call the
    band step does not take and that names no tiles runs the forward at
    `WINDOW_FWD_BLOCKS`, 512 x 1024."""
    from deeplearning_cfn_tpu.ops.pallas_attention import _clamp_block

    assert pallas_attention._takes_band_step(512, None, None, 8192, 8192)
    q = jax.ShapeDtypeStruct((2, 8192, 64, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
    traced = jax.make_jaxpr(
        lambda q, k, v: pallas_attention.flash_attention(q, k, v, window=512, interpret=True)
    )(q, kv, kv)
    (call,) = _named(traced.jaxpr, "pallas_call")
    assert call.params["name"] == "_window_flash_forward_band"
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (2, 8, 16, 8)  # batch, kv head, q block, query head of the group
    for operand in mapping.block_mappings[:5]:  # q, and k and v of the block before and the own
        assert [d.block_size for d in operand.block_shape[2:]] == [512, 128]

    assert pallas_attention.WINDOW_FWD_BLOCKS == (512, 1024)
    assert pallas_attention.WINDOW_BWD_DKV_BLOCKS == pallas_attention.WINDOW_BWD_DQ_BLOCKS == (512, 512)
    for blocks in (pallas_attention.WINDOW_FWD_BLOCKS, pallas_attention.WINDOW_BWD_DKV_BLOCKS):
        assert [_clamp_block(b, 8192) for b in blocks] == list(blocks)
    # at most two kv blocks a q block, and two q blocks a kv block, at those tiles
    assert pallas_attention._kv_steps(16, 8, 512, 1024, 512) == 2
    assert pallas_attention._q_steps(16, 16, 512, 512, 512) == 2


# --- the band step (PR 36) --------------------------------------------------------
#
# A windowed forward call whose window is whole lane tiles and shorter than the
# sequence, from a caller that names no tiles, takes a q block of `window` rows
# with its whole band in one grid step.  Each case: output, the three gradients
# (the unchanged backward kernels consume the band step's lse) and the lse itself
# against XLA.  W 128: the q blocks are 128 rows, so S 512 is four of them, S 400
# and 300 pad the last one (rows and keys), S 129 is one row past the window.

BAND_CASES = {
    "aligned-s512": dict(s=512),
    "ragged-s400": dict(s=400),
    "ragged-s300": dict(s=300),
    "one-row-past-the-window": dict(s=129),
    "group-of-8": dict(s=256, hq=8, hkv=1),
    "group-of-6-two-kv-heads": dict(s=256, hq=12, hkv=2),
    "group-of-1-two-kv-heads": dict(s=256, hq=2, hkv=2),
    "two-sequences": dict(b=2, s=256),
    "jit": dict(s=300, jit=True),
    # rows in chunks of `_BAND_ROW_CHUNK` (256): two a q block of 512, three of 768
    "window-512": dict(s=1100, window=512),
    "window-768": dict(s=1536, window=768, hq=1),
    "window-384-in-one-chunk": dict(s=800, window=384, hq=1),
}


def _band_case(case):
    kw = dict(b=1, s=256, hq=2, hkv=1, window=128, jit=False)
    kw.update(BAND_CASES[case])
    return kw, _qkv(b=kw["b"], s=kw["s"], hq=kw["hq"], hkv=kw["hkv"], seed=7)


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_step_matches_xla_attention_with_the_same_window(case):
    kw, (q, k, v) = _band_case(case)
    assert pallas_attention._takes_band_step(kw["window"], None, None, kw["s"], kw["s"])
    flash = lambda q, k, v: flash_attention(q, k, v, window=kw["window"])
    ref = lambda q, k, v: dot_product_attention(q, k, v, causal=True, window=kw["window"])
    run = jax.jit(flash) if kw["jit"] else flash
    np.testing.assert_allclose(
        np.asarray(run(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5
    )
    for a, b in zip(_grads(flash, q, k, v, jit=kw["jit"]), _grads(ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_step_lse_is_the_logsumexp_of_the_banded_scores(case):
    kw, (q, k, v) = _band_case(case)
    out, lse = pallas_attention._band_forward(q, k, v, q.shape[-1] ** -0.5, kw["window"], True)
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1]) and lse.dtype == jnp.float32
    want = _reference_lse(q, k, True, window=kw["window"])
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5, rtol=2e-5)
    # and the primal-only form writes no lse and the same output
    alone, none = pallas_attention._band_forward(
        q, k, v, q.shape[-1] ** -0.5, kw["window"], True, need_lse=False
    )
    assert none is None
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(out))


def test_band_steps_first_q_block_is_the_full_causal_kernels():
    """The first q block has no block before it: its step is the causal
    diagonal block, computed operation for operation as `_attn_kernel` computes
    a masked pair from fresh statistics (alpha is 0, so the accumulator is the
    product and the sum the row's), and rows 0..W-1 see the same keys with and
    without the window.  So output and lse equal the full-causal kernel's at
    tiles of W bit for bit."""
    q, k, v = _qkv(b=1, s=300, hq=4, hkv=2, seed=5)
    scale = q.shape[-1] ** -0.5
    band_out, band_lse = pallas_attention._band_forward(q, k, v, scale, 128, True)
    full_out, full_lse = pallas_attention._flash_forward(q, k, v, True, scale, 128, 128, True)
    np.testing.assert_array_equal(np.asarray(band_out[:, :128]), np.asarray(full_out[:, :128]))
    np.testing.assert_array_equal(np.asarray(band_lse[..., :128]), np.asarray(full_lse[..., :128]))
    # from row W on the window hides keys, and the two differ
    assert np.abs(np.asarray(band_out[:, 128:]) - np.asarray(full_out[:, 128:])).max() > 1e-3


# (batch, seq, q heads, kv heads, window, tiles named): which forward kernel a
# call lowers to.  The band step: the Laguna cell's window layers and
# tests/test_kernels_compile_for_tpu.py's ragged case.  The tiled kernel: a
# window that is not whole lane tiles (20, 300), one past 1024, one as long as
# the sequence or longer, and a caller that names a tile.
SELECTION_CASES = {
    "the-cells-shape": ((2, 8192, 64, 8, 512, {}), "_window_flash_forward_band"),
    "ragged-s2100-window-512": ((1, 2100, 64, 8, 512, {}), "_window_flash_forward_band"),
    "window-1024": ((1, 4096, 8, 8, 1024, {}), "_window_flash_forward_band"),
    "window-20": ((1, 2048, 4, 2, 20, {}), "_window_flash_forward"),
    "window-300-not-a-multiple": ((1, 4096, 12, 2, 300, {}), "_window_flash_forward"),
    "window-2048": ((1, 8192, 4, 2, 2048, {}), "_window_flash_forward"),
    "window-as-long-as-the-sequence": ((1, 512, 4, 2, 512, {}), "_window_flash_forward"),
    "window-longer-than-the-sequence": ((1, 256, 4, 2, 512, {}), "_window_flash_forward"),
    "names-block-q": ((2, 8192, 64, 8, 512, dict(block_q=512)), "_window_flash_forward"),
    "names-block-k": ((2, 8192, 64, 8, 512, dict(block_k=1024)), "_window_flash_forward"),
    "no-window": ((2, 8192, 48, 8, None, {}), "_flash_forward"),
}


@pytest.mark.parametrize("case", SELECTION_CASES)
def test_the_shapes_choose_the_forward_kernel(case):
    """Lowered for the TPU, forward and backward (no chip needed to lower): the
    forward kernel's name in the text, beside the kind's backward kernels (the
    fused one without a window, the pair under one).
    No argument chooses the band step; the shapes do."""
    import re

    (B, S, Hq, Hkv, window, tiles), forward = SELECTION_CASES[case]

    def loss(q, k, v):
        out = pallas_attention.flash_attention(q, k, v, window=window, **tiles)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((B, S, Hq, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)
    ).as_text(debug_info=True)
    backward = (
        {"_flash_backward_fused"} if window is None
        else {"_window_flash_backward_dkv", "_window_flash_backward_dq"}
    )
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == {forward} | backward
