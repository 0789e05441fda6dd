"""`models/mamba_attn.py` at toy widths on the CPU against the benchmark's plain
reference (`benchmarks/reference/mamba_attn.py`): layer by layer, then the loss
and every leaf's gradient end to end; the layer order at 28 and at 14 layers;
the tied table's gradient as the sum of its two uses; the parameter counts of
ISSUE 47; the counters.  One AdamW step through `Trainer.fit` is
tests/benchmark_tests/test_benchmark_mamba_attn.py."""

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.manifest import Manifest
from deeplearning_cfn_tpu.models import decoder_stack, mamba_attn
from deeplearning_cfn_tpu.models.mamba_attn import MambaAttnConfig

REPO = Path(__file__).resolve().parents[1]
MANIFEST = Manifest()
REFERENCE = MANIFEST.module("reference", "mamba_attn")
BUILDER = MANIFEST.module("builders", "mamba_attn")
TOY = dict(
    json.loads((REPO / "tests/benchmark_tests/configs/mamba-attn-toy.json").read_text()),
    torch_dtype="float32",
)


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def seeded():
    cfg = replace(BUILDER.model_config(TOY), remat=False)
    flat = REFERENCE.init_params(jax.random.key(3), TOY)
    x = jax.random.randint(jax.random.key(4), (2, 40), 0, TOY["vocab_size"])
    return cfg, flat, BUILDER.program_tree(flat, TOY, REFERENCE), x, jnp.roll(x, -1, axis=1)


def test_every_layer_is_the_references_layer(seeded):
    cfg, flat, tree, _, _ = seeded
    assert cfg.kinds == ("mamba", "mamba", "attention", "mamba", "mamba")
    assert cfg.runs == (("mamba", 2), ("attention", 1), ("mamba", 2))
    x = jax.random.normal(jax.random.key(5), (2, 40, cfg.dim))
    places = BUILDER._places(TOY)
    for (prefix, leaves), (r, i), kind in zip(REFERENCE.layers(TOY), places, cfg.kinds, strict=True):
        lp = jax.tree_util.tree_map(lambda a: a[i], tree["runs"][r])
        assert set(lp) == set(leaves)
        got, stats = mamba_attn.layer(cfg, None, kind, x, lp)
        want = REFERENCE.layer({n: flat[prefix + n] for n in leaves}, x, TOY)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5, err_msg=prefix)
        assert (stats is None) == (kind == "attention")


def test_loss_and_every_gradient_end_to_end_and_the_tied_tables_two_uses(seeded):
    cfg, flat, tree, x, y = seeded
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(lambda p: mamba_attn.lm_loss(cfg, p, x, y), has_aux=True)
    )(tree)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: REFERENCE.loss(p, x, y, TOY)))(flat)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = BUILDER.reference_leaves(grads, TOY, REFERENCE)
    # the table and the final norm; 17 leaves a Mamba layer, 9 the attention layer
    assert set(got) == set(REFERENCE.all_leaves(TOY)) and len(got) == 2 + 4 * 17 + 9
    for name in got:
        scale = float(jnp.max(jnp.abs(want[name])))
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 5e-5 * scale + 1e-9, name
    np.testing.assert_allclose(
        np.asarray(mamba_attn.logits(cfg, tree, x)["main"]),
        np.asarray(REFERENCE.forward(flat, x, y, TOY)["main"]), atol=5e-5, rtol=5e-5,
    )

    # the table's gradient is the lookup's scatter plus the head's matmul, in one leaf
    def two_tables(lookup, head):
        h, _ = mamba_attn.hidden_states(cfg, dict(tree, embed=lookup), x)
        return decoder_stack.head_loss(cfg, tree["final_norm"], head.T, h, y, ahead=1)

    by_lookup, by_head = jax.grad(two_tables, argnums=(0, 1))(tree["embed"], tree["embed"])
    assert float(jnp.max(jnp.abs(by_lookup))) > 0 and float(jnp.max(jnp.abs(by_head))) > 0
    np.testing.assert_allclose(
        np.asarray(grads["embed"]), np.asarray(by_lookup + by_head), atol=1e-7, rtol=1e-5)
    # the counters: dt over tokens, channels and the four Mamba layers
    assert set(metrics["counters"]) == {"ssm.dt_mean", "ssm.dt_max"}
    assert 1e-3 < float(metrics["counters"]["ssm.dt_mean"]) < float(metrics["counters"]["ssm.dt_max"])


def test_the_layer_order_at_28_and_at_14_layers_and_the_counts_of_issue_47():
    whole, held = MambaAttnConfig(n_layers=28), MambaAttnConfig()
    assert [i for i, k in enumerate(whole.kinds) if k == "attention"] == [7, 21]
    assert held.kinds == whole.kinds[:14] and held.kinds.count("mamba") == 13
    assert held.runs == (("mamba", 7), ("attention", 1), ("mamba", 6))
    assert whole.runs == (("mamba", 7), ("attention", 1), ("mamba", 13), ("attention", 1), ("mamba", 6))
    shapes = jax.eval_shape(lambda k: mamba_attn.init_params(held, k), jax.random.key(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(t))
    first, attention, last = shapes["runs"]
    assert count(first) == 7 * 104_161_472 and count(last) == 6 * 104_161_472
    assert count(attention) == 76_682_240 and count(shapes["runs"]) == 1_430_781_376
    assert mamba_attn.param_count(held) == 1_430_781_376 + 65_536 * 2560 + 2560
    # float32 leaves: the decays, the skip, the biases and every norm's scale
    f32 = {n for n, a in first.items() if a.dtype == jnp.float32}
    assert f32 == {"A_log", "D", "dt_bias", "conv_w", "conv_bias", "mixer_norm", "mlp_norm", "dt_norm", "b_norm",
                   "c_norm"}
    # the trainer's mask: by name and rank, the stacked A_log and D are decayed as matrices
    from deeplearning_cfn_tpu.train.trainer import decay_mask

    mask = decay_mask(first)
    assert mask["A_log"] and mask["D"] and mask["in_proj"] and mask["conv_w"]
    assert not (mask["dt_bias"] or mask["conv_bias"] or mask["dt_norm"] or mask["mixer_norm"])
    with pytest.raises(ValueError):
        MambaAttnConfig(attn_layer_offset=14)
