"""Both plain references against `models/` at tiny sizes in float32, and the
decoder reference's layer-by-layer steps against whole-model autodiff with
optax."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.traverse_util import unflatten_dict

from benchmarks.manifest import Manifest

HERE = Path(__file__).resolve().parent
MANIFEST = Manifest()
RESNET = MANIFEST.module("reference", "resnet")
DECODER = MANIFEST.module("reference", "decoder")
RESNET_TOY = json.loads((HERE / "configs/resnet-toy.json").read_text())
DECODER_TOY = dict(json.loads((HERE / "configs/decoder-toy.json").read_text()), torch_dtype="float32")


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


@pytest.fixture(scope="module")
def resnet_case():
    from deeplearning_cfn_tpu.models.resnet import ResNet
    from deeplearning_cfn_tpu.train.trainer import softmax_xent

    cfg = RESNET_TOY
    flat = RESNET.init_params(jax.random.key(3), cfg)
    model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]), num_classes=10, num_filters=8,
                   dtype=jnp.float32)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (4, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (4,), dtype=np.int32)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False)

    def model_loss(flat_params):
        x = (images.astype(np.float32) / 255.0 - np.asarray(cfg["input_mean"], np.float32)) / np.asarray(
            cfg["input_std"], np.float32)
        logits, _ = model.apply(
            {"params": unflatten_dict(flat_params, sep="/"), "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return softmax_xent(logits, labels, cfg["label_smoothing"]), logits

    return cfg, flat, images, labels, model_loss


def test_resnet_reference_names_every_leaf_of_the_model(resnet_case):
    from deeplearning_cfn_tpu.models.resnet import ResNet
    from flax.traverse_util import flatten_dict

    cfg, flat, *_ = resnet_case
    model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]), num_classes=10, num_filters=8)
    theirs = flatten_dict(
        jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False))["params"],
        sep="/")
    assert {k: v.shape for k, v in theirs.items()} == {k: v.shape for k, v in flat.items()}


def test_resnet_reference_logits_and_loss_match_the_model(resnet_case):
    cfg, flat, images, labels, model_loss = resnet_case
    with jax.default_matmul_precision("highest"):
        want_loss, want_logits = model_loss(flat)
        got_logits = RESNET.logits(flat, images, cfg)
        got_loss = RESNET.loss(flat, images, labels, cfg)
    assert close(got_logits, want_logits, 1e-4)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)


def test_resnet_reference_gradients_match_the_model(resnet_case):
    cfg, flat, images, labels, model_loss = resnet_case
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: model_loss(p)[0])(flat)
        got = jax.grad(RESNET.loss)(flat, images, labels, cfg)
    for name in flat:
        assert close(got[name], want[name], 2e-3), name


def test_resnet_follow_is_nesterov_sgd_on_the_reference_loss():
    cfg = RESNET_TOY
    rng = np.random.default_rng(1)
    batches = [
        (rng.integers(0, 256, (4, 32, 32, 3), dtype=np.uint8), rng.integers(0, 10, (4,), dtype=np.int32))
        for _ in range(3)
    ]
    key = jax.random.key(5)
    got = RESNET.follow(key, cfg, batches, 3)
    tx = optax.sgd(cfg["learning_rate"], momentum=cfg["momentum"], nesterov=True)
    with jax.default_matmul_precision("highest"):
        params = start = RESNET.init_params(key, cfg)
        opt = tx.init(params)
        losses = []
        for i, (x, y) in enumerate(batches):
            value, grads = jax.value_and_grad(RESNET.loss)(params, x, y, cfg)
            if i == 0:
                first = {k: float(jnp.linalg.norm(v)) for k, v in grads.items()}
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
            losses.append(float(value))
    assert got["loss"] == pytest.approx(losses, rel=1e-4)
    for k in first:
        assert got["grad_norm"][k] == pytest.approx(first[k], rel=1e-3, abs=1e-6)
        moved = float(jnp.linalg.norm(params[k] - start[k]))
        assert got["update_norm"][k] == pytest.approx(moved, rel=2e-3, abs=1e-6)


def decoder_program_params(flat, layers):
    return {
        "embed": flat["embed"], "output": flat["output"], "final_norm": flat["final_norm"],
        "layers": {n: jnp.stack([flat[f"layers/{i}/{n}"] for i in range(layers)])
                   for n in DECODER.LAYER_LEAVES},
    }


@pytest.fixture(scope="module")
def decoder_case():
    from deeplearning_cfn_tpu.models import llama

    cfg = DECODER_TOY
    flat = DECODER.init_params(jax.random.key(2), cfg)
    lcfg = llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"], rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.float32, remat=False, use_flash_attention=False)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 32), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)

    def model_loss(flat_params):
        return llama.causal_lm_loss(
            lcfg, decoder_program_params(flat_params, cfg["num_hidden_layers"]), tokens, targets)[0]

    return cfg, flat, tokens, targets, model_loss


def test_decoder_reference_loss_matches_the_model(decoder_case):
    cfg, flat, tokens, targets, model_loss = decoder_case
    with jax.default_matmul_precision("highest"):
        assert float(DECODER.loss(flat, tokens, targets, cfg)) == pytest.approx(
            float(model_loss(flat)), rel=1e-5)


def test_decoder_reference_gradients_match_the_model(decoder_case):
    cfg, flat, tokens, targets, model_loss = decoder_case
    with jax.default_matmul_precision("highest"):
        want = jax.grad(model_loss)(flat)
        got = jax.grad(DECODER.loss)(flat, tokens, targets, cfg)
    for name in flat:
        assert close(got[name], want[name], 2e-3), name


@pytest.mark.parametrize("steps", [1, 2])
def test_decoder_follow_layer_by_layer_is_clipped_adamw_on_the_reference_loss(steps):
    cfg = DECODER_TOY
    rng = np.random.default_rng(4)
    batches = []
    for _ in range(2):
        x = rng.integers(0, cfg["vocab_size"], (2, 32), dtype=np.int32)
        batches.append((x, np.roll(x, -1, axis=1)))
    key = jax.random.key(9)
    got = DECODER.follow(key, cfg, batches, steps)
    decay = lambda params: {k: not k.endswith("norm") for k in params}  # noqa: E731
    tx = optax.chain(
        optax.clip_by_global_norm(cfg["grad_clip_norm"]),
        optax.adamw(cfg["learning_rate"], b1=cfg["adam_b1"], b2=cfg["adam_b2"], eps=cfg["adam_eps"],
                    weight_decay=cfg["weight_decay"], mask=decay),
    )
    with jax.default_matmul_precision("highest"):
        params = start = DECODER.init_params(key, cfg)
        opt = tx.init(params)
        losses = []
        for i in range(steps):
            value, grads = jax.value_and_grad(DECODER.loss)(params, *batches[i], cfg)
            if i == 0:
                clipped, _ = optax.clip_by_global_norm(cfg["grad_clip_norm"]).update(grads, optax.EmptyState())
                first = {k: float(jnp.linalg.norm(v)) for k, v in clipped.items()}
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
            losses.append(float(value))
    assert got["loss"] == pytest.approx(losses, rel=1e-5)
    assert set(got["grad_norm"]) == set(first)
    for k in first:
        assert got["grad_norm"][k] == pytest.approx(first[k], rel=1e-3)
        moved = float(jnp.linalg.norm(params[k] - start[k]))
        assert got["update_norm"][k] == pytest.approx(moved, rel=1e-3)


def test_decoder_follow_refuses_a_third_step():
    with pytest.raises(ValueError, match="1 or 2 steps"):
        DECODER.follow(jax.random.key(0), DECODER_TOY, [], 3)


@pytest.mark.parametrize("reference", [RESNET, DECODER], ids=["resnet", "decoder"])
def test_fp8_rounds_operands_to_three_bits_of_mantissa(reference):
    x = jnp.asarray([1.0, 1.06, 0.3, -448.0, 1e-3])
    got = np.asarray(reference.ROUNDINGS["fp8"].operand(x))
    assert got[0] == 1.0 and got[3] == -448.0
    assert got[1] in (1.0, 1.125) and abs(got[2] - 0.3) <= 0.3 * 2**-4
    assert reference.ROUNDINGS["float32"].operand(x) is x
    assert reference.ROUNDINGS["float32"].result(x) is x


def test_fp8_loses_no_gradient_to_the_derivative_of_a_cast():
    """Both roundings are straight-through: a small cotangent comes back
    whole through the operand, and within e5m2's two bits through the
    result.  (Differentiating the cast itself would round 0.3 / (448 / amax)
    to e4m3 unscaled, which is zero.)"""
    from benchmarks.precision import fp8_operand, fp8_result

    x = jnp.asarray([1.0, 1.06, 0.3, -448.0, 1e-3])
    g = np.asarray(jax.grad(lambda v: jnp.sum(fp8_operand(v)) * 0.3)(x))
    assert np.all(g == np.float32(0.3))
    weights = jnp.asarray([1.0, 0.3, 1e-3, 3e-6, -0.11])
    g = np.asarray(jax.grad(lambda v: jnp.sum(fp8_result(v) * weights))(x))
    assert g[0] == 1.0 and abs(g[1] - 0.3) <= 0.3 * 2**-3 and abs(g[4] + 0.11) <= 0.11 * 2**-3
    assert abs(g[2] - 1e-3) <= 1e-3 * 2**-3 and g[3] != 0.0


def test_sketch_feels_noise_in_full_where_the_norm_hardly_does():
    """sum((g + e) * r) - sum(g * r) has the variance |e|^2; |g + e| - |g| is
    about |e|^2 / 2|g|.  And the signs are a fixed function of key, name and index."""
    from benchmarks.sketch import DRAWS, signs, sketch

    key = jax.random.key(5)
    g = jax.random.normal(jax.random.key(1), (64, 33))
    e = 0.3 * jax.random.normal(jax.random.key(2), (64, 33))
    drawn = np.asarray(sketch(g + e, "w", key) - sketch(g, "w", key))
    assert drawn.shape == (DRAWS,) and len(set(drawn.tolist())) == DRAWS
    by_sketch = float(np.sqrt(np.mean(drawn**2))) / float(jnp.linalg.norm(g))
    by_norm = abs(float(jnp.linalg.norm(g + e) - jnp.linalg.norm(g))) / float(jnp.linalg.norm(g))
    assert by_norm < 0.06 and 0.15 < by_sketch < 0.6  # |e| / |g| is 0.3
    r = np.asarray(signs((64, 33), jnp.uint32(7)))
    assert set(np.unique(r)) == {-1.0, 1.0} and abs(r.mean()) < 0.1
    assert np.array_equal(r.reshape(-1), np.asarray(signs((64 * 33,), jnp.uint32(7))))
    same = np.asarray(sketch(g, "w", key))
    assert np.array_equal(same, np.asarray(sketch(g, "w", key)))
    assert not np.array_equal(same, np.asarray(sketch(g, "v", key)))
    assert not np.array_equal(same, np.asarray(sketch(g, "w", jax.random.key(6))))
    assert set(np.abs(np.asarray(sketch(jnp.float32(2.0), "s", key))).tolist()) == {2.0}
