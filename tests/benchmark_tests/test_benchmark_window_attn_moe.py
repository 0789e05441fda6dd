"""The `window_attn_moe` kind (PR 33): the plain reference against
`models/window_attn_moe.py` (logits, loss, every leaf's gradient, one AdamW
step) over the pattern [full + dense, sliding x 3, full] under a share that is
not the first, at a length that is not a multiple of the window and at one
shorter than it; each attention kind alone; the reference's band, YaRN and gate
by hand; the shares of a layer against the uncut reference's whole layer with
the shared expert counted once; `flops/window_attn_moe.py` and
`flops/window_attention.py` against ISSUE 33's arithmetic; each new reader on
made-up rows; the configuration file against the published `config.json`; the
manifest's entries by name.  A whole run of the kind is
test_benchmark_window_attn_moe_run.py."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.manifest import Manifest

REPO = Path(__file__).resolve().parents[2]
MANIFEST = Manifest()
REFERENCE = MANIFEST.module("reference", "window_attn_moe")
BUILDER = MANIFEST.module("builders", "window_attn_moe")
FLOPS = MANIFEST.module("flops", "window_attn_moe")
WINDOW = MANIFEST.module("flops", "window_attention")
CELL = "laguna-xs.2.train-s8192"
GLM_CELL = "glm-4.7-flash.train-s8192"
LFM2_CELL = "lfm2-8b-a1b.train-s8192"
MISTRAL_CELL = "mistral-7b-v0.3.train-s4096"
CONFIG = MANIFEST.config("laguna-xs.2")
TRAFFIC = MANIFEST.json("traffic", "train-s8192")
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
P0 = "/device:TPU:0"
TOY = json.loads((REPO / "tests/benchmark_tests/configs/window-attn-moe-toy.json").read_text())
# The same structure in float32, where program and reference agree closely.
TOY32 = dict(TOY, torch_dtype="float32")
TOY_TRAFFIC = {"kind": "train", "input": "tokens", "seq_len": 16, "global_batch": 8,
               "pool_batches": 2, "log_every": 2, "warm_seconds": 0, "check_steps": 1,
               "trace_seconds": 1}


def toy_batch(seed=0, s=16):
    # eight sequences: the tests' mesh has eight devices and the builder uses them all
    x = np.random.default_rng(seed).integers(0, TOY["vocab_size"], (8, s), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


@pytest.fixture(scope="module", autouse=True)
def leave_no_counters():
    """`fit` folds the `moe.*` counters into the process's aggregates, and a
    later run in this worker reads them."""
    yield
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()


@pytest.fixture(scope="module")
def built():
    x, _ = toy_batch()
    return BUILDER.build(TOY32, TOY_TRAFFIC, jax.random.key(3), x, REFERENCE)


# 16 is neither a multiple of the window of 6 nor shorter than it; 4 is shorter.
@pytest.mark.parametrize("seq", [16, 4], ids=["s16-not-a-multiple-of-6", "s4-shorter-than-the-window"])
def test_reference_agrees_with_the_model_on_logits_loss_and_every_gradient(built, seq):
    """float32 on both sides, so what is left is the order of sums: logits to
    5e-5 of values of a few units, the loss to 1e-6, each leaf's gradient to
    2e-5 of its largest element."""
    from deeplearning_cfn_tpu.models import window_attn_moe

    key = jax.random.key(3)
    cfg = BUILDER.model_config(TOY32)
    assert cfg.held_experts == (4, 4)  # rank 1 of two chips: not the first span
    assert cfg.kinds == (("full_attention", 6, False),) + (("sliding_attention", 8, True),) * 3 + (
        ("full_attention", 6, True),)
    assert cfg.sliding_window == 6 and cfg.full_rotary.yarn_factor == 4.0
    x, y = (jnp.asarray(a) for a in toy_batch(s=seq))
    params = built.state.params
    with jax.default_matmul_precision("highest"):  # jitted: eager, each takes ten times as long
        seeded = jax.jit(lambda k: REFERENCE.init_params(k, TOY32))(key)
        ours = jax.jit(lambda p: window_attn_moe.logits(cfg, p, x))(params)
        theirs = jax.jit(lambda p: REFERENCE.forward(p, x, y, TOY32))(seeded)
        np.testing.assert_allclose(
            np.asarray(ours["main"]), np.asarray(theirs["main"]), atol=5e-5, rtol=5e-5
        )
        # every routed block selects the same experts
        assert ours["selected"].shape == theirs["selected"].shape == (4, 8 * seq, 2)
        np.testing.assert_array_equal(
            np.sort(np.asarray(ours["selected"]), -1), np.sort(np.asarray(theirs["selected"]), -1)
        )
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p: window_attn_moe.lm_loss(cfg, p, x, y)[0])
        )(params)
        assert float(loss) == pytest.approx(float(theirs["loss"]), rel=1e-6)
        got = built.to_reference(grads)
        want = jax.jit(jax.grad(lambda p: REFERENCE.loss(p, x, y, TOY32)))(seeded)
    # the table, the head and the final norm; 2 norms and 5 attention leaves a layer,
    # 3 of the dense feed-forward, 7 of a routed one
    assert set(got) == set(REFERENCE.all_leaves(TOY32)) and len(got) == 3 + 5 * 7 + 3 + 4 * 7
    for name in got:  # the selection bias is the one leaf left out: a buffer
        scale = float(jnp.max(jnp.abs(want[name])))
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 2e-5 * scale + 1e-9, name
    assert set(want) - set(got) == {f"layers/{i}/moe/router_bias" for i in (1, 2, 3, 4)}


@pytest.mark.parametrize("mixer,heads", [("full_attention", 6), ("sliding_attention", 8)])
def test_each_attention_kind_is_the_references(mixer, heads):
    """One attention alone on a normalised input, at 20 positions (more than
    three windows): the program's (XLA attention with the window, the rotary
    rule of the kind, the gate) against the reference's head by head under a
    plain mask."""
    from deeplearning_cfn_tpu.models import window_attn_moe

    cfg = BUILDER.model_config(TOY32)
    key = jax.random.key(7)
    prefix = "layers/0/" if mixer == "full_attention" else "layers/1/"
    lp = {n: REFERENCE.init_leaf(key, prefix + n, TOY32) for n in REFERENCE.ATTENTION_LEAVES}
    lp["wo"] = lp["wo"] / 0.03  # the seeded projection is drawn small; here its size is no matter
    assert lp["wq"].shape == (32, heads * 8) and lp["wg"].shape == (32, heads)
    h = jax.random.normal(jax.random.key(8), (2, 20, TOY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = window_attn_moe._attention_mixer(cfg, None, (mixer, heads, True), lp, h, jnp.arange(20))
        want = jax.vmap(
            lambda n: REFERENCE.attention_mixer(lp, n, TOY32, mixer, REFERENCE.Rounding())
        )(h)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * scale)


def test_the_references_band_rotary_and_gate_by_hand():
    """`t - W < j <= t` as a plain mask; YaRN over dim 4 by the formula written
    out; a sliding layer's output at t blind to token t - W and not to t - W + 1."""
    seen = np.asarray(REFERENCE.visible(7, 3))
    assert seen[5].tolist() == [False, False, False, True, True, True, False]
    assert seen[1].tolist() == [True, True] + [False] * 5
    np.testing.assert_array_equal(np.asarray(REFERENCE.visible(7, None)), np.tril(np.ones((7, 7), bool)))
    # the toy's full layers: dim 4 of a head of 8, theta 5e5, factor 4, original 8, beta 4 / 1
    rope = TOY["rope_parameters"]["full_attention"]
    c = lambda n: 4 * math.log(8 / (n * 2 * math.pi)) / (2 * math.log(5e5))
    low, high = max(math.floor(c(4)), 0), min(math.ceil(c(1)), 3)
    assert (low, high) == (0, 1)  # c(4) = -0.17, c(1) = 0.04
    f = [1.0, 5e5 ** -0.5]
    want = [f[0], f[1] / 4]  # r = (0, 1): the first keeps its frequency, the second is divided
    np.testing.assert_allclose(REFERENCE.yarn_inv_freq(rope, 4), want, rtol=1e-6)
    cos, sin = REFERENCE.rotary_tables(rope, 8, 5)
    assert cos.shape == (5, 2)
    np.testing.assert_allclose(np.asarray(cos[3]), [1.1386294361119891 * math.cos(3 * w) for w in want], rtol=1e-5)
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 8) * jnp.ones((5, 1, 1))
    turned = np.asarray(REFERENCE.rotate(x, cos, sin))[3, 0]
    np.testing.assert_allclose(turned[4:], [5, 6, 7, 8])  # the other half passes through
    np.testing.assert_allclose(turned[0], 1 * float(cos[3, 0]) - 3 * float(sin[3, 0]), rtol=1e-5)
    np.testing.assert_allclose(turned[3], 4 * float(cos[3, 1]) + 2 * float(sin[3, 1]), rtol=1e-5)
    # sliding layers: plain rotary over the whole head
    cos, _ = REFERENCE.rotary_tables(TOY["rope_parameters"]["sliding_attention"], 8, 5)
    np.testing.assert_allclose(np.asarray(cos[2]), [math.cos(2 * 1e4 ** (-i / 4)) for i in range(4)], rtol=1e-5)
    # the window's edge on a sliding layer of the reference: W = 6, t = 15
    key = jax.random.key(1)
    lp = {n: REFERENCE.init_leaf(key, "layers/1/" + n, TOY32).astype(jnp.float32)
          for n in REFERENCE.ATTENTION_LEAVES}
    n = jax.random.normal(jax.random.key(2), (16, 32), jnp.float32)
    mix = lambda n: np.asarray(REFERENCE.attention_mixer(lp, n, TOY32, "sliding_attention", REFERENCE.Rounding()))
    base = mix(n)
    np.testing.assert_array_equal(mix(n.at[:10].add(1.0))[15], base[15])  # tokens <= t - 6
    assert np.abs(mix(n.at[10].add(1.0))[15] - base[15]).max() > 1e-7  # token t - 5
    # a gate of one half everywhere where W_g is zero
    ungated = dict(TOY32, gating=False)
    np.testing.assert_allclose(
        np.asarray(REFERENCE.attention_mixer({**lp, "wg": 0 * lp["wg"]}, n, TOY32, "sliding_attention", REFERENCE.Rounding())),
        0.5 * np.asarray(REFERENCE.attention_mixer(lp, n, ungated, "sliding_attention", REFERENCE.Rounding())),
        rtol=1e-5, atol=1e-8,
    )


def test_one_adamw_step_of_the_trainer_is_the_references(built):
    """Through `Trainer.fit` and the probe, as a run's check reads it."""
    from benchmarks import check
    from benchmarks.probe import StateProbe
    from deeplearning_cfn_tpu.train.data import Batch

    key = jax.random.key(3)
    x, y = toy_batch()
    with jax.default_matmul_precision("highest"):
        probe = StateProbe(built, key, 1)
        state, losses = built.trainer.fit(
            built.fresh_state(key), iter([Batch(x, y)]), steps=1, checkpointer=probe
        )
        followed = REFERENCE.follow(key, TOY32, [(x, y)], 1)
    rows = check.compare({"loss": losses, **probe.readings()}, followed, dict.fromkeys(
        ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"), 1e-3
    ))
    assert all(r["ok"] for r in rows), rows
    assert followed["routing"] == {"assignments": 4 * 128 * 2, "differing": 0, "share": 0.0}
    assert followed["head_leaves"] == ["output", "final_norm"]  # the head is untied
    # The buffer stayed where it was seeded.
    bias = state.params["runs"][1]["moe"]["router_bias"][2]  # the third sliding layer
    np.testing.assert_allclose(
        np.asarray(bias), np.asarray(REFERENCE.init_leaf(key, "layers/3/moe/router_bias", TOY32)),
        rtol=1e-6,
    )


def test_the_four_shares_routed_parts_and_one_shared_expert_are_the_uncut_layer():
    """Four chips hold two experts each of the toy's eight (the deployment's
    64 each of 256 in small): what each computes for its own experts, with the
    shared expert, which every chip computes alike, counted once, adds up to
    the plain reference's layer with all eight experts held."""
    from deeplearning_cfn_tpu.ops.moe import routed_experts

    uncut = dict(TOY32, num_experts=8, deployment={"rank": 0})
    key = jax.random.key(5)
    lp = {n: REFERENCE.init_leaf(key, "layers/1/" + n, uncut).astype(jnp.float32)
          for n in REFERENCE.ROUTED_LEAVES + REFERENCE.BUFFERS}
    n = jax.random.normal(jax.random.key(6), (48, TOY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = REFERENCE.routed_ffn(lp, n, uncut, REFERENCE.Rounding())
        shared = REFERENCE._swiglu(
            REFERENCE._mm(REFERENCE.Rounding()), n, lp["moe/shared_gate"], lp["moe/shared_up"],
            lp["moe/shared_down"],
        )
        parts = 0
        for rank in range(4):
            cfg = BUILDER.model_config(dict(TOY32, num_experts=2, deployment={"rank": rank})).routed
            assert cfg.span == (2 * rank, 2) and cfg.shared_dim == 16 and cfg.scale == 2.5
            share = {k[4:]: v for k, v in lp.items()}
            for name in ("w_gate", "w_up", "w_down"):
                share[name] = share[name][2 * rank : 2 * rank + 2]
            y, stats = routed_experts(cfg, share, n[None], kind="xla")
            assert int(stats["dropped"]) == 0
            parts = parts + (y[0] - shared)  # every chip adds the shared expert: count it once
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole), atol=5e-5)
    assert float(jnp.max(jnp.abs(shared))) > 1e-2


# --- the counts -----------------------------------------------------------------


def test_weights_a_token_passes_through_by_hand():
    # q and o 2048 x 8192, k and v 2048 x 1024, the gate 2048 x 64
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert FLOPS.attention_weights(CONFIG, 64) == sliding == 37_879_808
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    assert FLOPS.attention_weights(CONFIG, 48) == full == 29_458_432
    expert = 3 * 2048 * 512
    assert expert == 3_145_728 and FLOPS.routed_tokens_share(CONFIG) == 8 * 64 / 256 == 2.0
    # the router 2048 x 256, two held experts in expectation and the shared one
    assert FLOPS.feed_forward_weights(CONFIG, "sparse") == 2048 * 256 + 3 * expert == 9_961_472
    assert FLOPS.feed_forward_weights(CONFIG, "dense") == 3 * 2048 * 8192 == 50_331_648
    head = 2048 * 25088
    assert FLOPS.matmul_weights(CONFIG) == (
        3 * sliding + 2 * full + 50_331_648 + 4 * 9_961_472 + head
    ) == 314_114_048


def test_flops_an_example_count_the_band_for_window_layers_and_are_the_programs():
    """A window layer's head computes S W - W (W - 1) / 2 = 4,063,488 scores at
    S 8192, a full layer's 33,554,432: 21.59 TFLOP an example, of which the three
    window layers' attention is 1.2 and the two full layers' 4.9."""
    assert WINDOW.scores(8192, 512) == 8192 * 512 - 512 * 511 / 2 == 4_063_488
    assert WINDOW.scores(300, 512) == 300 * 301 / 2  # a window longer than the sequence
    assert FLOPS.attended_scores(CONFIG, 8192) == 3 * 64 * 4_063_488 + 2 * 48 * 33_554_432
    example = FLOPS.per_example(CONFIG, TRAFFIC)
    scores = 3 * 2 * 2 * 128 * (3 * 64 * 4_063_488 + 2 * 48 * 33_554_432)
    assert example == 6.0 * 314_114_048 * 8192 + scores
    assert example == pytest.approx(21.59e12, rel=1e-3)
    assert 3 * 2 * 2 * 128 * 3 * 64 * 4_063_488 == pytest.approx(1.198e12, rel=1e-3)
    # crediting the window layers the triangle would read 8.7 TFLOP more, two fifths of the example
    assert 3 * 2 * 2 * 128 * 3 * 64 * (33_554_432 - 4_063_488) == pytest.approx(8.70e12, rel=1e-3)
    from deeplearning_cfn_tpu.models import window_attn_moe

    model = BUILDER.model_config(CONFIG)
    assert window_attn_moe.train_flops_per_token(model, 8192) * 8192 == pytest.approx(example, rel=1e-12)
    assert window_attn_moe.param_count(model) == 1_145_658_368
    assert CONFIG["num_hidden_layers"] == 5 == len(CONFIG["layer_types"])


def test_one_windowed_calls_flops_and_bytes_by_hand():
    # forward: QK^T and PV, 2 x 128 each a score, 2 sequences of 64 heads
    assert WINDOW.flops(2, 8192, 64, 128, 512) == 4 * 128 * 2 * 64 * 4_063_488 == 266_304_749_568
    # q and o 64 heads, k and v 8, of 128 in bfloat16, and the float32 log-sum-exp
    assert WINDOW.bytes_moved(2, 8192, 64, 8, 128) == 2 * 8192 * 128 * (128 + 16) * 2 + 2 * 64 * 8192 * 4
    assert WINDOW.backward_flops(2, 8192, 64, 128, 512) == 2.5 * WINDOW.flops(2, 8192, 64, 128, 512)
    assert WINDOW.backward_bytes_moved(2, 8192, 64, 8, 128) == (
        2 * 8192 * 128 * (4 * 64 + 4 * 8) * 2 + 3 * 2 * 64 * 8192 * 4)
    # compute-bound still: 1.35 ms of FLOPs against 0.74 ms of bytes forward
    compute = WINDOW.flops(2, 8192, 64, 128, 512) / PEAKS["bf16_flops_per_s"]
    memory = WINDOW.bytes_moved(2, 8192, 64, 8, 128) / PEAKS["hbm_bytes_per_s"]
    assert compute == pytest.approx(1.352e-3, rel=1e-3) and memory == pytest.approx(0.743e-3, rel=1e-3)
    assert WINDOW.window_layers(CONFIG) == (3, 64)
    assert WINDOW.window_layers(MANIFEST.config("glm-4.7-flash")) is None
    assert WINDOW.window_layers(MANIFEST.config("lfm2-8b-a1b")) is None  # layer_types, no window
    mixed = dict(CONFIG, num_attention_heads_per_layer=[48, 64, 32, 64, 48])
    assert WINDOW.window_layers(mixed) is None  # no one cost a call


# --- the readers on made-up rows --------------------------------------------------


def traced_run(ops: dict[str, tuple[str, int]], programs: int = 2, config=CONFIG) -> dict:
    """`ops`: operation -> (op_name, nanoseconds a step); laid end to end."""
    rows, names, t = [], {}, 0
    for step in range(programs):
        rows.append([P0, tr.MODULE_LINE, f"jit_train_step({step})", t, 10**9])
        for operation, (op_name, ns) in ops.items():
            kind = "custom-call" if "flash" in operation else "fusion"
            rows.append([P0, tr.OP_LINE, f"%{operation} = bf16[8]{{0}} {kind}()", t, ns])
            names[operation] = op_name
            t += ns
    return {
        "trace_rows": rows, "op_names": names, "trace": {"per_device": [{"programs": programs}]},
        "config": config, "traffic": TRAFFIC, "peaks": PEAKS, "manifest": MANIFEST, "chips": 1,
    }


STEP = "jit(train_step)/loss/"
BACK = STEP + "transpose(jvp(while))/body/checkpoint/"
# A step of three window layers and two full ones, rematerialised: six windowed
# forward calls, three backward passes; four full forward calls, two passes.
OPS = {}
for i in range(6):
    OPS[f"_window_flash_forward.{i}"] = (STEP + "while/body/checkpoint/attn_window/core/x", 5_000_000)
for i in range(3):
    OPS[f"_window_flash_backward_dkv.{i}"] = (BACK + "attn_window/core/attn_bwd/x", 8_000_000)
    OPS[f"_window_flash_backward_dq.{i}"] = (BACK + "attn_window/core/attn_bwd/x", 7_000_000)
for i in range(4):
    OPS[f"_flash_forward.{i}"] = (STEP + "while/body/checkpoint/attn/core/x", 15_000_000)
for i in range(2):
    OPS[f"_flash_backward_dkv.{i}"] = (BACK + "attn/core/attn_bwd/x", 20_000_000)
    OPS[f"_flash_backward_dq.{i}"] = (BACK + "attn/core/attn_bwd/x", 18_000_000)
OPS.update({
    "fusion.1": (STEP + "while/body/checkpoint/attn_window/qkv/dot_general", 9_000_000),
    "fusion.2": (STEP + "while/body/checkpoint/attn_window/gate/mul", 2_000_000),
    "fusion.3": (BACK + "attn_window/rope/mul", 3_000_000),
    "fusion.4": (STEP + "while/body/checkpoint/attn/gate/mul", 1_500_000),
    "fusion.5": (STEP + "while/body/checkpoint/attn/qkv/dot_general", 6_000_000),
    "fusion.6": (STEP + "while/body/checkpoint/moe/experts/jit(gmm)/pallas_call", 16_000_000),
})


def test_window_attention_time_is_the_three_windowed_kernels_per_program():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "window_attention_ms_per_step")
    assert reader.read(run) == pytest.approx(6 * 5 + 3 * 8 + 3 * 7)  # not the full layers' kernels
    assert run["notes"]["window_scope_ms_per_step"] == pytest.approx({
        "attn_window/qkv": 9.0, "attn_window/gate": 2.0, "attn_window/rope": 3.0,
        "attn_window/core": 6 * 5 + 3 * 15, "attn/qkv": 6.0, "attn/gate": 1.5,
    })
    assert run["notes"]["window_attention_kernels"]["calls_per_step"] == {
        "_window_flash_forward": 6.0, "_window_flash_backward_dkv": 3.0, "_window_flash_backward_dq": 3.0}


def test_window_roofline_shares_divide_the_bands_least_time_by_the_kernels():
    run = traced_run(OPS)
    forward = MANIFEST.module("layer_metrics", "window_attention_roofline_share")
    least = WINDOW.flops(2, 8192, 64, 128, 512) / PEAKS["bf16_flops_per_s"]
    assert forward.read(run) == pytest.approx(100 * least / 5e-3, rel=1e-9) == pytest.approx(27.04, rel=1e-3)
    note = run["notes"]["window_attention_roofline"]
    assert note["bound"] == "compute" and note["calls"] == 12 and note["window_layers"] == 3
    backward = MANIFEST.module("layer_metrics", "window_attention_backward_roofline_share")
    least = WINDOW.backward_flops(2, 8192, 64, 128, 512) / PEAKS["bf16_flops_per_s"]
    assert backward.read(run) == pytest.approx(100 * least / 15e-3, rel=1e-9) == pytest.approx(22.53, rel=1e-3)
    note = run["notes"]["window_attention_backward_roofline"]
    assert note["passes"] == 6 and note["kernel_calls"] == {
        "_window_flash_backward_dkv": 6, "_window_flash_backward_dq": 6}
    assert forward.read(run) < 100.0 and backward.read(run) < 100.0


def test_the_full_causal_readers_see_the_full_layers_calls_alone():
    """`attention_roofline_share` and `attention_backward_roofline_share` match
    `^_flash_forward` / `^_flash_backward` and multiply by `num_attention_heads`
    = 48: the windowed kernels' names do not match, so the shares are the two
    full layers' (four forward calls, two passes a step)."""
    run = traced_run(OPS)
    forward = MANIFEST.module("layer_metrics", "attention_roofline_share")
    backward = MANIFEST.module("layer_metrics", "attention_backward_roofline_share")
    cost = MANIFEST.module("flops", "attention")
    least = cost.flops(2, 8192, 48, 128) / PEAKS["bf16_flops_per_s"]
    assert forward.read(run) == pytest.approx(100 * least / 15e-3, rel=1e-9) == pytest.approx(55.8, rel=1e-3)
    assert run["notes"]["attention_roofline"]["calls"] == 8
    assert backward.read(run) == pytest.approx(100 * 2.5 * least / 38e-3, rel=1e-9)
    assert run["notes"]["attention_backward_roofline"]["kernel_calls"] == {
        "_flash_backward_dkv": 4, "_flash_backward_dq": 4}
    assert CONFIG["num_attention_heads"] == 48 and CONFIG["head_dim"] == 128


def test_a_program_without_the_kernels_gives_nothing_and_raises_nothing():
    """The parent of this PR with this PR's readers laid over it, on the cells
    it has; a traced run with no device plane; and configurations without
    window layers."""
    readers = [MANIFEST.module("layer_metrics", name) for name in (
        "window_attention_ms_per_step", "window_attention_roofline_share",
        "window_attention_backward_roofline_share")]
    old = {k: v for k, v in OPS.items() if not k.startswith("_window")}
    glm = MANIFEST.config("glm-4.7-flash")
    no_device_plane = {"trace_rows": [], "trace": {"per_device": []}, "config": CONFIG,
                       "traffic": TRAFFIC, "manifest": MANIFEST}  # a traced run on the CPU
    for run in (traced_run(old), traced_run(old, config=glm), no_device_plane,
                {"config": CONFIG, "traffic": TRAFFIC, "manifest": MANIFEST}):
        assert [reader.read(run) for reader in readers] == [None, None, None]
    # windowed kernels under a configuration that names no window layers: the time, no share
    assert [reader.read(traced_run(OPS, config=glm)) for reader in readers[1:]] == [None, None]


# --- the manifest's new entries ---------------------------------------------------


def test_configuration_file_holds_every_published_key_and_the_cut():
    period = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
        "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False, "rms_norm_eps": 1e-06,
        "num_experts": 256, "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "gating": True,
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": period * 10, "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5, "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
    }
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():  # the guide's row, where the guide is installed
        row = next(json.loads(line) for line in catalog.read_text().splitlines() if '"Laguna-XS.2"' in line)
        assert row["config"] == published and row["source_url"] == CONFIG["source"]
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
               "num_experts", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    assert {k: CONFIG[k] for k in published if k not in reduced} == {
        k: v for k, v in published.items() if k not in reduced}
    assert CONFIG["published"] == {k: published[k] for k in reduced}
    # as run: published layers 0-4, the dense full layer and one whole period
    assert CONFIG["layer_types"] == published["layer_types"][:5] == period + ["full_attention"]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert CONFIG["num_hidden_layers"] == 5
    # the guide's floors: a whole period and four layers after the dense one, 8 experts, an eighth
    assert CONFIG["num_experts"] == 64 >= 8 and CONFIG["vocab_size"] * 4 == 100352
    assert CONFIG["deployment"]["chips_per_layer"] * CONFIG["num_experts"] == 256
    assert CONFIG["deployment"]["rank"] == 0 and "expert parallelism" in CONFIG["deployment"]["layout"]
    for key in ("gate", "router", "expert_bias", "qk_norm", "shared_expert", "router_weight", "rotary",
                "sliding_window", "reader_keys", "seeded_weights", "auxiliary_loss", "optimizer",
                "remat_policy"):
        assert key in CONFIG["assumed"]
    # the accepted readers' key names, beside the published ones
    assert CONFIG["n_routed_experts"] == CONFIG["num_experts"]
    assert CONFIG["first_k_dense_replace"] == CONFIG["mlp_layer_types"].count("dense") == 1
    assert CONFIG["num_nextn_predict_layers"] == 0
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == "laguna-xs.2")
    assert CONFIG["source"] == entry["source"] and entry["reduced"] == reduced
    assert entry["source"] == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    # the builder reads both rotary rules off the file
    model = BUILDER.model_config(CONFIG)
    assert model.full_rotary.inv_freq(128).shape == (32,) and model.sliding_rotary.inv_freq(128).shape == (64,)
    assert model.full_rotary.attention_factor == 1.4158883083359672 and model.sliding_window == 512


def test_the_cells_of_pr_26_31_and_33_and_their_metrics_by_name():
    """What `test_benchmark_conv_attn_moe.py::test_the_cells_of_pr_26_and_pr_31_
    and_their_metrics_by_name` held (and through it PR 26's test of the
    manifest's tail), by name and by containment and never by position, so
    that the next appended cell or metric supersedes nothing; and the same
    for this PR's entries."""
    data = MANIFEST.data
    cells = {w["name"]: w for w in data["workloads"]}
    for name, config in ((GLM_CELL, "glm-4.7-flash"), (LFM2_CELL, "lfm2-8b-a1b"), (CELL, "laguna-xs.2")):
        assert {k: cells[name][k] for k in ("config", "traffic", "chips")} == {
            "config": config, "traffic": "train-s8192", "chips": 1}
        assert set(cells[name]) == {"name", "config", "traffic", "chips", "why"}
    assert {"resnet50.train-b128", MISTRAL_CELL, "resnet50.train-dp4", GLM_CELL, LFM2_CELL, CELL} <= set(cells)
    order = [w["name"] for w in data["workloads"]]
    assert order.index(GLM_CELL) < order.index(LFM2_CELL) < order.index(CELL)  # each appended in its turn
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    assert TRAFFIC["seq_len"] == 8192 and TRAFFIC["global_batch"] == 2
    metrics = {m["name"]: m for m in data["per_layer"]}
    names = [m["name"] for m in data["per_layer"]]
    glm_only = ["mla_projection_ms_per_step", "mtp_ms_per_step"]
    routed = ["moe_ms_per_step", "moe_dispatch_ms_per_step", "moe_experts_roofline_share",
              "moe_load_max_over_mean"]
    lfm2_only = ["conv_mixer_ms_per_step", "short_conv_roofline_share"]
    new = ["window_attention_ms_per_step", "window_attention_roofline_share",
           "window_attention_backward_roofline_share"]
    # each PR's metrics follow the earlier PRs', in the order they were appended
    positions = [names.index(n) for n in routed + glm_only + lfm2_only + new]
    assert positions == sorted(positions)
    kernels = {"attention_roofline_share", "attention_backward_ms_per_step",
               "attention_backward_roofline_share", "recompute_ms_per_step"}
    for name in glm_only:
        assert metrics[name]["workloads"] == [GLM_CELL]
    for name in lfm2_only:
        assert metrics[name]["workloads"] == [LFM2_CELL]
    for name in new:
        assert set(metrics[name]["workloads"]) >= {CELL}
        assert not {GLM_CELL, LFM2_CELL, MISTRAL_CELL} & set(metrics[name]["workloads"])
    for name in routed:
        assert set(metrics[name]["workloads"]) >= {GLM_CELL, LFM2_CELL, CELL}
        assert MISTRAL_CELL not in metrics[name]["workloads"]
    for name in kernels:
        assert set(metrics[name]["workloads"]) >= {MISTRAL_CELL, GLM_CELL, LFM2_CELL, CELL}
    assert all(metrics[n]["moves"] == "train_throughput" for n in routed + glm_only + lfm2_only + new)
    assert (metrics[lfm2_only[0]]["layer"], metrics[lfm2_only[1]]["layer"]) == ("trainer", "kernels")
    assert metrics[lfm2_only[1]]["unit"] == "%" and metrics[lfm2_only[1]]["source"] == "device_trace"
    assert [metrics[n]["layer"] for n in new] == ["kernels"] * 3
    assert [metrics[n]["unit"] for n in new] == ["ms", "%", "%"]
    assert all(metrics[n]["source"] == "device_trace" for n in new)
    reported = {m["name"] for m in MANIFEST.per_layer_for(CELL)}
    assert set(new) | set(routed) | kernels <= reported
    assert not {"collective_exposed_ms_per_step", *glm_only, *lfm2_only} & reported
    lfm2_reported = {m["name"] for m in MANIFEST.per_layer_for(LFM2_CELL)}
    assert set(lfm2_only) | set(routed) | kernels <= lfm2_reported
    assert not {"collective_exposed_ms_per_step", *glm_only, *new} & lfm2_reported
    glm_reported = {m["name"] for m in MANIFEST.per_layer_for(GLM_CELL)}
    assert set(routed) | set(glm_only) | kernels <= glm_reported
    assert not (set(lfm2_only) | set(new)) & glm_reported
    # the other cells read none of the three PRs' metrics
    for cell in (MISTRAL_CELL, "resnet50.train-b128"):
        assert not set(routed + glm_only + lfm2_only + new) & {
            m["name"] for m in MANIFEST.per_layer_for(cell)}
    # every file a cell's names lead to is there
    for name in new:
        assert callable(MANIFEST.module("layer_metrics", name).read)
    assert callable(BUILDER.build) and callable(REFERENCE.follow) and callable(FLOPS.per_example)
    assert MANIFEST.json("limits", CELL)


def test_the_limits_file_has_the_five_limits_and_the_readings_they_were_set_from():
    limits = MANIFEST.json("limits", CELL)
    assert set(limits) == {"loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap",
                           "update_norm_gap", "readings"}
    readings = limits["readings"]
    failed = 0
    for name in ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"):
        assert readings[name]["sound_max"] < limits[name], name  # room above the sound readings
        assert readings[name]["seeds"] >= 3
        failed += readings[name]["control_min"] > limits[name]
    assert failed >= 1  # the fp8 control is not correct
    assert "origin" in readings and "why" in readings
