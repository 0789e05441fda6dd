"""The `conv_attn_moe` kind (PR 31): the plain reference against
`models/conv_attn_moe.py` (logits, loss, every leaf's gradient, one AdamW step)
over the pattern [conv + dense, attention, conv, conv, conv] under a share that
is not the first; the mixers one by one; the shares of a layer against the uncut
reference's whole layer; `flops/conv_attn_moe.py` and `flops/short_conv.py`
against ISSUE 31's arithmetic; each new reader on made-up rows; the
configuration file against the published `config.json`; the manifest's appended
entries.  A whole run of the kind is test_benchmark_conv_attn_moe_run.py."""

import json
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
REFERENCE = MANIFEST.module("reference", "conv_attn_moe")
BUILDER = MANIFEST.module("builders", "conv_attn_moe")
FLOPS = MANIFEST.module("flops", "conv_attn_moe")
CONV = MANIFEST.module("flops", "short_conv")
CELL = "lfm2-8b-a1b.train-s8192"
GLM_CELL = "glm-4.7-flash.train-s8192"
CONFIG = MANIFEST.config("lfm2-8b-a1b")
TRAFFIC = MANIFEST.json("traffic", "train-s8192")
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
P0 = "/device:TPU:0"
TOY = json.loads((REPO / "tests/benchmark_tests/configs/conv-attn-moe-toy.json").read_text())
# The same structure in float32, where program and reference agree closely.
TOY32 = dict(TOY, torch_dtype="float32")
TOY_TRAFFIC = {"kind": "train", "input": "tokens", "seq_len": 16, "global_batch": 8,
               "pool_batches": 2, "log_every": 2, "warm_seconds": 0, "check_steps": 1,
               "trace_seconds": 1}


def toy_batch(seed=0):
    # eight sequences: the tests' mesh has eight devices and the builder uses them all
    x = np.random.default_rng(seed).integers(0, TOY["vocab_size"], (8, 16), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


@pytest.fixture(scope="module", autouse=True)
def leave_no_counters():
    """`fit` folds the `moe.*` counters into the process's aggregates, and a
    later run in this worker (test_benchmark_mla_moe_run.py) reads them."""
    yield
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()


@pytest.fixture(scope="module")
def built():
    x, _ = toy_batch()
    return BUILDER.build(TOY32, TOY_TRAFFIC, jax.random.key(3), x, REFERENCE)


def test_reference_agrees_with_the_model_on_logits_loss_and_every_gradient(built):
    """float32 on both sides, so what is left is the order of sums: logits to
    5e-5 of values of a few units, the loss to 1e-6, each leaf's gradient to
    2e-5 of its largest element."""
    from deeplearning_cfn_tpu.models import conv_attn_moe

    key = jax.random.key(3)
    cfg = BUILDER.model_config(TOY32)
    assert cfg.held_experts == (4, 4)  # rank 1 of two chips: not the first span
    assert cfg.kinds == (("conv", False), ("full_attention", True)) + (("conv", True),) * 3
    x, y = (jnp.asarray(a) for a in toy_batch())
    params = built.state.params
    with jax.default_matmul_precision("highest"):  # jitted: eager, each takes ten times as long
        seeded = jax.jit(lambda k: REFERENCE.init_params(k, TOY32))(key)
        ours = jax.jit(lambda p: conv_attn_moe.logits(cfg, p, x))(params)
        theirs = jax.jit(lambda p: REFERENCE.forward(p, x, y, TOY32))(seeded)
        np.testing.assert_allclose(
            np.asarray(ours["main"]), np.asarray(theirs["main"]), atol=5e-5, rtol=5e-5
        )
        # every routed block selects the same experts
        assert ours["selected"].shape == theirs["selected"].shape == (4, 128, 2)
        np.testing.assert_array_equal(
            np.sort(np.asarray(ours["selected"]), -1), np.sort(np.asarray(theirs["selected"]), -1)
        )
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p: conv_attn_moe.lm_loss(cfg, p, x, y)[0])
        )(params)
        assert float(loss) == pytest.approx(float(theirs["loss"]), rel=1e-6)
        got = built.to_reference(grads)
        want = jax.jit(jax.grad(lambda p: REFERENCE.loss(p, x, y, TOY32)))(seeded)
    # the table and the final norm; 8 leaves of the dense conv layer, 12 of the
    # routed attention layer, 9 of each routed conv layer
    assert set(got) == set(REFERENCE.all_leaves(TOY32)) and len(got) == 2 + 8 + 12 + 3 * 9
    for name in got:  # the selection bias is the one leaf left out: a buffer
        scale = float(jnp.max(jnp.abs(want[name])))
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 2e-5 * scale + 1e-9, name
    assert set(want) - set(got) == {f"layers/{i}/moe/router_bias" for i in (1, 2, 3, 4)}


@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_each_mixer_is_the_references(mixer):
    """One mixer alone on a normalised input: the gated short convolution
    against the reference's explicit sum over taps, and attention with
    normalised queries and keys against the reference's head by head."""
    from deeplearning_cfn_tpu.models import conv_attn_moe

    cfg = BUILDER.model_config(TOY32)
    key = jax.random.key(7)
    prefix = "layers/0/" if mixer == "conv" else "layers/1/"
    lp = {n: REFERENCE.init_leaf(key, prefix + n, TOY32) for n in REFERENCE.MIXER_LEAVES[mixer]}
    if mixer == "full_attention":  # scales far from 1, so that a norm left out would show
        lp["q_norm"], lp["k_norm"] = lp["q_norm"] * 3.0, lp["k_norm"] * 0.25
    h = jax.random.normal(jax.random.key(8), (2, 16, TOY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        if mixer == "conv":
            got = conv_attn_moe._conv_mixer(lp, h)
            want = jax.vmap(lambda n: REFERENCE.conv_mixer(lp, n, TOY32, REFERENCE.Rounding()))(h)
        else:
            got = conv_attn_moe._attention_mixer(cfg, None, lp, h, jnp.arange(16))
            want = jax.vmap(
                lambda n: REFERENCE.attention_mixer(lp, n, TOY32, REFERENCE.Rounding())
            )(h)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * scale)


def test_the_references_convolution_is_causal_and_position_zero_sees_the_last_tap():
    z = jax.random.normal(jax.random.key(0), (7, 3), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (3, 3), jnp.float32)
    c = np.asarray(REFERENCE.short_conv(z, w))
    np.testing.assert_allclose(c[0], np.asarray(w[2] * z[0]), rtol=1e-6)
    np.testing.assert_allclose(
        c[2], np.asarray(w[0] * z[0] + w[1] * z[1] + w[2] * z[2]), rtol=1e-5, atol=1e-6
    )
    c2 = np.asarray(REFERENCE.short_conv(z.at[4].add(1.0), w))
    np.testing.assert_array_equal(c2[:4], c[:4])
    assert np.all(c2[4:7] != c[4:7])


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
    assert followed["head_leaves"] == ["final_norm"]  # the tied table is no head leaf
    # The buffer stayed where it was seeded.
    bias = state.params["runs"][2]["moe"]["router_bias"][1]  # the second conv layer of its run
    np.testing.assert_allclose(
        np.asarray(bias), np.asarray(REFERENCE.init_leaf(key, "layers/3/moe/router_bias", TOY32)),
        rtol=1e-6,
    )


def test_the_shares_routed_parts_are_the_uncut_layer():
    """Two chips hold four experts each of the toy's eight: what each computes
    adds up to the plain reference's layer with all eight experts held; there
    is no shared expert to count once."""
    from deeplearning_cfn_tpu.ops.moe import routed_experts

    uncut = dict(TOY32, num_experts=8, deployment={"rank": 0})
    key = jax.random.key(5)
    lp = {n: REFERENCE.init_leaf(key, "layers/1/" + n, uncut).astype(jnp.float32)
          for n in REFERENCE.ROUTED_LEAVES + REFERENCE.BUFFERS}
    n = jax.random.normal(jax.random.key(6), (48, TOY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = REFERENCE.routed_ffn(lp, n, uncut, REFERENCE.Rounding())
        parts = 0
        for rank in range(2):
            cfg = BUILDER.model_config(dict(TOY32, deployment={"rank": rank})).routed
            assert cfg.span == (4 * rank, 4) and cfg.shared_dim == 0 and cfg.renormalize_eps == 1e-6
            share = {k[4:]: v for k, v in lp.items()}
            for name in ("w_gate", "w_up", "w_down"):
                share[name] = share[name][4 * rank : 4 * rank + 4]
            y, stats = routed_experts(cfg, share, n[None], kind="xla")
            assert int(stats["dropped"]) == 0
            parts = parts + y[0]
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=2e-5)


# --- the counts -----------------------------------------------------------------


def test_weights_a_token_passes_through_by_hand():
    # in 2048 x 6144, three taps a channel, out 2048 x 2048
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert FLOPS.mixer_weights(CONFIG, "conv") == conv == 16_783_360
    # q and o 2048 x 2048, k and v 2048 x 512 (8 heads of 64)
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert FLOPS.mixer_weights(CONFIG, "full_attention") == attention == 10_485_760
    expert = 3 * 2048 * 1792
    assert expert == 11_010_048 and FLOPS.routed_tokens_share(CONFIG) == 4 * 8 / 32 == 1.0
    # router 2048 x 32 and one held expert in expectation; no shared one
    assert FLOPS.feed_forward_weights(CONFIG, 1) == 2048 * 32 + expert == 11_075_584
    assert FLOPS.feed_forward_weights(CONFIG, 0) == 3 * 2048 * 7168 == 44_040_192
    head = 2048 * 16384
    # ten conv layers and three attention layers; one dense and twelve routed; the table once
    assert FLOPS.matmul_weights(CONFIG) == (
        10 * conv + 3 * attention + 44_040_192 + 12 * 11_075_584 + head
    ) == 409_792_512


def test_flops_a_token_and_a_step_are_issue_31s():
    """ISSUE 31 reckons about 2.76 GFLOP a trained token and 22.6 TFLOP an
    example at 13 layers and S 8192."""
    example = FLOPS.per_example(CONFIG, TRAFFIC)
    scores = 3 * 8192 * 8192 * 32 * (64 + 64) * 3  # the three attention layers only
    assert example == 6.0 * FLOPS.matmul_weights(CONFIG) * 8192 + scores
    assert example / 8192 == pytest.approx(2.76e9, rel=2e-3)
    assert example == pytest.approx(22.6e12, rel=2e-3)
    # and the program's own count says the same
    from deeplearning_cfn_tpu.models import conv_attn_moe

    model = BUILDER.model_config(CONFIG)
    assert conv_attn_moe.train_flops_per_token(model, 8192) * 8192 == pytest.approx(example, rel=1e-12)
    assert conv_attn_moe.param_count(model) == 1_334_692_608
    assert CONFIG["num_hidden_layers"] == 13 == len(CONFIG["layer_types"])


def test_the_convolutions_bytes_and_flops_by_hand():
    # 16,384 tokens of 2048 channels in bfloat16: B, C, u in and the result out
    one = 16384 * 2048 * 2
    assert CONV.bytes_moved(16384, 2048, 1, 0) == 4 * one == 268_435_456
    assert CONV.bytes_moved(16384, 2048, 0, 1) == 7 * one
    assert CONV.bytes_moved(16384, 2048, 2, 1) == 15 * one  # a rematerialised block
    # the product, three multiply-adds and the gate: 8 FLOPs an element forward
    assert CONV.flops(16384, 2048, 3, 1, 0) == 8 * 16384 * 2048
    assert CONV.flops(16384, 2048, 3, 2, 1) == 4 * 8 * 16384 * 2048
    # memory-bound by far: 15 x 67 MB over 819 GB/s is 1.2 ms, the FLOPs 5 us
    assert CONV.bytes_moved(16384, 2048, 2, 1) / PEAKS["hbm_bytes_per_s"] > 100 * (
        CONV.flops(16384, 2048, 3, 2, 1) / PEAKS["bf16_flops_per_s"])


# --- the readers on made-up rows --------------------------------------------------


def traced_run(ops: dict[str, tuple[str, int]], programs: int = 2) -> dict:
    """`ops`: operation -> (op_name, nanoseconds a step); laid end to end."""
    rows, names, t = [], {}, 0
    for step in range(programs):
        rows.append([P0, tr.MODULE_LINE, f"jit_train_step({step})", t, 10**9])
        for operation, (op_name, ns) in ops.items():
            rows.append([P0, tr.OP_LINE, f"%{operation} = bf16[8]{{0}} fusion()", t, ns])
            names[operation] = op_name
            t += ns
    return {
        "trace_rows": rows, "op_names": names, "trace": {"per_device": [{"programs": programs}]},
        "config": CONFIG, "traffic": TRAFFIC, "peaks": PEAKS, "manifest": MANIFEST, "chips": 1,
    }


STEP = "jit(train_step)/loss/"
BACK = STEP + "transpose(jvp(while))/body/checkpoint/"
OPS = {
    "fusion.1": (STEP + "while/body/checkpoint/operator_norm/mul", 1_000_000),
    "fusion.2": (STEP + "while/body/checkpoint/conv/in/dot_general", 20_000_000),
    "fusion.3": (STEP + "while/body/checkpoint/conv/core/mul", 4_000_000),
    "fusion.4": (STEP + "while/body/checkpoint/conv/out/dot_general", 7_000_000),
    "fusion.5": (BACK + "rematted_computation/conv/core/mul", 4_000_000),
    "fusion.6": (BACK + "conv/core/mul", 12_000_000),
    "fusion.7": (BACK + "conv/in/dot_general", 40_000_000),
    "fusion.8": (STEP + "while/body/checkpoint/attn/qkv/dot_general", 3_000_000),
    "fusion.9": (BACK + "attn/qk_norm/mul", 500_000),
    "fusion.10": (STEP + "while/body/checkpoint/attn/core/_flash_forward", 9_000_000),
    "fusion.11": (STEP + "while/body/checkpoint/moe/experts/jit(gmm)/pallas_call", 16_000_000),
    "fusion.12": (STEP + "while/body/checkpoint/ffn_norm/convert_element_type", 1_500_000),
    "fusion.13": ("jit(train_step)/optimizer/add", 7_000_000),
}


def test_conv_mixer_time_is_per_program_and_its_scopes_go_to_the_notes():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "conv_mixer_ms_per_step")
    assert reader.read(run) == pytest.approx(20 + 4 + 7 + 4 + 12 + 40)  # not the attention's core
    assert run["notes"]["conv_scope_ms_per_step"] == pytest.approx({
        "conv/in": 60.0, "conv/core": 20.0, "conv/out": 7.0, "attn/qkv": 3.0,
        "attn/qk_norm": 0.5, "loss/operator_norm": 1.0, "loss/ffn_norm": 1.5,
    })


def test_short_conv_roofline_share_counts_the_passes_its_events_hold():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "short_conv_roofline_share")
    # ten conv layers; forward, the rematerialised forward and one backward: 15 tensors
    # of 16,384 x 2048 bfloat16 a layer over the HBM peak, against 20 ms measured
    least = 10 * 15 * 16384 * 2048 * 2 / PEAKS["hbm_bytes_per_s"]
    assert reader.read(run) == pytest.approx(100 * 1e3 * least / 20.0, rel=1e-9)
    note = run["notes"]["short_conv_roofline"]
    assert note["bound"] == "memory" and note["forward_passes"] == 2 and note["conv_layers"] == 10
    # nothing rematerialised: one forward pass is counted, not two
    plain = {k: v for k, v in OPS.items() if "rematted" not in v[0]}
    assert reader.read(traced_run(plain)) == pytest.approx(
        100 * 1e3 * least * 11 / 15 / 16.0, rel=1e-9
    )
    assert reader.read(run) < 100.0


def test_a_program_without_the_scopes_gives_nothing_and_raises_nothing():
    """The parent of this PR with this PR's readers laid over it, on the cells
    it has; a traced run with no device plane; and a configuration without
    `conv` layers, whose convolutions' least time nothing can count."""
    readers = [MANIFEST.module("layer_metrics", name)
               for name in ("conv_mixer_ms_per_step", "short_conv_roofline_share")]
    old = {k: (v[0].replace("conv/", "mixer/"), v[1]) for k, v in OPS.items()}
    glm = MANIFEST.config("glm-4.7-flash")
    no_device_plane = {"trace_rows": [], "trace": {"per_device": []}, "config": CONFIG,
                       "traffic": TRAFFIC, "manifest": MANIFEST}  # a traced run on the CPU
    for run in (traced_run(old), dict(traced_run(old), config=glm), no_device_plane,
                {"config": CONFIG, "traffic": TRAFFIC, "manifest": MANIFEST}):
        assert [reader.read(run) for reader in readers] == [None, None]
    assert readers[1].read(dict(traced_run(OPS), config=glm)) is None


# --- the manifest's new entries ---------------------------------------------------


def test_configuration_file_holds_every_published_key_and_the_cut():
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "layer_types": ["conv", "conv", "full_attention"] + ["conv", "conv", "conv", "full_attention"] * 4
        + ["conv", "conv", "full_attention", "conv", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536,
    }
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size", "layer_types"]
    assert CONFIG["reduced"] == reduced
    assert {k: CONFIG[k] for k in published if k not in reduced} == {
        k: v for k, v in published.items() if k not in reduced}
    assert CONFIG["published"] == {k: published[k] for k in reduced}
    # as run: published layers 1-13, one dense layer and three whole periods
    assert CONFIG["layer_types"] == published["layer_types"][1:14]
    assert CONFIG["layer_types"][1:5] == ["full_attention", "conv", "conv", "conv"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"]) == (13, 1)
    # the guide's floors: a whole period and four layers after the dense one, 8 experts, an eighth
    assert CONFIG["num_experts"] == 8 and CONFIG["vocab_size"] * 4 == 65536
    assert CONFIG["deployment"]["chips_per_layer"] * CONFIG["num_experts"] == 32
    assert CONFIG["deployment"]["rank"] == 0 and "expert parallelism" in CONFIG["deployment"]["layout"]
    for key in ("tie_word_embeddings", "head_dim", "reader_keys", "rotary", "conv_filter", "qk_norm",
                "expert_bias", "renormalize_eps", "seeded_weights", "router", "auxiliary_loss",
                "optimizer", "remat_policy"):
        assert key in CONFIG["assumed"]
    # the accepted readers' key names, beside the published ones
    assert CONFIG["head_dim"] * CONFIG["num_attention_heads"] == CONFIG["hidden_size"]
    assert CONFIG["n_routed_experts"] == CONFIG["num_experts"]
    assert CONFIG["first_k_dense_replace"] == CONFIG["num_dense_layers"]
    assert CONFIG["num_nextn_predict_layers"] == 0
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == "lfm2-8b-a1b")
    assert CONFIG["source"] == entry["source"] and entry["reduced"] == reduced
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"


def test_the_cells_of_pr_26_and_pr_31_and_their_metrics_by_name():
    """What PR 26's test of the manifest's tail held, by name and not by
    position (a position holds for one PR), and the same for this PR's
    entries, which are last today."""
    data = MANIFEST.data
    cells = {w["name"]: w for w in data["workloads"]}
    assert {k: cells[GLM_CELL][k] for k in ("config", "traffic", "chips")} == {
        "config": "glm-4.7-flash", "traffic": "train-s8192", "chips": 1}
    assert data["workloads"][-1] == {
        "name": CELL, "config": "lfm2-8b-a1b", "traffic": "train-s8192", "chips": 1,
        "why": data["workloads"][-1]["why"],
    }
    assert [w["name"] for w in data["workloads"][:4]] == [
        "resnet50.train-b128", "mistral-7b-v0.3.train-s4096", "resnet50.train-dp4", GLM_CELL]
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    assert TRAFFIC["seq_len"] == 8192 and TRAFFIC["global_batch"] == 2
    metrics = {m["name"]: m for m in data["per_layer"]}
    order = [m["name"] for m in data["per_layer"]]
    glm_only = ["mla_projection_ms_per_step", "mtp_ms_per_step"]
    routed = ["moe_ms_per_step", "moe_dispatch_ms_per_step", "moe_experts_roofline_share",
              "moe_load_max_over_mean"]
    new = ["conv_mixer_ms_per_step", "short_conv_roofline_share"]
    assert order[-8:] == routed + glm_only + new
    kernels = {"attention_roofline_share", "attention_backward_ms_per_step",
               "attention_backward_roofline_share", "recompute_ms_per_step"}
    for name in glm_only:
        assert metrics[name]["workloads"] == [GLM_CELL]
    for name in routed:
        assert metrics[name]["workloads"] == [GLM_CELL, CELL]
    for name in kernels:
        assert metrics[name]["workloads"] == ["mistral-7b-v0.3.train-s4096", GLM_CELL, CELL]
    for name in new:
        assert metrics[name]["workloads"] == [CELL]
    assert all(metrics[n]["moves"] == "train_throughput" for n in routed + glm_only + new)
    assert (metrics[new[0]]["layer"], metrics[new[1]]["layer"]) == ("trainer", "kernels")
    assert metrics[new[1]]["unit"] == "%" and metrics[new[1]]["source"] == "device_trace"
    reported = {m["name"] for m in MANIFEST.per_layer_for(CELL)}
    assert set(new) | set(routed) | kernels <= reported
    assert not {"collective_exposed_ms_per_step", *glm_only} & reported
    glm_reported = {m["name"] for m in MANIFEST.per_layer_for(GLM_CELL)}
    assert set(routed) | set(glm_only) | kernels <= glm_reported and not set(new) & glm_reported
    # the other cells read none of either PR's metrics
    for cell in ("mistral-7b-v0.3.train-s4096", "resnet50.train-b128"):
        assert not set(routed + glm_only + new) & {m["name"] for m in MANIFEST.per_layer_for(cell)}


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
