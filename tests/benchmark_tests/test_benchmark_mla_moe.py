"""The `mla_moe` kind (PR 26): the plain reference against `models/mla_moe.py`
(both heads' logits, the loss, every leaf's gradient, one AdamW step) under a
share that is not the first; the shares of a layer against the uncut
reference's whole layer; `flops/mla_moe.py` and `flops/moe_experts.py` against
ISSUE 26's arithmetic; each new reader on made-up rows and counters; the
configuration file against the published `config.json`.  A whole run of the
kind is test_benchmark_mla_moe_run.py (a file of its own, so that it goes to
another worker)."""

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
REFERENCE = MANIFEST.module("reference", "mla_moe")
BUILDER = MANIFEST.module("builders", "mla_moe")
FLOPS = MANIFEST.module("flops", "mla_moe")
EXPERTS = MANIFEST.module("flops", "moe_experts")
CELL = "glm-4.7-flash.train-s8192"
CONFIG = MANIFEST.config("glm-4.7-flash")
TRAFFIC = MANIFEST.json("traffic", "train-s8192")
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
P0 = "/device:TPU:0"
TOY = json.loads((REPO / "tests/benchmark_tests/configs/mla-moe-toy.json").read_text())
# The same structure in float32, where program and reference agree closely.
TOY32 = dict(TOY, torch_dtype="float32")
TOY_TRAFFIC = {"kind": "train", "input": "tokens", "seq_len": 16, "global_batch": 8,
               "pool_batches": 2, "log_every": 2, "warm_seconds": 0, "check_steps": 1,
               "trace_seconds": 1}


def toy_batch(seed=0):
    # eight sequences: the tests' mesh has eight devices and the builder uses them all
    x = np.random.default_rng(seed).integers(0, TOY["vocab_size"], (8, 16), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


@pytest.fixture(scope="module")
def built():
    x, _ = toy_batch()
    return BUILDER.build(TOY32, TOY_TRAFFIC, jax.random.key(3), x, REFERENCE)


def test_reference_agrees_with_the_model_on_logits_loss_and_every_gradient(built):
    from deeplearning_cfn_tpu.models import mla_moe

    key = jax.random.key(3)
    cfg = BUILDER.model_config(TOY32)
    assert cfg.held_experts == (4, 4)  # rank 1 of two chips: not the first span
    x, y = (jnp.asarray(a) for a in toy_batch())
    params = built.state.params
    with jax.default_matmul_precision("highest"):  # jitted: eager, each takes ten times as long
        seeded = jax.jit(lambda k: REFERENCE.init_params(k, TOY32))(key)
        ours = jax.jit(lambda p: mla_moe.logits(cfg, p, x, y))(params)
        theirs = jax.jit(lambda p: REFERENCE.forward(p, x, y, TOY32))(seeded)
        for head in ("main", "mtp"):
            np.testing.assert_allclose(
                np.asarray(ours[head]), np.asarray(theirs[head]), atol=5e-5, rtol=5e-5
            )
        # every routed block, the prediction module's too, selects the same experts
        assert ours["selected"].shape == theirs["selected"].shape == (3, 128, 2)
        np.testing.assert_array_equal(
            np.sort(np.asarray(ours["selected"]), -1), np.sort(np.asarray(theirs["selected"]), -1)
        )
        loss, grads = jax.jit(jax.value_and_grad(lambda p: mla_moe.lm_loss(cfg, p, x, y)[0]))(params)
        assert float(loss) == pytest.approx(float(theirs["loss"]), rel=1e-6)
        got = built.to_reference(grads)
        want = jax.jit(jax.grad(lambda p: REFERENCE.loss(p, x, y, TOY32)))(seeded)
    assert set(got) == set(REFERENCE.all_leaves(TOY32)) and len(got) == 67
    for name in got:  # the selection bias is the one leaf left out: a buffer
        scale = float(jnp.max(jnp.abs(want[name])))
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 2e-5 * scale + 1e-9, name
    assert set(want) - set(got) == {"layers/0/moe/router_bias", "layers/1/moe/router_bias",
                                   "mtp/block/moe/router_bias"}


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
    assert followed["routing"] == {"assignments": 3 * 128 * 2, "differing": 0, "share": 0.0}
    assert followed["head_leaves"] == ["output", "final_norm", "mtp/final_norm"]
    # The buffer stayed where it was seeded.
    bias = state.params["layers"]["moe"]["router_bias"][0]
    np.testing.assert_allclose(
        np.asarray(bias), np.asarray(REFERENCE.init_leaf(key, "layers/0/moe/router_bias", TOY32)),
        rtol=1e-6,
    )


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Two chips hold four experts each of the toy's eight: what each
    computes, the shared expert counted once, adds up to the plain reference's
    layer with all eight experts held."""
    from deeplearning_cfn_tpu.ops.moe import routed_experts

    uncut = dict(TOY32, n_routed_experts=8, deployment={"rank": 0})
    key = jax.random.key(5)
    lp = {n: REFERENCE.init_leaf(key, "layers/0/" + n, uncut).astype(jnp.float32)
          for n in REFERENCE.ROUTED_LEAVES + REFERENCE.BUFFERS if n.startswith("moe/")}
    n = jax.random.normal(jax.random.key(6), (48, TOY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = REFERENCE.routed_ffn(lp, n, uncut, REFERENCE.Rounding())
        parts = 0
        for rank in range(2):
            cfg = BUILDER.model_config(dict(TOY32, deployment={"rank": rank})).routed
            assert cfg.span == (4 * rank, 4)
            share = {k[4:]: v for k, v in lp.items()}
            for name in ("w_gate", "w_up", "w_down"):
                share[name] = share[name][4 * rank : 4 * rank + 4]
            y, stats = routed_experts(cfg, share, n[None], kind="xla")
            assert int(stats["dropped"]) == 0
            parts = parts + y[0]
        shared = (jax.nn.silu(n @ lp["moe/shared_gate"]) * (n @ lp["moe/shared_up"])) @ lp[
            "moe/shared_down"]
    np.testing.assert_allclose(np.asarray(parts - shared), np.asarray(whole), atol=2e-5)


def test_differing_assignments_counts_experts_the_other_side_did_not_choose():
    ours = np.array([[[0, 1], [2, 3], [4, 5]]])
    theirs = np.array([[[1, 0], [2, 7], [6, 7]]])  # the same set; one of two; none
    assert REFERENCE.differing_assignments(ours, theirs) == {
        "assignments": 6, "differing": 3, "share": 0.5}


# --- the counts -----------------------------------------------------------------


def test_weights_a_token_passes_through_by_hand():
    # q down 2048x768, q up 768x5120, kv down 2048x576, kv up 512x8960, out 5120x2048
    attention = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert FLOPS.attention_weights(CONFIG) == attention == 21_757_952
    expert = 3 * 2048 * 1536
    assert expert == 9_437_184 and FLOPS.routed_tokens_share(CONFIG) == 4 * 16 / 64 == 1.0
    # router 2048 x 64, one held expert in expectation, the shared one
    routed = attention + 2048 * 64 + 2 * expert
    assert FLOPS.routed_block_weights(CONFIG) == routed == 40_763_392
    dense = attention + 3 * 2048 * 10240
    head, join = 2048 * 38720, 2 * 2048 * 2048
    # a dense layer, four routed ones and the prediction module's, two heads, the join
    assert FLOPS.matmul_weights(CONFIG) == dense + 5 * routed + 2 * head + join == 455_475_200


@pytest.mark.parametrize("layers, per_token, per_step", [(5, 4.243e9, 69.51e12), (6, 4.739e9, 77.64e12)])
def test_flops_a_token_and_a_step_are_issue_26s(layers, per_token, per_step):
    """ISSUE 26 reckons 4.74 GFLOP a token and 77.6 TFLOP a step at five
    routed layers and S 8192; the cell runs four."""
    config = dict(CONFIG, num_hidden_layers=layers)
    example = FLOPS.per_example(config, TRAFFIC)
    scores = 3 * 8192 * 8192 * 20 * (256 + 256) * (layers + 1)  # the prediction module's block too
    assert example == 6.0 * FLOPS.matmul_weights(config) * 8192 + scores
    assert example / 8192 == pytest.approx(per_token, rel=1e-3)
    assert 2 * example == pytest.approx(per_step, rel=1e-3)
    # a routed layer's score products: 252 M FLOP a token, about half of the layer's
    a_layer = 3 * 8192 * 20 * 512
    assert a_layer == pytest.approx(252e6, rel=2e-3)
    assert 0.45 < a_layer / (a_layer + 6 * FLOPS.routed_block_weights(config)) < 0.55
    # and the program's own count says the same
    from deeplearning_cfn_tpu.models import mla_moe

    model = BUILDER.model_config(config)
    assert mla_moe.train_flops_per_token(model, 8192) * 8192 == pytest.approx(example, rel=1e-12)
    assert CONFIG["num_hidden_layers"] == 5


def test_grouped_matmul_flops_and_bytes_by_hand():
    # 1000 rows through a SwiGLU of 2048 x 1536: 3 matmuls, 2 FLOPs a weight
    forward = 1000 * 3 * 2 * 2048 * 1536
    assert EXPERTS.flops(1000, 2048, 1536, 1, 0) == forward
    assert EXPERTS.flops(1000, 2048, 1536, 1, 1) == 3 * forward
    assert EXPERTS.flops(1000, 2048, 1536, 2, 1) == 4 * forward  # a rematerialised block
    weights = 16 * 3 * 2048 * 1536 * 2
    assert EXPERTS.bytes_moved(1000, 16, 2048, 1536, 1, 0) == weights + 2 * 1000 * 2048 * 2
    assert EXPERTS.bytes_moved(1000, 16, 2048, 1536, 0, 1) == 2 * weights + 3 * 1000 * 2048 * 2


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
    "fusion.1": (STEP + "while/body/checkpoint/moe/router/dot_general", 1_000_000),
    "fusion.2": (STEP + "while/body/checkpoint/moe/dispatch/gather", 2_000_000),
    "gmm.3": (STEP + "while/body/checkpoint/moe/experts/jit(gmm)/pallas_call", 16_000_000),
    "gmm.4": (BACK + "rematted_computation/moe/experts/jit(gmm)/pallas_call", 16_000_000),
    "tgmm.5": (BACK + "moe/experts/jit(tgmm)/pallas_call", 32_000_000),
    "fusion.6": (BACK + "moe/combine/mul", 3_000_000),
    "fusion.7": (STEP + "while/body/checkpoint/moe/shared/dot_general", 5_000_000),
    "fusion.8": (STEP + "while/body/checkpoint/attn/q_down/dot_general", 1_500_000),
    "fusion.9": (BACK + "attn/kv_up/dot_general", 2_500_000),
    "fusion.10": (STEP + "while/body/checkpoint/attn/rope/concatenate", 500_000),
    "fusion.11": (STEP + "while/body/checkpoint/attn/core/_flash_forward", 9_000_000),
    "fusion.12": (STEP + "mtp/join/dot_general", 2_000_000),
    "fusion.13": (STEP + "transpose(jvp(mtp))/block/moe/experts/jit(gmm)/pallas_call", 4_000_000),
    "fusion.14": ("jit(train_step)/optimizer/add", 7_000_000),
}


@pytest.fixture()
def counted():
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()
    for _ in range(3):  # three steps; five routed blocks of 16 held experts
        tracing.counter("moe.assignments", 5 * 65536.0)
        tracing.counter("moe.assignments_held", 80_000.0)
        tracing.counter("moe.expert_load_max", 1500.0)
        tracing.counter("moe.expert_load_mean", 1000.0)
        tracing.counter("moe.dropped", 0.0)
    yield
    tracing.reset_aggregates()


def test_scope_times_are_per_program_and_by_scope(counted):
    run = traced_run(OPS)
    read = lambda name: MANIFEST.module("layer_metrics", name).read(run)
    assert read("moe_ms_per_step") == pytest.approx(1 + 2 + 16 + 16 + 32 + 3 + 5 + 4)
    assert read("moe_dispatch_ms_per_step") == pytest.approx(1 + 2 + 3)
    assert read("mla_projection_ms_per_step") == pytest.approx(1.5 + 2.5 + 0.5)  # not the core
    assert read("mtp_ms_per_step") == pytest.approx(2 + 4)
    assert read("moe_load_max_over_mean") == pytest.approx(1.5)
    assert run["notes"]["moe_routing"]["moe.dropped"] == 0.0
    assert run["notes"]["moe_routing"]["steps"] == 3


def test_experts_roofline_share_counts_the_passes_its_events_hold(counted):
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "moe_experts_roofline_share")
    measured_ms = 16 + 16 + 32 + 4
    # forward, the rematerialised forward and one backward: 4 forward passes' FLOPs
    least = 80_000 * 6 * 2048 * 1536 * 4 / PEAKS["bf16_flops_per_s"]
    assert reader.read(run) == pytest.approx(100 * 1e3 * least / measured_ms, rel=1e-9)
    note = run["notes"]["moe_experts_roofline"]
    assert note["bound"] == "compute" and note["forward_passes"] == 2
    # nothing rematerialised: one forward pass is counted, not two
    plain = {k: v for k, v in OPS.items() if "rematted" not in v[0]}
    assert reader.read(traced_run(plain)) == pytest.approx(
        100 * 1e3 * least * 3 / 4 / (16 + 32 + 4), rel=1e-9
    )
    assert reader.read(run) < 100.0


def test_a_program_without_the_scopes_or_the_counters_gives_nothing_and_raises_nothing():
    """The parent of the PR that added them, with this PR's readers laid over it."""
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()
    old = {k: (v[0].replace("moe/", "mlp/").replace("attn/", "a/").replace("mtp", "m"), v[1])
           for k, v in OPS.items()}
    no_device_plane = {"trace_rows": [], "trace": {"per_device": []}, "config": CONFIG,
                       "traffic": TRAFFIC, "manifest": MANIFEST}  # a traced run on the CPU
    for run in (traced_run(old), no_device_plane,
                {"config": CONFIG, "traffic": TRAFFIC, "manifest": MANIFEST}):
        for name in ("moe_ms_per_step", "moe_dispatch_ms_per_step", "moe_experts_roofline_share",
                     "moe_load_max_over_mean", "mla_projection_ms_per_step", "mtp_ms_per_step"):
            assert MANIFEST.module("layer_metrics", name).read(run) is None, name


# --- the manifest's new entries ---------------------------------------------------


def test_configuration_file_holds_every_published_key_and_the_cut():
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
    }
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    assert {k: CONFIG[k] for k in published if k not in reduced} == {
        k: v for k, v in published.items() if k not in reduced}
    assert CONFIG["published"] == {k: published[k] for k in reduced}
    # the guide's floors: four routed layers after the dense one, 8 experts, an eighth
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] == 16 >= 8 and CONFIG["vocab_size"] * 4 == 154880
    assert CONFIG["deployment"]["chips_per_layer"] * CONFIG["n_routed_experts"] == 64
    assert CONFIG["deployment"]["rank"] == 0 and "expert parallelism" in CONFIG["deployment"]["layout"]
    for key in ("head_dim", "rotary", "selection_bias", "router", "auxiliary_loss", "mtp",
                "optimizer", "remat_policy"):
        assert key in CONFIG["assumed"]
    assert CONFIG["head_dim"] == CONFIG["qk_nope_head_dim"] + CONFIG["qk_rope_head_dim"]
    assert CONFIG["source"] == next(
        c["source"] for c in MANIFEST.data["configs"] if c["name"] == "glm-4.7-flash")


def test_the_cell_and_its_metrics_are_appended_and_nothing_else_changed():
    data = MANIFEST.data
    assert data["workloads"][-1] == {
        "name": CELL, "config": "glm-4.7-flash", "traffic": "train-s8192", "chips": 1,
        "why": data["workloads"][-1]["why"],
    }
    assert TRAFFIC["seq_len"] == 8192 and TRAFFIC["global_batch"] == 2
    new = ["moe_ms_per_step", "moe_dispatch_ms_per_step", "moe_experts_roofline_share",
           "moe_load_max_over_mean", "mla_projection_ms_per_step", "mtp_ms_per_step"]
    assert [m["name"] for m in data["per_layer"][-6:]] == new
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_throughput"
               for m in data["per_layer"][-6:])
    shared = {"attention_roofline_share", "attention_backward_ms_per_step",
              "attention_backward_roofline_share", "recompute_ms_per_step"}
    for m in data["per_layer"]:
        if m["name"] in shared:
            assert m["workloads"] == ["mistral-7b-v0.3.train-s4096", CELL]
    reported = {m["name"] for m in MANIFEST.per_layer_for(CELL)}
    assert set(new) | shared <= reported and "collective_exposed_ms_per_step" not in reported
    # the other cells read none of the new metrics
    assert not set(new) & {m["name"] for m in MANIFEST.per_layer_for("mistral-7b-v0.3.train-s4096")}
