"""The `ssm_attn_moe` kind (PR 41): the plain reference against
`models/ssm_attn_moe.py` (logits, loss, every leaf's gradient, one AdamW step)
over the pattern EMEM* under a share that is not the first; the reference's
recurrence against the program's chunked scan; the shares of an `E` block
against the uncut reference's whole block; `flops/ssm_attn_moe.py`,
`flops/ssd.py` and `flops/latent_experts.py` against ISSUE 41's arithmetic;
each new reader on made-up rows; the configuration file against the published
`config.json`; the manifest's appended entries.  A whole run of the kind is
test_benchmark_ssm_attn_moe_run.py."""

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
REFERENCE = MANIFEST.module("reference", "ssm_attn_moe")
BUILDER = MANIFEST.module("builders", "ssm_attn_moe")
FLOPS = MANIFEST.module("flops", "ssm_attn_moe")
SSD = MANIFEST.module("flops", "ssd")
LATENT = MANIFEST.module("flops", "latent_experts")
CELL = "nemotron-3-super-120b-a12b.train-s8192x1"
OTHER_DECODER_CELLS = ("mistral-7b-v0.3.train-s4096", "glm-4.7-flash.train-s8192",
                       "lfm2-8b-a1b.train-s8192", "laguna-xs.2.train-s8192")
CONFIG = MANIFEST.config("nemotron-3-super-120b-a12b")
TRAFFIC = MANIFEST.json("traffic", "train-s8192x1")
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
P0 = "/device:TPU:0"
TOY = json.loads((REPO / "tests/benchmark_tests/configs/ssm-attn-moe-toy.json").read_text())
# The same structure in float32, where program and reference agree closely.
TOY32 = dict(TOY, torch_dtype="float32")
# 20 tokens: two whole chunks of 8 and a ragged one.
TOY_TRAFFIC = {"kind": "train", "input": "tokens", "seq_len": 20, "global_batch": 8,
               "pool_batches": 2, "log_every": 2, "warm_seconds": 0, "check_steps": 1,
               "trace_seconds": 1}


def toy_batch(seed=0):
    # eight sequences: the tests' mesh has eight devices and the builder uses them all
    x = np.random.default_rng(seed).integers(0, TOY["vocab_size"], (8, 20), dtype=np.int32)
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


def test_reference_agrees_with_the_model_on_logits_loss_and_every_gradient(built):
    """float32 on both sides, so what is left is the order of sums (and the
    chunked scan's against the recurrence's): logits to 5e-5 of values of a few
    units, the loss to 1e-6, each leaf's gradient to 5e-5 of its largest
    element."""
    from deeplearning_cfn_tpu.models import ssm_attn_moe

    key = jax.random.key(3)
    cfg = BUILDER.model_config(TOY32)
    assert cfg.held_experts == (4, 4)  # rank 1 of two chips: not the first span
    assert cfg.runs == (("EM", 2), ("*", 1)) and cfg.routed.expert == "relu2"
    x, y = (jnp.asarray(a) for a in toy_batch())
    params = built.state.params
    with jax.default_matmul_precision("highest"):  # jitted: eager, each takes ten times as long
        seeded = jax.jit(lambda k: REFERENCE.init_params(k, TOY32))(key)
        ours = jax.jit(lambda p: ssm_attn_moe.logits(cfg, p, x))(params)
        theirs = jax.jit(lambda p: REFERENCE.forward(p, x, y, TOY32))(seeded)
        np.testing.assert_allclose(
            np.asarray(ours["main"]), np.asarray(theirs["main"]), atol=5e-5, rtol=5e-5
        )
        # both routed blocks select the same experts
        assert ours["selected"].shape == theirs["selected"].shape == (2, 160, 3)
        np.testing.assert_array_equal(
            np.sort(np.asarray(ours["selected"]), -1), np.sort(np.asarray(theirs["selected"]), -1)
        )
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p: ssm_attn_moe.lm_loss(cfg, p, x, y)[0])
        )(params)
        assert float(loss) == pytest.approx(float(theirs["loss"]), rel=1e-6)
        got = built.to_reference(grads)
        want = jax.jit(jax.grad(lambda p: REFERENCE.loss(p, x, y, TOY32)))(seeded)
    # the table, the head and the final norm; 8 leaves of each E block, 9 of each M, 5 of *
    assert set(got) == set(REFERENCE.all_leaves(TOY32)) and len(got) == 3 + 2 * (8 + 9) + 5
    for name in got:  # the selection bias is the one leaf left out: a buffer
        scale = float(jnp.max(jnp.abs(want[name])))
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 5e-5 * scale + 1e-9, name
    assert set(want) - set(got) == {f"layers/{i}/moe/router_bias" for i in (0, 2)}
    # the seeded decays are a trained model's: A in [1, 16], dt in [1e-3, 1e-1], D one
    a, dt_bias = np.exp(seeded["layers/1/A_log"]), np.asarray(seeded["layers/1/dt_bias"])
    assert a.min() >= 1.0 and a.max() <= 16.0 and np.all(seeded["layers/3/D"] == 1.0)
    dt = np.log1p(np.exp(dt_bias))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001


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
    assert followed["routing"] == {"assignments": 2 * 160 * 3, "differing": 0, "share": 0.0}
    assert followed["head_leaves"] == ["output", "final_norm"]
    # The buffer stayed where it was seeded: the second pair's E block, published block 2.
    bias = state.params["runs"][0][0]["moe"]["router_bias"][1]
    np.testing.assert_allclose(
        np.asarray(bias), np.asarray(REFERENCE.init_leaf(key, "layers/2/moe/router_bias", TOY32)),
        rtol=1e-6,
    )


@pytest.mark.parametrize("length", [24, 13], ids=["whole-chunks", "a-ragged-tail"])
def test_the_references_recurrence_is_the_programs_chunked_scan(length):
    """The definition a token at a time against `ops/ssd.py`'s four matmuls a
    chunk, value and the gradient of every input, groups of two heads."""
    from deeplearning_cfn_tpu.ops.ssd import ssd

    k = jax.random.split(jax.random.key(length), 7)
    H, P, G, N = 4, 3, 2, 5
    args = (
        jax.random.normal(k[0], (length, H, P)), 0.1 * jax.nn.softplus(jax.random.normal(k[1], (length, H))),
        -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0), jax.random.normal(k[3], (length, G, N)),
        jax.random.normal(k[4], (length, G, N)), jax.random.normal(k[5], (H,)),
    )
    dy = jax.random.normal(k[6], (length, H, P))
    batched = lambda x, dt, A, B, C, D: ssd(x[None], dt[None], A, B[None], C[None], D, 8)[0]
    with jax.default_matmul_precision("highest"):
        want, pull_want = jax.vjp(lambda *a: REFERENCE.recurrence(*a, chunk=8), *args)
        got, pull_got = jax.vjp(batched, *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
        for g, w in zip(pull_got(dy), pull_want(dy)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4)


def test_the_shares_routed_parts_through_the_latent_are_the_uncut_block():
    """Two chips hold four experts each of the toy's eight: the routed parts
    each computes, each through the latent output projection, with the shared
    expert counted once, add up to the plain reference's block with all eight
    experts held."""
    from deeplearning_cfn_tpu.models import ssm_attn_moe

    uncut = dict(TOY32, n_routed_experts=8, deployment={"rank": 0})
    key = jax.random.key(5)
    leaves = REFERENCE.BLOCK_LEAVES["E"] + REFERENCE.BUFFERS
    lp = {n: REFERENCE.init_leaf(key, "layers/0/" + n, uncut).astype(jnp.float32) for n in leaves}
    x = jax.random.normal(jax.random.key(6), (1, 48, TOY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = REFERENCE.layer(lp, x, uncut)
        shared_only = None
        parts = 0
        for rank in range(2):
            cfg = BUILDER.model_config(dict(TOY32, deployment={"rank": rank}))
            assert cfg.routed.span == (4 * rank, 4) and cfg.routed.shared_dim == 0
            share = BUILDER._nested(lp)
            for name in ("w_up", "w_down"):
                share["moe"][name] = share["moe"][name][4 * rank : 4 * rank + 4]
            y, stats = ssm_attn_moe._block(cfg, None, "E", x, share)
            assert int(stats["dropped"]) == 0
            # the block is x + routed W_out + shared(n): what every chip computes alike
            # is x and the shared expert, which a share with no held assignment gives alone
            if shared_only is None:
                nothing = dict(share, moe=dict(share["moe"], w_up=0 * share["moe"]["w_up"]))
                shared_only, _ = ssm_attn_moe._block(cfg, None, "E", x, nothing)
            parts = parts + (y - shared_only)
        total = shared_only + parts
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=3e-5)
    assert float(jnp.max(jnp.abs(parts))) > 1e-2  # and the routed parts are not nothing


# --- the counts -----------------------------------------------------------------


def test_weights_a_token_passes_through_by_hand():
    # in 4096 x (8192 + 10240 + 128), four taps a channel of 10240, out 8192 x 4096
    mamba = 4096 * 18560 + 4 * 10240 + 8192 * 4096
    assert FLOPS.block_weights(CONFIG, "M") == mamba == 109_617_152
    # q and o 4096 x 4096, k and v 4096 x 256 (2 heads of 128)
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256
    assert FLOPS.block_weights(CONFIG, "*") == attention == 35_651_584
    assert FLOPS.routed_tokens_share(CONFIG) == 22 * 8 / 512 == 0.34375
    # router 4096 x 512, two latent projections, the shared expert's two matrices,
    # and 0.34 held experts of two matrices 1024 x 2688 in expectation
    experts = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 2 * 1024 * 2688 * 0.34375
    assert FLOPS.block_weights(CONFIG, "E") == experts == 56_418_304.0
    head = 4096 * 16384
    assert FLOPS.matmul_weights(CONFIG) == 5 * mamba + attention + 5 * experts + head == 932_937_728.0


def test_flops_a_step_are_the_programs_own_count_and_near_issue_41s():
    example = FLOPS.per_example(CONFIG, TRAFFIC)
    scan = 5 * 3 * 4 * 128 * 64 * 128 * 8192  # the recurrence, forward and twice backward
    scores = 3 * 8192 * 8192 * 32 * (128 + 128)  # the one attention block
    assert example == 6.0 * FLOPS.matmul_weights(CONFIG) * 8192 + scan + scores
    # ISSUE 41 reckoned 61 TFLOP a step with the rematerialised forward at 16 held: 48.5 model
    # FLOPs; at 8 held (0.34 assignments a token and block, not 0.69) it is 48.0
    assert example == pytest.approx(48.0e12, rel=2e-3)
    from deeplearning_cfn_tpu.models import ssm_attn_moe

    model = BUILDER.model_config(CONFIG)
    assert ssm_attn_moe.train_flops_per_token(model, 8192) * 8192 == pytest.approx(example, rel=1e-12)
    assert ssm_attn_moe.param_count(model) == 1_210_931_584
    assert model.runs == (("EM", 5), ("*", 1)) and model.held_experts == (0, 8)


def test_the_scans_and_the_latent_experts_bytes_and_flops_by_hand():
    # 8192 tokens, 128 heads of 64, 8 groups, a state of 128, bfloat16
    x, bc, dt = 128 * 64, 2 * 8 * 128, 128
    assert SSD.bytes_moved(8192, 128, 64, 8, 128, 1, 0) == 8192 * 2 * (2 * x + bc + dt) == 304_087_040
    assert SSD.bytes_moved(8192, 128, 64, 8, 128, 0, 1) == 8192 * 2 * (3 * x + 2 * bc + 2 * dt)
    assert SSD.flops(8192, 128, 64, 128, 1, 0) == 4 * 128 * 64 * 128 * 8192 == 34_359_738_368
    assert SSD.flops(8192, 128, 64, 128, 3, 1) == 5 * SSD.flops(8192, 128, 64, 128, 1, 0)
    # memory-bound: 304 MB over 819 GB/s is 0.37 ms, 34 GFLOP over 197 TFLOP/s 0.17
    assert SSD.bytes_moved(8192, 128, 64, 8, 128, 1, 0) / PEAKS["hbm_bytes_per_s"] > (
        SSD.flops(8192, 128, 64, 128, 1, 0) / PEAKS["bf16_flops_per_s"])
    # 14,080 assignments (five blocks' expectation), two matmuls of 1024 x 2688 a row
    assert LATENT.flops(14080, 1024, 2688, 1, 0) == 4 * 1024 * 2688 * 14080
    assert LATENT.flops(14080, 1024, 2688, 2, 1) == 4 * LATENT.flops(14080, 1024, 2688, 1, 0)
    weights = 2 * 40 * 1024 * 2688 * 2
    assert LATENT.bytes_moved(14080, 40, 1024, 2688, 1, 0) == weights + 2 * 14080 * 1024 * 2
    assert LATENT.bytes_moved(14080, 40, 1024, 2688, 0, 1) == 2 * weights + 3 * 14080 * 1024 * 2
    # where `moe_experts_roofline_share` would count six times the work: three matmuls of 4096 x 2688
    accepted = MANIFEST.module("flops", "moe_experts")
    assert accepted.flops(14080, 4096, 2688, 1, 0) == 6 * LATENT.flops(14080, 1024, 2688, 1, 0)


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
FWD = STEP + "while/body/checkpoint/"
BACK = STEP + "transpose(jvp(while))/body/checkpoint/"
REMAT = BACK + "rematted_computation/"
OPS = {
    "fusion.1": (FWD + "ssm_norm/mul", 1_000_000),
    "fusion.2": (FWD + "ssm/in_proj/dot_general", 10_000_000),
    "fusion.3": (FWD + "ssm/conv/checkpoint/mul", 2_000_000),
    "fusion.4": (FWD + "ssm/scan/while/body/checkpoint/within/dot_general", 6_000_000),
    "fusion.5": (REMAT + "ssm/scan/while/body/checkpoint/within/dot_general", 6_000_000),
    "fusion.6": (REMAT + "ssm/scan/transpose(jvp(while))/body/checkpoint/rematted_computation/within/exp", 5_000_000),
    "fusion.7": (BACK + "ssm/scan/transpose(jvp(while))/body/checkpoint/within/dot_general", 13_000_000),
    "fusion.8": (BACK + "ssm/gate_norm/checkpoint/rematted_computation/mul", 1_500_000),
    "fusion.9": (BACK + "ssm/out_proj/dot_general", 9_000_000),
    "fusion.10": (FWD + "moe_norm/mul", 500_000),
    "fusion.11": (FWD + "moe/latent_in/dot_general", 2_000_000),
    "fusion.12": (FWD + "moe/experts/jit(gmm)/pallas_call", 4_000_000),
    "fusion.13": (REMAT + "moe/experts/jit(gmm)/pallas_call", 4_000_000),
    "fusion.14": (BACK + "moe/experts/jit(tgmm)/pallas_call", 12_000_000),
    "fusion.15": (BACK + "moe/latent_out/dot_general", 3_000_000),
    "fusion.16": (FWD + "attn/qkv/dot_general", 1_000_000),
    "fusion.17": ("jit(train_step)/optimizer/add", 7_000_000),
}


def test_ssm_mixer_time_is_per_program_and_its_scopes_go_to_the_notes():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "ssm_mixer_ms_per_step")
    assert reader.read(run) == pytest.approx(10 + 2 + 6 + 6 + 5 + 13 + 1.5 + 9)  # not ssm_norm
    assert run["notes"]["ssm_scope_ms_per_step"] == pytest.approx({
        "ssm/in_proj": 10.0, "ssm/conv": 2.0, "ssm/scan": 30.0, "ssm/gate_norm": 1.5,
        "ssm/out_proj": 9.0, "attn/qkv": 1.0, "moe/latent_in": 2.0, "moe/latent_out": 3.0,
        "loss/ssm_norm": 1.0, "loss/moe_norm": 0.5,
    })


def test_ssm_scan_roofline_share_counts_the_passes_its_events_hold():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "ssm_scan_roofline_share")
    # five M blocks; the forward pass, the block's rematerialised one and a tile's inside it,
    # one backward: bytes over the HBM peak, against 30 ms measured
    one = 8192 * 2
    least = 5 * (3 * (2 * 8192 + 2048 + 128) + (3 * 8192 + 2 * 2048 + 2 * 128)) * one / PEAKS["hbm_bytes_per_s"]
    assert reader.read(run) == pytest.approx(100 * 1e3 * least / 30.0, rel=1e-9)
    note = run["notes"]["ssm_scan_roofline"]
    assert note["bound"] == "memory" and note["forward_passes"] == 3 and note["ssm_blocks"] == 5
    # nothing rematerialised: one forward pass is counted
    plain = traced_run({k: v for k, v in OPS.items() if "rematted" not in v[0]})
    assert 0.0 < reader.read(plain) < reader.read(run) < 100.0
    assert plain["notes"]["ssm_scan_roofline"]["forward_passes"] == 1


def test_latent_experts_roofline_share_reads_the_counted_assignments(monkeypatch):
    from deeplearning_cfn_tpu.obs import tracing

    counted = {"moe.assignments": 900_000.0, "moe.assignments_held": 14_080.0,
               "moe.expert_load_max": 500.0, "moe.expert_load_mean": 352.0, "moe.dropped": 0.0}
    monkeypatch.setattr(
        tracing, "counters", lambda: {k: {"count": 3, "total": 3 * v} for k, v in counted.items()})
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "latent_experts_roofline_share")
    # forward, the rematerialised forward and one backward of two matmuls 1024 x 2688 a row
    compute = 4 * 4 * 1024 * 2688 * 14080 / PEAKS["bf16_flops_per_s"]
    weights = 2 * 40 * 1024 * 2688 * 2
    memory = (2 * (weights + 2 * 14080 * 2048) + 2 * weights + 3 * 14080 * 2048) / PEAKS["hbm_bytes_per_s"]
    # so thin a share (352 rows an expert) multiplies hardly longer than it reads its weights
    assert memory < compute < 1.5 * memory
    assert reader.read(run) == pytest.approx(100 * 1e3 * compute / 20.0, rel=1e-9)
    note = run["notes"]["latent_experts_roofline"]
    assert note["bound"] == "compute" and note["forward_passes"] == 2
    assert note["assignments_held_per_step"] == 14080.0 and reader.read(run) < 100.0
    # a configuration whose experts are not latent ones has nothing for this reader
    assert reader.read(dict(traced_run(OPS), config=MANIFEST.config("glm-4.7-flash"))) is None


def test_a_program_without_the_scopes_gives_nothing_and_raises_nothing(monkeypatch):
    """The parent of this PR with this PR's readers laid over it, on the cells
    it has; a traced run with no device plane; and a configuration without `M`
    blocks, whose scan's least time nothing can count."""
    from deeplearning_cfn_tpu.obs import tracing

    monkeypatch.setattr(tracing, "counters", lambda: {})
    readers = [MANIFEST.module("layer_metrics", name) for name in
               ("ssm_mixer_ms_per_step", "ssm_scan_roofline_share", "latent_experts_roofline_share")]
    old = {k: (v[0].replace("ssm/", "mixer/").replace("moe/", "ffn/"), v[1]) for k, v in OPS.items()}
    glm = MANIFEST.config("glm-4.7-flash")
    no_device_plane = {"trace_rows": [], "trace": {"per_device": []}, "config": CONFIG,
                       "traffic": TRAFFIC, "manifest": MANIFEST}  # a traced run on the CPU
    for run in (traced_run(old), dict(traced_run(old), config=glm), no_device_plane,
                {"config": CONFIG, "traffic": TRAFFIC, "manifest": MANIFEST}):
        assert [reader.read(run) for reader in readers] == [None, None, None]
    assert readers[1].read(dict(traced_run(OPS), config=glm)) is None


# --- the manifest's new entries ---------------------------------------------------


def test_configuration_file_holds_every_published_key_and_the_cut():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096, "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E", "n_group": 1,
        "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_hidden_layers": 88, "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 5,
        "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
    }
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEM*EMEMEMEME")
    published["hybrid_override_pattern"] = pattern
    if catalog.is_file():  # the catalog's row, where the guide is installed
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)
        assert row["config"] == published and row["source_url"] == CONFIG["source"]
    reduced = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers"]
    assert CONFIG["reduced"] == reduced
    for key, value in published.items():
        if key in reduced:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    # the cut: published blocks 26-36, the period between two attention blocks
    assert CONFIG["hybrid_override_pattern"] == pattern[26:37] == "EMEMEMEMEM*"
    assert CONFIG["num_hidden_layers"] == 11 and [pattern[i : i + 11] for i in (26, 37, 48, 59)] == ["EMEMEMEMEM*"] * 4
    assert CONFIG["n_routed_experts"] == 8 >= 8 and CONFIG["vocab_size"] * 8 == 131072
    assert CONFIG["num_nextn_predict_layers"] == 0
    assert CONFIG["deployment"]["chips_per_layer"] * CONFIG["n_routed_experts"] == 512
    assert CONFIG["deployment"]["rank"] == 0 and "19.99 GB" in CONFIG["deployment"]["layout"]
    for key in ("rotary", "latent_experts", "router", "mamba", "multi_token_prediction",
                "seeded_weights", "optimizer", "remat_policy", "torch_dtype"):
        assert key in CONFIG["assumed"]
    # no width is cut, in the file or in a nested group
    for key in ("hidden_size", "head_dim", "mamba_head_dim", "mamba_num_heads", "ssm_state_size",
                "moe_latent_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                "num_experts_per_tok", "expand", "intermediate_size"):
        assert key not in reduced and CONFIG[key] == published[key]
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == "nemotron-3-super-120b-a12b")
    assert CONFIG["source"] == entry["source"] and entry["reduced"] == reduced
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert CONFIG["kind"] == "ssm_attn_moe" and BUILDER.model_config(CONFIG).n_layers == 11


def test_the_cell_and_its_metrics_by_name_and_by_containment():
    """Never by position: the next appended cell or metric supersedes nothing."""
    data = MANIFEST.data
    cells = {w["name"]: w for w in data["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "nemotron-3-super-120b-a12b", "traffic": "train-s8192x1",
        "chips": 1, "why": cells[CELL]["why"],
    }
    assert len(cells[CELL]["why"]) <= 200 and "8 of 512" in cells[CELL]["why"]
    assert {"resnet50.train-b128", "resnet50.train-dp4", *OTHER_DECODER_CELLS, CELL} <= set(cells)
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    assert TRAFFIC == {"kind": "train", "input": "tokens", "seq_len": 8192, "global_batch": 1,
                       "pool_batches": 4, "log_every": 2, "warm_seconds": 2.0, "check_steps": 2,
                       "trace_seconds": 3.5}
    assert MANIFEST.json("traffic", "train-s8192")["global_batch"] == 2  # the pinned file, untouched
    metrics = {m["name"]: m for m in data["per_layer"]}
    names = [m["name"] for m in data["per_layer"]]
    new = ["ssm_mixer_ms_per_step", "ssm_scan_roofline_share", "latent_experts_roofline_share"]
    # appended after the newest accepted metric, in this order
    positions = [names.index(n) for n in ["window_gc_ms_per_step"] + new]
    assert positions == sorted(positions)
    for name in new:
        assert CELL in metrics[name]["workloads"]
        assert not set(OTHER_DECODER_CELLS) & set(metrics[name]["workloads"])
        assert metrics[name]["moves"] == "train_throughput" and metrics[name]["source"] == "device_trace"
        assert set(metrics[name]) == set(metrics["short_conv_roofline_share"])
        assert MANIFEST.find("layer_metrics", f"{name}.py").is_file()
    assert [metrics[n]["layer"] for n in new] == ["trainer", "kernels", "kernels"]
    assert [metrics[n]["unit"] for n in new] == ["ms", "%", "%"]
    assert [metrics[n]["better"] for n in new] == ["lower", "higher", "higher"]
    shared = {"attention_roofline_share", "attention_backward_ms_per_step",
              "attention_backward_roofline_share", "recompute_ms_per_step", "moe_ms_per_step",
              "moe_dispatch_ms_per_step", "moe_load_max_over_mean"}
    for name in shared:
        assert CELL in metrics[name]["workloads"]
        assert set(metrics[name]["workloads"]) >= {"glm-4.7-flash.train-s8192", "laguna-xs.2.train-s8192"}
    # three matmuls of hidden_size x moe_intermediate_size a row is six times this model's work
    assert CELL not in metrics["moe_experts_roofline_share"]["workloads"]
    reported = {m["name"] for m in MANIFEST.per_layer_for(CELL)}
    assert set(new) | shared | {"mfu", "device_scope_coverage", "device_idle_share"} <= reported
    assert not {"moe_experts_roofline_share", "collective_exposed_ms_per_step", "conv_mixer_ms_per_step",
                "short_conv_roofline_share", "mla_projection_ms_per_step", "mtp_ms_per_step",
                "window_attention_ms_per_step"} & reported
    for cell in ("resnet50.train-b128", *OTHER_DECODER_CELLS):  # no other cell reads the new three
        assert not set(new) & {m["name"] for m in MANIFEST.per_layer_for(cell)}
    # the cell resolves to its files by name
    for folder, name in (("builders", "ssm_attn_moe.py"), ("reference", "ssm_attn_moe.py"),
                         ("flops", "ssm_attn_moe.py"), ("flops", "ssd.py"),
                         ("flops", "latent_experts.py"), ("limits", f"{CELL}.json"),
                         ("traffic", "train-s8192x1.json")):
        assert MANIFEST.find(folder, name).is_file()


def test_every_limit_lies_between_the_sound_runs_largest_and_the_controls_smallest():
    limits = MANIFEST.json("limits", CELL)
    readings = limits["readings"]
    failed = []
    for name in ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"):
        r = readings[name]
        assert r["sound_max"] < limits[name], name
        assert r["seeds"] >= 12 and r["control_seeds"] >= 2
        if r["control_min"] > limits[name]:
            failed.append(name)
    assert failed, "the control has to fail one of the cell's limits"
    assert "PR 41" in readings["origin"]
