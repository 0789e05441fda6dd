"""The `mamba_attn` kind (PR 47): one AdamW step of the trainer against the
plain reference's through `Trainer.fit` and the probe (the model against the
reference layer by layer and end to end is tests/test_mamba_attn.py); the
reference's recurrence against the program's scan; `flops/mamba_attn.py` and
`flops/selective_scan.py` against ISSUE 47's arithmetic; each new reader on
made-up rows; the configuration file against the published `config.json`; the
manifest's appended entries.  A whole run of the kind is
test_benchmark_mamba_attn_run.py."""

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
REFERENCE = MANIFEST.module("reference", "mamba_attn")
BUILDER = MANIFEST.module("builders", "mamba_attn")
FLOPS = MANIFEST.module("flops", "mamba_attn")
SCAN = MANIFEST.module("flops", "selective_scan")
CELL = "jamba2-3b.train-s8192x1"
EARLIER_DECODER_CELLS = (
    "mistral-7b-v0.3.train-s4096", "glm-4.7-flash.train-s8192", "lfm2-8b-a1b.train-s8192",
    "laguna-xs.2.train-s8192", "nemotron-3-super-120b-a12b.train-s8192x1", "ouro-2.6b.train-s8192x1")
CONFIG = MANIFEST.config("jamba2-3b")
TRAFFIC = MANIFEST.json("traffic", "train-s8192x1")
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
P0 = "/device:TPU:0"
TOY = json.loads((REPO / "tests/benchmark_tests/configs/mamba-attn-toy.json").read_text())
TOY32 = dict(TOY, torch_dtype="float32")
# Three layers for the test that compiles both sides' programs: the reference's
# projections are a program a leaf's name.
SMALL32 = dict(TOY32, num_hidden_layers=3, attn_layer_period=3, attn_layer_offset=1)
TOY_TRAFFIC = {"kind": "train", "input": "tokens", "seq_len": 20, "global_batch": 2,
               "pool_batches": 2, "log_every": 2, "warm_seconds": 0, "check_steps": 1,
               "trace_seconds": 1}


@pytest.fixture(scope="module", autouse=True)
def leave_no_counters():
    """`fit` folds the `ssm.*` counters into the process's aggregates, and a
    later run in this worker reads them."""
    yield
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()


def test_one_adamw_step_of_the_trainer_is_the_references(monkeypatch):
    """Through `Trainer.fit` and the probe, as a run's check reads it (on one
    of the tests' devices: nothing here exists only across them, and a program
    partitioned eight ways takes several times as long to compile)."""
    from benchmarks import check
    from benchmarks.probe import StateProbe
    from deeplearning_cfn_tpu.obs import tracing
    from deeplearning_cfn_tpu.train.data import Batch

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    key = jax.random.key(3)
    x = np.random.default_rng(0).integers(0, TOY["vocab_size"], (2, 20), dtype=np.int32)
    y = np.roll(x, -1, axis=1)
    assert BUILDER.model_config(SMALL32).runs == (("mamba", 1), ("attention", 1), ("mamba", 1))
    tracing.reset_aggregates()
    with jax.default_matmul_precision("highest"):
        built = BUILDER.build(SMALL32, TOY_TRAFFIC, key, x, REFERENCE)
        probe = StateProbe(built, key, 1)
        _, losses = built.trainer.fit(built.state, iter([Batch(x, y)]), steps=1, checkpointer=probe)
        followed = REFERENCE.follow(key, SMALL32, [(x, y)], 1)
    rows = check.compare({"loss": losses, **probe.readings()}, followed, dict.fromkeys(
        ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"), 1e-3
    ))
    assert all(r["ok"] for r in rows), rows
    # the tied table is the head's matrix: one leaf, compared as one leaf
    assert followed["head_leaves"] == ["embed", "final_norm"] and "output" not in followed["grad_norm"]
    assert len(followed["grad_norm"]) == 2 + 2 * 17 + 9
    counted = tracing.counters()
    assert counted["ssm.dt_mean"]["count"] == counted["ssm.dt_max"]["count"] == 1
    assert 1e-3 < counted["ssm.dt_mean"]["total"] < counted["ssm.dt_max"]["total"]
    # the seeded decays are a trained model's: A[c, n] = n + 1, dt in [1e-3, 1e-1], D one
    seeded = REFERENCE.init_params(key, SMALL32)
    np.testing.assert_allclose(np.exp(seeded["layers/0/A_log"])[5], np.arange(1, 9), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(seeded["layers/2/dt_bias"])))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001 and np.all(seeded["layers/2/D"] == 1.0)


@pytest.mark.parametrize("length", [256, 133], ids=["whole-chunks", "a-ragged-tail"])
def test_the_references_recurrence_is_the_programs_scan(length):
    """The reference's definition against `ops/selective_scan.py`'s, value and
    the gradient of every input."""
    from deeplearning_cfn_tpu.ops.selective_scan import selective_scan

    k = jax.random.split(jax.random.key(length), 7)
    I, N = 12, 5
    args = (
        jax.random.normal(k[0], (length, I)), 0.1 * jax.nn.softplus(jax.random.normal(k[1], (length, I))),
        -jax.random.uniform(k[2], (I, N), minval=1.0, maxval=16.0), jax.random.normal(k[3], (length, N)),
        jax.random.normal(k[4], (length, N)), jax.random.normal(k[5], (I,)),
    )
    dy = jax.random.normal(k[6], (length, I))
    batched = lambda x, dt, A, B, C, D: selective_scan(x[None], dt[None], A, B[None], C[None], D)[0]
    want, pull_want = jax.vjp(REFERENCE.recurrence, *args)
    got, pull_got = jax.vjp(batched, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    for g, w in zip(pull_got(dy), pull_want(dy)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4)


def test_the_references_projections_are_the_probes_to_the_bit():
    """`reference/mamba_attn.py` projects a leaf with its name as a number (one
    program a shape); the probe projects it with `sketch.sketch`."""
    from benchmarks.sketch import sketch

    key = jax.random.key(5)
    for name, shape in (("embed", (96, 32)), ("layers/3/A_log", (64, 8)), ("layers/0/D", (64,))):
        x = jax.random.normal(jax.random.fold_in(key, len(name)), shape)
        assert bool(jnp.all(REFERENCE._sketch(x, name, key) == sketch(x, name, key))), name


# --- the counts -----------------------------------------------------------------


def test_weights_a_token_passes_through_by_hand():
    # in 2560 x 10240, four taps a channel of 5120, x_proj 5120 x 192, dt 160 x 5120, out 5120 x 2560
    mamba = 2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert FLOPS.mixer_weights(CONFIG, "mamba") == mamba == 41_144_320
    # q and o 2560 x 2560, k and v 2560 x 128 (one head of 128)
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert FLOPS.mixer_weights(CONFIG, "attention") == attention == 13_762_560
    mlp, head = 3 * 2560 * 8192, 2560 * 8192
    assert FLOPS.kinds(CONFIG) == ["mamba"] * 7 + ["attention"] + ["mamba"] * 6
    assert FLOPS.matmul_weights(CONFIG) == 13 * mamba + attention + 14 * mlp + head == 1_450_414_080
    # the parameters ISSUE 47 counts, less what no token multiplies (norms, biases, A_log, D)
    assert 13 * 104_161_472 + 76_682_240 == 1_430_781_376
    assert 104_161_472 - (mamba + mlp) == 5120 + 192 + 5120 + 16 * 5120 + 5120 + 2 * 2560


def test_flops_a_step_are_the_programs_own_count_and_near_issue_47s():
    example = FLOPS.per_example(CONFIG, TRAFFIC)
    scan = 13 * 3 * 4 * 16 * 5120 * 8192  # the recurrence, forward and twice backward
    scores = 3 * 8192 * 8192 * 20 * (128 + 128)  # the one attention layer
    assert example == 6.0 * FLOPS.matmul_weights(CONFIG) * 8192 + scan + scores
    # ISSUE 47 reckoned 72 TFLOP a step at 16,384 rows (6 x 1.47 G x 8192); at 8,192 rows it is 72.4
    # with the scan and the scores
    assert example == pytest.approx(72.4e12, rel=2e-3)
    from deeplearning_cfn_tpu.models import mamba_attn

    model = BUILDER.model_config(CONFIG)
    assert mamba_attn.train_flops_per_token(model, 8192) * 8192 == pytest.approx(example, rel=1e-12)
    assert mamba_attn.param_count(model) == 1_430_781_376 + 8192 * 2560 + 2560
    assert model.runs == (("mamba", 7), ("attention", 1), ("mamba", 6)) and model.remat


def test_the_selective_scans_bytes_and_flops_by_hand():
    # 8192 tokens, 5120 channels, a state of 16, bfloat16 x, B, C and y, float32 dt
    read = (5120 + 32) * 2 + 5120 * 4
    assert SCAN.bytes_moved(8192, 5120, 16, 1, 0) == 8192 * (read + 5120 * 2) == 336_068_608
    assert SCAN.bytes_moved(8192, 5120, 16, 0, 1) == 8192 * (2 * read + 5120 * 2)
    assert SCAN.flops(8192, 5120, 16, 1, 0) == 4 * 16 * 5120 * 8192 == 2_684_354_560
    assert SCAN.flops(8192, 5120, 16, 2, 1) == 4 * SCAN.flops(8192, 5120, 16, 1, 0)
    # memory-bound by far: 336 MB over 819 GB/s is 0.41 ms, 2.7 GFLOP over 197 TFLOP/s 0.014
    assert SCAN.bytes_moved(8192, 5120, 16, 1, 0) / PEAKS["hbm_bytes_per_s"] > 20 * (
        SCAN.flops(8192, 5120, 16, 1, 0) / PEAKS["bf16_flops_per_s"])


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
    "fusion.3": (FWD + "ssm/conv/pallas_call", 2_000_000),
    "fusion.4": (FWD + "ssm/x_proj/dot_general", 1_000_000),
    "fusion.5": (FWD + "ssm/dt_proj/dot_general", 1_500_000),
    "_selective_scan_forward.1": (FWD + "ssm/scan/jit(_forward)/pallas_call", 6_000_000),
    "_selective_scan_forward.2": (REMAT + "ssm/scan/jit(_forward)/pallas_call", 6_500_000),
    "_selective_scan_backward.1": (BACK + "ssm/scan/jit(_backward)/pallas_call", 20_000_000),
    "fusion.6": (BACK + "ssm/scan/jit(_backward)/reduce_sum", 500_000),
    "fusion.7": (BACK + "ssm/gate/checkpoint/rematted_computation/mul", 1_500_000),
    "fusion.8": (BACK + "ssm/out_proj/dot_general", 9_000_000),
    "fusion.9": (FWD + "attn/qkv/dot_general", 1_000_000),
    "fusion.10": (FWD + "mlp/dot_general", 30_000_000),
    "fusion.11": ("jit(train_step)/optimizer/add", 7_000_000),
}


def test_selective_scan_time_is_per_program_and_its_scopes_go_to_the_notes():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "selective_scan_ms_per_step")
    assert reader.read(run) == pytest.approx(6 + 6.5 + 20 + 0.5)
    notes = run["notes"]["selective_scan_scope_ms_per_step"]
    kernels = {k: notes.pop(k) for k in ("_selective_scan_forward", "_selective_scan_backward")}
    assert notes == pytest.approx({
        "ssm/in_proj": 10.0, "ssm/conv": 2.0, "ssm/x_proj": 1.0, "ssm/dt_proj": 1.5, "ssm/scan": 33.0,
        "ssm/gate": 1.5, "ssm/out_proj": 9.0, "attn/qkv": 1.0, "loss/ssm_norm": 1.0,
    })
    assert kernels["_selective_scan_forward"] == {"calls": 4, "ms_per_call": pytest.approx(6.25)}
    assert kernels["_selective_scan_backward"] == {"calls": 2, "ms_per_call": pytest.approx(20.0)}
    # the accepted reader of the scope `ssm` whole finds this kind's scopes too
    assert MANIFEST.module("layer_metrics", "ssm_mixer_ms_per_step").read(traced_run(OPS)) == pytest.approx(
        10 + 2 + 1 + 1.5 + 33 + 1.5 + 9)


def test_selective_scan_roofline_share_counts_the_passes_its_events_hold():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "selective_scan_roofline_share")
    # thirteen Mamba layers; the forward pass, the layer's rematerialised one, one backward:
    # bytes over the HBM peak, against 33 ms measured
    read = (5120 + 32) * 2 + 5120 * 4
    least = 13 * 8192 * (2 * (read + 10240) + (2 * read + 10240)) / PEAKS["hbm_bytes_per_s"]
    assert reader.read(run) == pytest.approx(100 * 1e3 * least / 33.0, rel=1e-9)
    note = run["notes"]["selective_scan_roofline"]
    assert note["bound"] == "memory" and note["forward_passes"] == 2 and note["mamba_layers"] == 13
    plain = traced_run({k: v for k, v in OPS.items() if "rematted" not in v[0]})
    assert 0.0 < reader.read(plain) < 100.0
    assert plain["notes"]["selective_scan_roofline"]["forward_passes"] == 1


def test_a_program_without_the_scopes_gives_nothing_and_raises_nothing():
    """The parent of this PR with this PR's readers laid over it, on the cells
    it has; a traced run with no device plane; an untraced run."""
    readers = [MANIFEST.module("layer_metrics", name) for name in
               ("selective_scan_ms_per_step", "selective_scan_roofline_share")]
    old = {k: (v[0].replace("ssm/", "mixer/"), v[1]) for k, v in OPS.items()}
    nemotron = MANIFEST.config("nemotron-3-super-120b-a12b")
    no_device_plane = {"trace_rows": [], "trace": {"per_device": []}, "config": CONFIG,
                       "traffic": TRAFFIC, "manifest": MANIFEST}  # a traced run on the CPU
    for run in (traced_run(old), dict(traced_run(OPS), config=nemotron), no_device_plane,
                {"config": CONFIG, "traffic": TRAFFIC, "manifest": MANIFEST}):
        assert [reader.read(run) for reader in readers] == [None, None]


# --- the manifest's new entries ---------------------------------------------------


def test_configuration_file_holds_every_published_key_and_the_cut():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
        "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536,
    }
    if catalog.is_file():  # the catalog's row, where the guide is installed
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if '"AI21-Jamba2-3B"' in line)
        assert row["config"] == published and row["source_url"] == CONFIG["source"]
    reduced = ["num_hidden_layers", "vocab_size"]
    assert CONFIG["reduced"] == reduced and CONFIG["published"] == {k: published[k] for k in reduced}
    for key, value in published.items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    # the cut: one whole period, published layers 0-13, and an eighth of the table (both floors)
    assert CONFIG["num_hidden_layers"] == 14 == CONFIG["attn_layer_period"]
    assert CONFIG["vocab_size"] * 8 == 65536 and CONFIG["deployment"]["vocab_rows"] == [0, 8192]
    assert CONFIG["deployment"]["chips_per_layer"] == 8 and CONFIG["deployment"]["rank"] == 0
    layout = CONFIG["deployment"]["layout"]
    for compiled in ("17.62", "17.12", "16.79", "16.62", "15.9 GB"):
        assert compiled in layout, compiled
    assert CONFIG["head_dim"] * CONFIG["num_attention_heads"] == CONFIG["hidden_size"]
    for key in ("layer_order", "head_dim", "positions", "mamba", "seeded_weights", "optimizer",
                "remat_policy", "torch_dtype", "layout", "sequence", "left_out"):
        assert key in CONFIG["assumed"]
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == "jamba2-3b")
    assert CONFIG["source"] == entry["source"] and entry["reduced"] == reduced
    assert entry["source"] == "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    assert CONFIG["kind"] == "mamba_attn" and BUILDER.model_config(CONFIG).n_layers == 14


def test_the_cell_and_its_metrics_by_name_and_by_containment():
    """Never by position: the next appended cell or metric supersedes nothing."""
    data = MANIFEST.data
    cells = {w["name"]: w for w in data["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "jamba2-3b", "traffic": "train-s8192x1", "chips": 1,
        "why": cells[CELL]["why"],
    }
    assert len(cells[CELL]["why"]) <= 200 and "8192 of 65536" in cells[CELL]["why"]
    assert {"resnet50.train-b128", "resnet50.train-dp4", *EARLIER_DECODER_CELLS, CELL} <= set(cells)
    assert len(cells) >= 9 and sum(w["chips"] == 4 for w in data["workloads"]) == 1
    assert TRAFFIC["global_batch"] == 1 and TRAFFIC["seq_len"] == 8192  # the pinned file, untouched
    metrics = {m["name"]: m for m in data["per_layer"]}
    names = [m["name"] for m in data["per_layer"]]
    new = ["selective_scan_ms_per_step", "selective_scan_roofline_share"]
    positions = [names.index(n) for n in ["loop_pass_ms_per_pass"] + new]
    assert positions == sorted(positions)  # appended after the newest accepted metric
    for name in new:
        assert metrics[name]["workloads"][0] == CELL
        assert not set(EARLIER_DECODER_CELLS) & set(metrics[name]["workloads"])
        assert metrics[name] == {
            "name": name, "unit": metrics[name]["unit"], "better": metrics[name]["better"],
            "source": "device_trace", "layer": "kernels", "moves": "train_throughput",
            "workloads": metrics[name]["workloads"]}
        assert MANIFEST.find("layer_metrics", f"{name}.py").is_file()
    assert [(metrics[n]["unit"], metrics[n]["better"]) for n in new] == [("ms", "lower"), ("%", "higher")]
    shared = {"ssm_mixer_ms_per_step", "attention_roofline_share", "attention_backward_roofline_share",
              "attention_backward_ms_per_step", "recompute_ms_per_step"}
    for name in shared:
        assert CELL in metrics[name]["workloads"]
        assert "nemotron-3-super-120b-a12b.train-s8192x1" in metrics[name]["workloads"]
    reported = {m["name"] for m in MANIFEST.per_layer_for(CELL)}
    assert set(new) | shared | {"mfu", "device_scope_coverage", "device_idle_share"} <= reported
    assert not {"ssm_scan_roofline_share", "latent_experts_roofline_share", "moe_ms_per_step",
                "moe_experts_roofline_share", "collective_exposed_ms_per_step", "loop_head_ms_per_step",
                "conv_mixer_ms_per_step", "window_attention_ms_per_step"} & reported
    for cell in ("resnet50.train-b128", *EARLIER_DECODER_CELLS):  # no other cell reads the new two
        assert not set(new) & {m["name"] for m in MANIFEST.per_layer_for(cell)}
    for folder, name in (("builders", "mamba_attn.py"), ("reference", "mamba_attn.py"),
                         ("flops", "mamba_attn.py"), ("flops", "selective_scan.py"),
                         ("limits", f"{CELL}.json"), ("traffic", "train-s8192x1.json")):
        assert MANIFEST.find(folder, name).is_file()


def test_every_limit_lies_between_the_sound_runs_largest_and_the_controls_smallest():
    limits = MANIFEST.json("limits", CELL)
    readings = limits["readings"]
    failed = []
    for name in ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap"):
        r = readings[name]
        assert r["sound_max"] < limits[name], name
        assert r["control_seeds"] >= 2 and r["seeds"] >= (7 if name == "update_norm_gap" else 12)
        if r["control_min"] > limits[name]:
            failed.append(name)
    assert set(failed) >= {"grad_sketch_gap", "head_sketch_gap"}, "the control has to fail one of the cell's limits"
    # the update's norm: between the first reading and 1, what an unchanged state reads
    assert readings["update_norm_gap"]["sound_max"] < limits["update_norm_gap"] < 1.0
    assert "PR 47" in readings["origin"] and len(readings["why"]) > 500
