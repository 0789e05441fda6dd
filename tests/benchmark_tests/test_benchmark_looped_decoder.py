"""The `looped_decoder` kind (PR 43): the plain reference against
`models/looped_decoder.py` (every pass's logits, the gate, the mixed loss, every
leaf's gradient, one AdamW step through the trainer); the reference's own
block-application-at-a-time gradients against its autodiff; the fp8 control at
toy size; `flops/looped_decoder.py` against `flops/decoder.py` at one pass and
against ISSUE 43's arithmetic; the two readers on made-up rows; the
configuration file against the published `config.json`; the manifest's
appended entries.  A whole run of the kind is
test_benchmark_looped_decoder_run.py."""

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
REFERENCE = MANIFEST.module("reference", "looped_decoder")
BUILDER = MANIFEST.module("builders", "looped_decoder")
FLOPS = MANIFEST.module("flops", "looped_decoder")
CELL = "ouro-2.6b.train-s8192x1"
STANDING_CELLS = (
    "resnet50.train-b128", "mistral-7b-v0.3.train-s4096", "resnet50.train-dp4",
    "glm-4.7-flash.train-s8192", "lfm2-8b-a1b.train-s8192", "laguna-xs.2.train-s8192",
    "nemotron-3-super-120b-a12b.train-s8192x1",
)
CONFIG = MANIFEST.config("ouro-2.6b")
TRAFFIC = MANIFEST.json("traffic", "train-s8192x1")
PEAKS = bench_run.load_peaks()["TPU v5 lite"]
P0 = "/device:TPU:0"
TOY = json.loads((REPO / "tests/benchmark_tests/configs/looped-decoder-toy.json").read_text())
# The same structure in float32, where program and reference agree closely.
TOY32 = dict(TOY, torch_dtype="float32")
TOY_TRAFFIC = {"kind": "train", "input": "tokens", "seq_len": 20, "global_batch": 8,
               "pool_batches": 2, "log_every": 2, "warm_seconds": 0, "check_steps": 1,
               "trace_seconds": 1}
NUMBERS = ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap")


def toy_batch(seed=0):
    # eight sequences: the tests' mesh has eight devices and the builder uses them all
    x = np.random.default_rng(seed).integers(0, TOY["vocab_size"], (8, 20), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


@pytest.fixture(scope="module", autouse=True)
def leave_no_counters():
    """`fit` folds the `loop.*` counters into the process's aggregates, and a
    later run in this worker reads them."""
    yield
    from deeplearning_cfn_tpu.obs import tracing

    tracing.reset_aggregates()


@pytest.fixture(scope="module")
def built():
    x, _ = toy_batch()
    return BUILDER.build(TOY32, TOY_TRAFFIC, jax.random.key(3), x, REFERENCE)


def test_reference_agrees_with_the_model_on_every_passs_logits_the_loss_and_every_gradient(built):
    """float32 on both sides, so what is left is the order of sums: each of the
    four passes' logits to 5e-5 of values of a few units, the gate's three
    logits that are read, the mixed loss to 1e-6, each leaf's gradient to 5e-5
    of its largest element; and the reference's own gradients a block
    application at a time, a layer's summed over its four, are its autodiff's."""
    from deeplearning_cfn_tpu.models import looped_decoder

    key = jax.random.key(3)
    cfg = BUILDER.model_config(TOY32)
    assert cfg.passes == 4 and cfg.exit_beta == 0.05 and cfg.decoder.n_layers == 2
    assert cfg.decoder.n_heads == cfg.decoder.n_kv_heads == 4  # a group of one, as the model's
    x, y = (jnp.asarray(a) for a in toy_batch())
    params = built.state.params
    with jax.default_matmul_precision("highest"):
        seeded = jax.jit(lambda k: REFERENCE.init_params(k, TOY32))(key)
        ours = jax.jit(lambda p: looped_decoder.logits(cfg, p, x))(params)
        theirs = jax.jit(lambda p: REFERENCE.forward(p, x, y, TOY32))(seeded)
        assert ours["logits"].shape == theirs["logits"].shape == (4, 8, 20, 128)
        np.testing.assert_allclose(
            np.asarray(ours["logits"]), np.asarray(theirs["logits"]), atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(
            np.asarray(ours["gate"][:3]), np.asarray(theirs["gate"][:3]), atol=5e-5, rtol=5e-5)
        # the passes differ: the loop is not the same pass four times
        assert float(jnp.max(jnp.abs(theirs["logits"][3] - theirs["logits"][0]))) > 0.1
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            lambda p: looped_decoder.lm_loss(cfg, p, x, y), has_aux=True))(params)
        assert float(loss) == pytest.approx(float(theirs["loss"]), rel=1e-6)
        got = built.to_reference(grads)
        want = jax.jit(jax.grad(lambda p: REFERENCE.loss(p, x, y, TOY32)))(seeded)
        pieces = REFERENCE._pieces(json.dumps(TOY32, sort_keys=True), "float32")
        value, by_application = pieces.gradients(
            lambda n, i: seeded[REFERENCE.leaf_name(n, i)], x, y)
    # five leaves outside the stack, eleven a layer
    assert set(got) == set(want) == set(seeded) and len(got) == 5 + 2 * 11
    assert float(value) == pytest.approx(float(theirs["loss"]), rel=1e-6)
    for name in got:
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 5e-5 * scale + 1e-9, name
    for (n, i), g in by_application.items():
        name = REFERENCE.leaf_name(n, i)
        scale = float(jnp.max(jnp.abs(want[name])))
        assert g.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(g - want[name]))) <= 5e-5 * scale + 1e-9, name
    # the program's counters are the reference's means: each pass's loss, where the gate puts its mass
    mask = np.ones((8, 20), np.float32)
    mask[:, -1] = 0
    for t in range(4):
        assert float(metrics["counters"][f"loop.loss.{t + 1}"]) == pytest.approx(
            float(np.sum(np.asarray(theirs["nll"][t]) * mask) / mask.sum()), rel=1e-5)
        assert float(metrics["counters"][f"loop.exit_mass.{t + 1}"]) == pytest.approx(
            float(np.sum(np.asarray(theirs["p"][t]) * mask) / mask.sum()), rel=1e-5)
    # the seeded gate: lambda near a half on average, so every pass's loss carries weight
    mass = [float(metrics["counters"][f"loop.exit_mass.{t}"]) for t in (1, 2, 3, 4)]
    assert 0.3 < mass[0] < 0.7 and min(mass) > 0.05
    assert np.all(np.asarray(seeded["layers/1/mlp_post_norm"]) == 1.0)
    assert float(seeded["exit_gate_b"]) == 0.0 and seeded["exit_gate_w"].dtype == jnp.float32


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
    rows = check.compare({"loss": losses, **probe.readings()}, followed, dict.fromkeys(NUMBERS, 1e-3))
    assert all(r["ok"] for r in rows), rows
    assert followed["head_leaves"] == ["output", "final_norm", "exit_gate_w", "exit_gate_b"]
    assert min(followed["update_norm"].values()) > 0  # the gate and every norm moved too
    with pytest.raises(ValueError, match="1 or 2 steps"):
        REFERENCE.follow(key, TOY32, [(x, y)], 3)


def test_the_fp8_control_fails_the_limits_the_sound_program_passes():
    """The toy cell in bfloat16 through `control.CellReader`, two steps
    followed: the sound program inside every toy limit, the reference in fp8
    outside both projections' (`limits/looped-decoder-toy.train-toy-tokens.json`
    has the readings of six seeds)."""
    from benchmarks.control import CellReader

    data = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "looped-decoder-toy.train-toy-tokens"
    data["configs"].append({
        "name": "looped-decoder-toy", "source": "test fixture", "reduced": [], "why": "toy",
        "file": "tests/benchmark_tests/configs/looped-decoder-toy.json"})
    data["workloads"].append({"name": cell, "config": "looped-decoder-toy",
                              "traffic": "train-toy-tokens", "chips": 1, "why": "toy"})
    manifest = Manifest.__new__(Manifest)
    manifest.path, manifest.data = MANIFEST.path, data
    limits = manifest.json("limits", cell)
    row = CellReader(manifest, cell).read(2**31 + 12)
    sound = {r["name"]: r["value"] for r in row["sound"]}
    control = {r["name"]: r["value"] for r in row["control"]}
    assert all(sound[name] <= limits[name] for name in NUMBERS), sound
    for name in ("grad_sketch_gap", "head_sketch_gap"):
        assert control[name] > limits[name] and control[name] > 2.5 * sound[name], control


# --- the counts -----------------------------------------------------------------


def test_flops_at_one_pass_are_the_decoder_kinds_and_at_four_issue_43s():
    decoder = MANIFEST.module("flops", "decoder")
    # one pass of the stack and one head, the gate's 6 d a token apart
    once = dict(CONFIG, total_ut_steps=1)
    gate = 6.0 * 2048 * 8192
    assert FLOPS.per_example(once, TRAFFIC) == decoder.per_example(CONFIG, TRAFFIC) + gate
    assert FLOPS.per_example(CONFIG, TRAFFIC) == 4 * FLOPS.per_pass(CONFIG, TRAFFIC)
    assert 4 * 2048**2 + 3 * 2048 * 5632 == 51_380_224  # a block's matrices
    assert 51_380_224 * CONFIG["num_hidden_layers"] + 2048 * 49152 == decoder.matmul_weights(CONFIG)
    # ISSUE 43's count at the depth it drew: blocks 1.21e14, attention 4.0e13, heads 2.0e13
    drawn = dict(CONFIG, num_hidden_layers=12)
    assert FLOPS.per_example(drawn, TRAFFIC) == pytest.approx(1.81e14, rel=5e-3)
    blocks = 6 * 12 * 51_380_224 * 8192 * 4
    attention = 6 * 8192**2 * 2048 * 48
    heads = 6 * 2048 * 49152 * 8192 * 4
    assert FLOPS.per_example(drawn, TRAFFIC) == blocks + attention + heads + 4 * gate
    assert (blocks, attention, heads) == pytest.approx((1.21e14, 4.0e13, 2.0e13), rel=2e-2)
    # and the program's own count is the benchmark's, at the depth that is held
    from deeplearning_cfn_tpu.models import looped_decoder

    model = BUILDER.model_config(CONFIG)
    assert looped_decoder.train_flops_per_token(model, 8192) * 8192 == pytest.approx(
        FLOPS.per_example(CONFIG, TRAFFIC), rel=1e-12)
    layers = CONFIG["num_hidden_layers"]
    assert looped_decoder.param_count(model) == layers * (51_380_224 + 4 * 2048) + 2 * 2048 * 49152 + 2 * 2048 + 1


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
PASS = STEP + "while/body/loop_pass/while/body/checkpoint/"
PASS_BACK = STEP + "transpose(jvp(while))/body/loop_pass/transpose(jvp(while))/body/checkpoint/"
HEAD = STEP + "while/body/loop_head/checkpoint/"
HEAD_BACK = STEP + "transpose(jvp(while))/body/loop_head/checkpoint/"
OPS = {
    "fusion.1": (STEP + "jvp(embed)/gather", 500_000),
    "fusion.2": (PASS + "attn_norm/mul", 1_000_000),
    "fusion.3": (PASS + "attn/qkv/dot_general", 8_000_000),
    "fusion.4": (PASS + "attn/core/pallas_call", 12_000_000),
    "fusion.5": (PASS + "attn_post_norm/mul", 1_000_000),
    "fusion.6": (PASS + "mlp/dot_general", 20_000_000),
    "fusion.7": (PASS_BACK + "rematted_computation/mlp/dot_general", 20_000_000),
    "fusion.8": (PASS_BACK + "mlp/dot_general", 40_000_000),
    "fusion.9": ("jit(train_step)/attn_bwd/pallas_call", 30_000_000),
    "fusion.10": (HEAD + "final_norm/mul", 500_000),
    "fusion.11": (HEAD + "head/dot_general", 6_000_000),
    "fusion.12": (HEAD + "xent/reduce", 2_000_000),
    "fusion.13": (HEAD + "exit_gate/dot_general", 250_000),
    "fusion.14": (HEAD_BACK + "rematted_computation/head/dot_general", 6_000_000),
    "fusion.15": (HEAD_BACK + "head/dot_general", 12_000_000),
    "fusion.16": (STEP + "jvp(exit_mix)/exp", 100_000),
    "fusion.17": (STEP + "transpose(jvp(exit_mix))/mul", 150_000),
    "fusion.18": ("jit(train_step)/optimizer/add", 7_000_000),
}


def counted(monkeypatch, passes=4.0, steps=3):
    from deeplearning_cfn_tpu.obs import tracing

    monkeypatch.setattr(tracing, "counters", lambda: {
        "loop.passes": {"count": steps, "total": steps * passes},
        "loop.exit_mass.1": {"count": steps, "total": steps * 0.5},
        "moe.dropped": {"count": steps, "total": 0.0}, "loop.never": {"count": 0, "total": 0.0}})


def test_loop_head_time_is_per_program_and_its_parts_go_to_the_notes():
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "loop_head_ms_per_step")
    assert reader.read(run) == pytest.approx(0.5 + 6 + 2 + 0.25 + 6 + 12 + 0.1 + 0.15)
    assert run["notes"]["loop_head_scope_ms_per_step"] == pytest.approx({
        "loop_head/final_norm": 0.5, "loop_head/head": 24.0, "loop_head/xent": 2.0,
        "loop_head/exit_gate": 0.25, "exit_mix": 0.25,
    })


def test_loop_pass_time_is_per_pass_by_the_programs_counter(monkeypatch):
    counted(monkeypatch)
    run = traced_run(OPS)
    reader = MANIFEST.module("layer_metrics", "loop_pass_ms_per_pass")
    step = 1 + 8 + 12 + 1 + 20 + 20 + 40 + 30  # the backward kernels carry `attn_bwd` alone
    assert reader.read(run) == pytest.approx(step / 4)
    note = run["notes"]["loop_pass"]
    assert note.pop("counters") == {"loop.passes": 4.0, "loop.exit_mass.1": 0.5}  # the loop's alone
    assert note == pytest.approx(
        {"passes_per_step": 4.0, "ms_per_step": step, "under_loop_pass_ms_per_step": step - 30})
    counted(monkeypatch, passes=2.0)  # a program that loops twice a step
    assert reader.read(traced_run(OPS)) == pytest.approx(step / 2)


def test_a_program_without_the_scopes_or_the_counter_gives_nothing_and_raises_nothing(monkeypatch):
    """The parent of this PR with this PR's readers laid over it; a one-pass
    decoder, which has `attn_bwd` and `head` and neither new scope; a traced run
    with no device plane; and the scopes without the counter."""
    from deeplearning_cfn_tpu.obs import tracing

    readers = [MANIFEST.module("layer_metrics", name)
               for name in ("loop_head_ms_per_step", "loop_pass_ms_per_pass")]
    plain = {k: (v[0].replace("loop_pass/", "").replace("loop_head/", "").replace("exit_mix", "xent"),
                 v[1]) for k, v in OPS.items()}
    no_device_plane = {"trace_rows": [], "trace": {"per_device": []}, "config": CONFIG,
                       "traffic": TRAFFIC, "manifest": MANIFEST}  # a traced run on the CPU
    counted(monkeypatch)
    for run in (traced_run(plain), no_device_plane,
                {"config": CONFIG, "traffic": TRAFFIC, "manifest": MANIFEST}):
        assert [reader.read(run) for reader in readers] == [None, None]
    monkeypatch.setattr(tracing, "counters", lambda: {})
    assert readers[1].read(traced_run(OPS)) is None and readers[0].read(traced_run(OPS)) is not None


# --- the manifest's new entries ---------------------------------------------------


def test_configuration_file_holds_every_published_key_and_the_cut():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
    }
    if catalog.is_file():  # the catalog's row, where the guide is installed
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if '"Ouro-2.6B"' in line)
        assert row["config"] == published and row["source_url"] == CONFIG["source"]
    reduced = ["num_hidden_layers", "layer_types", "max_window_layers"]
    assert CONFIG["reduced"] == reduced
    for key, value in published.items():
        if key in reduced:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    # the cut: ISSUE 43's rule, 12 layers or 8 and nothing else; the loop and the vocabulary whole
    layers = CONFIG["num_hidden_layers"]
    assert layers == 8 and "16.63 GB" in CONFIG["deployment"]["layout"]
    assert CONFIG["layer_types"] == ["full_attention"] * layers and CONFIG["max_window_layers"] == layers
    assert CONFIG["total_ut_steps"] == 4 and CONFIG["vocab_size"] == 49152
    assert CONFIG["exit_beta"] == 0.05 and CONFIG["torch_dtype"] == "bfloat16"
    for key in ("sandwich_norm", "loop", "exit_gate", "objective", "left_out", "rotary",
                "seeded_weights", "optimizer", "remat_policy", "torch_dtype"):
        assert key in CONFIG["assumed"]
    # no width is cut
    for key in ("hidden_size", "head_dim", "intermediate_size", "num_attention_heads",
                "num_key_value_heads"):
        assert key not in reduced and CONFIG[key] == published[key]
    # the trainer's keys are the Mistral configuration's
    mistral = MANIFEST.config("mistral-7b-v0.3")
    for key in ("learning_rate", "weight_decay", "grad_clip_norm", "adam_b1", "adam_b2", "adam_eps",
                "remat_policy", "use_flash_attention", "torch_dtype"):
        assert CONFIG[key] == mistral[key], key
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == "ouro-2.6b")
    assert CONFIG["source"] == entry["source"] and entry["reduced"] == reduced
    assert entry["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert entry["file"] == "benchmarks/configs/ouro-2.6b.json" and len(entry["why"]) <= 200
    model = BUILDER.model_config(CONFIG)
    assert CONFIG["kind"] == "looped_decoder" and model.decoder.n_layers == layers and model.passes == 4
    assert model.decoder.head_dim == 128 and model.decoder.rope_theta == 1e6 and model.decoder.norm_eps == 1e-6
    with pytest.raises(ValueError, match="plain rotary"):
        BUILDER.model_config(dict(CONFIG, use_sliding_window=True))


def test_the_cell_and_its_metrics_by_name_and_by_containment():
    """Never by position: the next appended cell or metric supersedes nothing."""
    data = MANIFEST.data
    cells = {w["name"]: w for w in data["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "ouro-2.6b", "traffic": "train-s8192x1", "chips": 1,
        "why": cells[CELL]["why"],
    }
    assert len(cells[CELL]["why"]) <= 200 and "8 of 48 layers" in cells[CELL]["why"]
    assert {*STANDING_CELLS, CELL} <= set(cells)
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    assert TRAFFIC == {"kind": "train", "input": "tokens", "seq_len": 8192, "global_batch": 1,
                       "pool_batches": 4, "log_every": 2, "warm_seconds": 2.0, "check_steps": 2,
                       "trace_seconds": 3.5}  # the Nemotron cell's file, as it stands
    metrics = {m["name"]: m for m in data["per_layer"]}
    names = [m["name"] for m in data["per_layer"]]
    new = ["loop_head_ms_per_step", "loop_pass_ms_per_pass"]
    # appended after the newest accepted metric, in this order
    positions = [names.index(n) for n in ["latent_experts_roofline_share"] + new]
    assert positions == sorted(positions)
    for name in new:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name] == {**metrics["ssm_mixer_ms_per_step"], "name": name, "workloads": [CELL]}
        assert MANIFEST.find("layer_metrics", f"{name}.py").is_file()
    shared = {"attention_roofline_share", "attention_backward_ms_per_step",
              "attention_backward_roofline_share", "recompute_ms_per_step"}
    for name in shared:
        assert CELL in metrics[name]["workloads"]
        assert set(metrics[name]["workloads"]) >= {"mistral-7b-v0.3.train-s4096", "glm-4.7-flash.train-s8192"}
    for name, metric in metrics.items():  # and in no other list
        if name not in shared | set(new):
            assert CELL not in metric.get("workloads", []), name
    reported = {m["name"] for m in MANIFEST.per_layer_for(CELL)}
    assert set(new) | shared | {"mfu", "device_scope_coverage", "device_idle_share",
                                "optimizer_ms_per_step"} <= reported
    assert not {"moe_ms_per_step", "moe_experts_roofline_share", "collective_exposed_ms_per_step",
                "ssm_mixer_ms_per_step", "mtp_ms_per_step", "window_attention_ms_per_step"} & reported
    for cell in STANDING_CELLS:  # no other cell reads the new two
        assert not set(new) & {m["name"] for m in MANIFEST.per_layer_for(cell)}
    assert {m["name"] for m in MANIFEST.end_to_end_for(CELL)} == {"train_throughput", "setup_s"}
    # the cell resolves to its files by name
    for folder, name in (("builders", "looped_decoder.py"), ("reference", "looped_decoder.py"),
                         ("flops", "looped_decoder.py"), ("limits", f"{CELL}.json"),
                         ("traffic", "train-s8192x1.json")):
        assert MANIFEST.find(folder, name).is_file()


def test_every_limit_lies_above_the_sound_runs_largest_and_the_control_fails_three():
    limits = MANIFEST.json("limits", CELL)
    readings = limits["readings"]
    failed = []
    for name in NUMBERS:
        r = readings[name]
        assert r["sound_max"] < limits[name], name
        assert r["seeds"] >= 12 and r["control_seeds"] >= 2
        if r["control_min"] > limits[name]:
            failed.append(name)
    # the three the fp8 control moves lie between the two readings, with room on both sides
    assert failed == ["grad_norm_gap", "grad_sketch_gap", "head_sketch_gap"]
    for name in failed:
        r = readings[name]
        assert 1.1 * r["sound_max"] < limits[name] < r["control_min"] / 1.1, name
    # the two it does not move: the accepted cells' loss limit, and the update's norm
    # between the first reading and 1 (a step that returns its state unchanged)
    assert limits["loss_gap"] == MANIFEST.json("limits", "laguna-xs.2.train-s8192")["loss_gap"]
    assert 2 * readings["update_norm_gap"]["sound_max"] < limits["update_norm_gap"] < 0.5
    assert "PR 43" in readings["origin"] and "bfloat16" in readings["why"]
