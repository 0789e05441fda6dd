"""`models/ssm_attn_moe.py` and what it asked of `ops/moe.py`: the pattern's
units, each block kind by hand at a tiny size, both forms of a routed expert
(SwiGLU, relu^2 with rows of their own) against a dense loop over experts, and
a step of the trainer.  Model against reference is
tests/benchmark_tests/test_benchmark_ssm_attn_moe.py; the cell's lowered step
is pinned in tests/test_cell_steps.py."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import ssm_attn_moe as model
from deeplearning_cfn_tpu.ops import moe
from deeplearning_cfn_tpu.ops.moe import RoutedConfig, init_routed_params, route, routed_experts

HIGHEST = partial(jax.default_matmul_precision, "highest")


# --- the pattern ------------------------------------------------------------------


@pytest.mark.parametrize(
    "pattern, units",
    [
        ("EMEMEMEMEM*", (("EM", 5), ("*", 1))),
        ("MMM", (("M", 3),)),
        ("M*E", (("M*", 1), ("E", 1))),  # a pair once reaches further than a block once
        ("MEMEM*", (("ME", 2), ("M*", 1))),
        ("EMEM*EM", (("EM", 2), ("*E", 1), ("M", 1))),
    ],
)
def test_the_pattern_parts_into_runs_of_a_repeated_unit(pattern, units):
    assert model.units_of(pattern) == units
    assert "".join(unit * n for unit, n in units) == pattern


def test_the_published_pattern_is_88_blocks_in_the_models_own_ratio_and_few_runs():
    p = model.PUBLISHED_PATTERN
    assert (len(p), p.count("M"), p.count("E"), p.count("*")) == (88, 40, 40, 8)
    assert p[26:37] == "EMEMEMEMEM*" == model.SsmAttnMoeConfig().pattern  # the cell's period
    runs = model.units_of(p)
    assert "".join(unit * n for unit, n in runs) == p and len(runs) == 17  # not 88 bodies
    with pytest.raises(ValueError, match="a block is one of"):
        model.SsmAttnMoeConfig(pattern="EMX")


def test_the_published_sizes_count_what_issue_41_counted():
    cfg = model.SsmAttnMoeConfig(vocab_size=16384, held_experts=(0, 16))
    assert model.param_count(cfg) == 1_431_132_544  # 16 held: ISSUE 41's 1,431 M
    held8 = model.SsmAttnMoeConfig(vocab_size=16384, held_experts=(0, 8))
    assert model.param_count(held8) == 1_210_931_584  # the fallback the cell runs: 1,211 M
    assert cfg.ssm_inner == 8192 and cfg.ssm_conv_dim == 10240
    assert cfg.routed.expert == "relu2" and cfg.routed.shared_dim == 0
    assert held8.routed.buffer_rows(8192) == 65536


# --- each block by hand -------------------------------------------------------------


def tiny_block(block: str, seed: int = 0):
    cfg = model.SsmAttnMoeConfig.tiny()
    lp = model._block_params(cfg, jax.random.key(seed), block)
    x = jax.random.normal(jax.random.key(seed + 1), (2, 12, cfg.dim), jnp.float32)
    return cfg, lp, x


def rms(x, w, eps=1e-5):
    return x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + eps) * w


def test_the_mamba2_block_by_hand_a_token_at_a_time():
    cfg, lp, x = tiny_block("M")
    lp = dict(lp, conv_bias=0.3 * jax.random.normal(jax.random.key(9), lp["conv_bias"].shape),
              D=jax.random.normal(jax.random.key(8), lp["D"].shape),
              gate_norm=1.0 + 0.5 * jax.random.normal(jax.random.key(7), lp["gate_norm"].shape))
    with HIGHEST():
        got, stats = model._block(cfg, None, "M", x, lp)
    assert stats is None
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    H, P, G, N, inner = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_inner
    want = np.zeros(x.shape)
    for b in range(x.shape[0]):
        xs = np.asarray(x[b], np.float64)
        proj = rms(xs, p["norm"]) @ p["in_proj"]
        z, xBC, dt = proj[:, :inner], proj[:, inner : inner + cfg.ssm_conv_dim], proj[:, -H:]
        conv = np.zeros_like(xBC)
        for t in range(len(xs)):
            for j in range(cfg.conv_taps):  # tap j reads conv_taps - 1 - j tokens back
                if t - (cfg.conv_taps - 1 - j) >= 0:
                    conv[t] += p["conv_w"][j] * xBC[t - (cfg.conv_taps - 1 - j)]
        xBC = conv + p["conv_bias"]
        xBC = xBC / (1 + np.exp(-xBC))
        u = xBC[:, :inner].reshape(-1, H, P)
        Bm = xBC[:, inner : inner + G * N].reshape(-1, G, N)
        Cm = xBC[:, inner + G * N :].reshape(-1, G, N)
        dt = np.log1p(np.exp(dt + p["dt_bias"]))
        A = -np.exp(p["A_log"])
        h, y = np.zeros((H, P, N)), np.zeros((len(xs), H, P))
        for t in range(len(xs)):
            for head in range(H):
                g = head // (H // G)
                h[head] = np.exp(dt[t, head] * A[head]) * h[head] + dt[t, head] * np.outer(u[t, head], Bm[t, g])
                y[t, head] = h[head] @ Cm[t, g] + p["D"][head] * u[t, head]
        gated = (y.reshape(-1, inner) * (z / (1 + np.exp(-z)))).reshape(-1, G, inner // G)
        normed = rms(gated, 1.0).reshape(-1, inner) * p["gate_norm"]
        want[b] = xs + normed @ p["out_proj"]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_attention_block_by_hand_has_no_positions_and_groups_of_two():
    cfg, lp, x = tiny_block("*")
    with HIGHEST():
        got, _ = model._block(cfg, None, "*", x, lp)
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    want = np.zeros(x.shape)
    for b in range(x.shape[0]):
        xs = np.asarray(x[b], np.float64)
        n = rms(xs, p["norm"])
        q, k, v = (n @ p["wq"]).reshape(-1, H, hd), (n @ p["wk"]).reshape(-1, KV, hd), (n @ p["wv"]).reshape(-1, KV, hd)
        out = np.zeros((len(xs), H, hd))
        for head in range(H):
            scores = q[:, head] @ k[:, head // (H // KV)].T / np.sqrt(hd)
            scores = np.where(np.tril(np.ones_like(scores, bool)), scores, -np.inf)
            weights = np.exp(scores - scores.max(-1, keepdims=True))
            out[:, head] = weights / weights.sum(-1, keepdims=True) @ v[:, head // (H // KV)]
        want[b] = xs + out.reshape(len(xs), -1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_experts_block_by_hand_routes_at_full_width_and_computes_in_the_latent():
    cfg, lp, x = tiny_block("E")
    lp = dict(lp, moe=dict(lp["moe"], router_bias=0.1 * jax.random.normal(jax.random.key(5), (cfg.n_experts,))))
    with HIGHEST():
        got, stats = model._block(cfg, None, "E", x, lp)
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), lp)
    first, count = cfg.held_experts
    relu2 = lambda a: np.square(np.maximum(a, 0))
    want, held = np.zeros(x.shape), 0
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            xs = np.asarray(x[b, t], np.float64)
            n = rms(xs, p["norm"])
            scores = 1 / (1 + np.exp(-(n @ p["moe"]["router"])))
            chosen = np.argsort(-(scores + p["moe"]["router_bias"]))[: cfg.top_k]
            weights = cfg.routed_scaling_factor * scores[chosen] / scores[chosen].sum()
            latent, routed = n @ p["latent_in"], np.zeros(cfg.latent_dim)
            for e, w in zip(chosen, weights):
                if first <= e < first + count:
                    held += 1
                    routed += w * (relu2(latent @ p["moe"]["w_up"][e - first]) @ p["moe"]["w_down"][e - first])
            want[b, t] = xs + routed @ p["latent_out"] + relu2(n @ p["shared_up"]) @ p["shared_down"]
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    assert int(stats["assignments_held"]) == held and int(stats["dropped"]) == 0
    assert stats["selected"].shape == (24, cfg.top_k)
    assert "w_gate" not in lp["moe"] and lp["moe"]["w_up"].shape == (4, cfg.latent_dim, cfg.expert_dim)


# --- an expert's two forms against a dense loop ----------------------------------------

KINDS = {"xla": dict(kind="xla"), "pallas-interpret": dict(kind="pallas", interpret=True)}


def dense_loop(cfg: RoutedConfig, p: dict, x, rows):
    """sum over the held experts of w_i E_i(rows), every expert on every
    token under a mask; the router reads x."""
    xt, rt = x.reshape(-1, x.shape[-1]), rows.reshape(-1, rows.shape[-1])
    experts, weights = route(cfg, p, xt)
    first, count = cfg.span
    y = jnp.zeros_like(rt)
    for j in range(count):
        share = jnp.sum(jnp.where(experts == first + j, weights, 0.0), axis=-1)
        if cfg.expert == "swiglu":
            out = (jax.nn.silu(rt @ p["w_gate"][j]) * (rt @ p["w_up"][j])) @ p["w_down"][j]
        else:
            out = jnp.square(jax.nn.relu(rt @ p["w_up"][j])) @ p["w_down"][j]
        y = y + share[:, None] * out
    return y.reshape(rows.shape)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("form, latent", [("swiglu", None), ("relu2", None), ("relu2", 8), ("swiglu", 8)])
def test_both_expert_forms_with_and_without_rows_of_their_own_are_the_dense_loop(kind, form, latent):
    cfg = RoutedConfig(n_routed=8, top_k=3, held=(2, 4), selection_bias=True, scale=5.0, expert=form)
    p = init_routed_params(cfg, jax.random.key(0), 16, 24, jnp.float32, rows_dim=latent)
    assert ("w_gate" in p) == (form == "swiglu") and p["w_up"].shape == (4, latent or 16, 24)
    assert set(moe.routed_param_specs(cfg)) == set(p)
    x = jax.random.normal(jax.random.key(1), (2, 40, 16), jnp.float32)
    rows = x if latent is None else jax.random.normal(jax.random.key(2), (2, 40, latent), jnp.float32)

    def ours(p, x, rows):
        given = {} if latent is None else {"expert_rows": rows}
        return routed_experts(cfg, p, x, **given, **KINDS[kind])

    with HIGHEST():
        y, stats = ours(p, x, rows)
        want = dense_loop(cfg, p, x, rows)
        assert y.shape == rows.shape and int(stats["dropped"]) == 0
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=3e-5)
        loss = lambda f: (lambda p, x, rows: jnp.sum(f(p, x, rows) ** 2))
        got = jax.grad(loss(lambda *a: ours(*a)[0]), (0, 1, 2))(p, x, rows)
        wanted = jax.grad(loss(lambda p, x, rows: dense_loop(cfg, p, x, rows)), (0, 1, 2))(p, x, rows)
    if latent is None:  # one array both ways: its gradient is the sum of the two
        got, wanted = (got[0], got[1] + got[2]), (wanted[0], wanted[1] + wanted[2])
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(wanted)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_the_config_refuses_an_unknown_form_and_a_shared_expert_beside_latent_rows():
    with pytest.raises(ValueError, match="unknown expert form"):
        RoutedConfig(n_routed=8, top_k=2, expert="gelu")
    cfg = RoutedConfig(n_routed=8, top_k=2, shared_dim=8)
    p = init_routed_params(cfg, jax.random.key(0), 16, 24, jnp.float32, rows_dim=8)
    with pytest.raises(ValueError, match="is the model's"):
        routed_experts(cfg, p, jnp.zeros((1, 4, 16)), expert_rows=jnp.zeros((1, 4, 8)), kind="xla")
    # the field is read by model modules, never by a user: no flag, option or TrainerConfig field names it
    from deeplearning_cfn_tpu.train import trainer

    assert "expert" not in {f.name for f in trainer.TrainerConfig.__dataclass_fields__.values()}


# --- the trainer --------------------------------------------------------------------


def test_three_steps_of_the_trainer_lower_the_loss_and_count_two_routed_blocks():
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.data import Batch
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig, decay_mask

    cfg = model.SsmAttnMoeConfig.tiny(remat=True)
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = model.make_trainer(cfg, mesh, TrainerConfig(
        strategy="fsdp", optimizer="adamw", learning_rate=3e-3, weight_decay=0.1, grad_clip_norm=1.0))
    x = np.random.default_rng(0).integers(0, cfg.vocab_size, (len(jax.devices()), 20), dtype=np.int32)
    state = trainer.init(jax.random.key(0), x)
    state, losses = trainer.fit(state, iter([Batch(x, np.roll(x, -1, 1))] * 3), steps=3)
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    runs = state.params["runs"]
    assert [len(run) for run in runs] == [2, 1]  # the pair's two blocks; the attention block
    assert runs[0][1]["in_proj"].shape == (2, 32, 2 * 32 + 2 * 2 * 8 + 4)  # [z | x B C | dt]
    # what AdamW decays: matrices (A_log and D, stacked, too), not norms or biases
    mask = decay_mask(state.params)["runs"][0]
    assert mask[1]["in_proj"] and mask[1]["conv_w"] and mask[1]["A_log"] and mask[1]["D"]
    assert not (mask[1]["norm"] or mask[1]["gate_norm"] or mask[1]["dt_bias"] or mask[1]["conv_bias"])
    assert mask[0]["moe"]["router"] and not mask[0]["moe"]["router_bias"]
    # the scopes the per-layer metrics read, on the lowered step's operations
    tokens = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=trainer.batch_sharding)
    with jax.set_mesh(mesh):
        text = trainer.step_fn.lower(state, tokens, tokens).as_text(debug_info=True)
    for scope in ("ssm_norm", "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm", "ssm/out_proj",
                  "attn_norm", "attn/qkv", "attn/core", "attn/out", "moe_norm", "moe/router",
                  "moe/latent_in", "moe/dispatch", "moe/experts", "moe/combine", "moe/latent_out",
                  "moe/shared", "final_norm", "head", "xent"):
        assert f"/{scope}/" in text, scope


# --- the scan's two forms through the model ---------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_loss_and_gradients_with_the_scans_kernels_are_those_with_the_xla_form(dtype, monkeypatch):
    """A lane-aligned model (chunks and a state of 128, groups of two heads of
    64) through `lm_loss`, rematerialised as the cell's: the mixer takes
    `ops/pallas_ssd.ssd` where the rule says so (here the test says so, and the
    Pallas interpreter runs the kernels) and `ops/ssd.ssd` elsewhere."""
    from deeplearning_cfn_tpu.ops import pallas_ssd

    cfg = model.SsmAttnMoeConfig.tiny(
        ssm_heads=4, ssm_head_dim=64, ssm_groups=2, ssm_state=128, chunk=128, remat=True, dtype=dtype
    )
    params = model.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 256), 0, cfg.vocab_size)
    loss = jax.jit(jax.value_and_grad(lambda p: model.lm_loss(cfg, p, tokens, jnp.roll(tokens, -1, 1))[0]))
    with HIGHEST():
        want, want_grads = loss(params)
        entered = []
        kernels = pallas_ssd.ssd
        monkeypatch.setattr(pallas_ssd, "takes_kernel", lambda *a, **k: entered.append(a) or True)
        monkeypatch.setattr(pallas_ssd, "ssd", partial(kernels, interpret=True))
        got, got_grads = jax.jit(jax.value_and_grad(
            lambda p: model.lm_loss(cfg, p, tokens, jnp.roll(tokens, -1, 1))[0]))(params)
    assert entered
    tolerance = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tolerance)
    flat = lambda tree: jax.tree_util.tree_leaves_with_path(tree)
    for (path, g), (_, w) in zip(flat(got_grads), flat(want_grads), strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) <= tolerance * max(np.linalg.norm(w), 1e-3), jax.tree_util.keystr(path)


# --- the two elementwise stages' two forms through the model ----------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_loss_and_gradients_with_the_stages_kernels_are_those_with_the_jnp_stages(dtype, monkeypatch):
    """The same lane-aligned model through `lm_loss`, rematerialised as the
    cell's: the mixer takes `ops/pallas_ssm_stages.py`'s `conv_silu` and
    `gate_norm` where the rules say so (here the test says so, the Pallas
    interpreter runs the kernels, four row tiles a sequence) and
    `jax.checkpoint` of `_conv_silu` and `_gate_norm` elsewhere."""
    from deeplearning_cfn_tpu.ops import pallas_ssm_stages as stages

    cfg = model.SsmAttnMoeConfig.tiny(
        ssm_heads=4, ssm_head_dim=64, ssm_groups=2, ssm_state=128, chunk=128, remat=True, dtype=dtype
    )
    params = model.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 256), 0, cfg.vocab_size)
    loss = lambda p: model.lm_loss(cfg, p, tokens, jnp.roll(tokens, -1, 1))[0]
    with HIGHEST():
        want, want_grads = jax.jit(jax.value_and_grad(loss))(params)
        entered = []
        monkeypatch.setattr(stages, "CONV_TILE", (64, 256))
        monkeypatch.setattr(stages, "GATE_NORM_ROWS", 64)
        for rule in ("takes_conv_kernel", "takes_gate_norm_kernel"):
            monkeypatch.setattr(stages, rule, lambda *a, rule=rule, **k: entered.append(rule) or True)
        for kernel in ("conv_silu", "gate_norm"):
            monkeypatch.setattr(stages, kernel, partial(getattr(stages, kernel), interpret=True))
        traced = jax.jit(jax.value_and_grad(loss)).trace(params)
        got, got_grads = traced.lower().compile()(params)
    assert set(entered) == {"takes_conv_kernel", "takes_gate_norm_kernel"}
    # The pair `EM` is one run, its body traced once: each kernel forward in
    # the first pass and in the block's rematerialised one, and once backward.
    from tests.kernel_text import kernel_calls

    calls = kernel_calls(traced.jaxpr)
    assert {n: c for n, c in calls.items() if n.startswith(("_conv", "_gate"))} == {
        "_conv_silu_forward": 2, "_conv_silu_backward": 1, "_gate_norm_forward": 2, "_gate_norm_backward": 1,
    }
    tolerance = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tolerance)
    flat = lambda tree: jax.tree_util.tree_leaves_with_path(tree)
    for (path, g), (_, w) in zip(flat(got_grads), flat(want_grads), strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) <= tolerance * max(np.linalg.norm(w), 1e-3), jax.tree_util.keystr(path)
