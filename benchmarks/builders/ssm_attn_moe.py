"""Builder for configurations of kind `ssm_attn_moe`:
`models/ssm_attn_moe.py`'s decoder (blocks that are a Mamba-2 mixer, attention
or routed latent experts alone, by `hybrid_override_pattern`, the untied head)
at the configuration's sizes through `ssm_attn_moe.make_trainer`, with the
`TrainerConfig` of the `decoder` kind (fsdp, adamw, weight decay, gradient
clipping, constant rate) and the benchmark's seeded weights in place of the
model's own initialisation.

`n_routed_experts` in the file is the number of experts this chip holds of
`published.n_routed_experts`, and `deployment.rank` which span of them.  The
builder also hands the reference the program's own selection of experts at the
seeded weights (`reference.program_routing`), so that the check can say on how
many assignments the two differ."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.builders.conv_attn_moe import _nested
from benchmarks.probe import Built, optimizer_state, require_same_leaves


def model_config(config: dict):
    from deeplearning_cfn_tpu.models.ssm_attn_moe import SsmAttnMoeConfig

    if config["remat_policy"] != "full":
        raise ValueError("models/ssm_attn_moe.py rematerialises whole blocks or nothing")
    if config["attention_bias"] or config["mamba_proj_bias"] or config["mlp_bias"]:
        raise ValueError("no projection has a bias")
    if not config["use_conv_bias"] or config["tie_word_embeddings"]:
        raise ValueError("the convolution has a bias and the head is untied")
    if (config["mlp_hidden_act"], config["mamba_hidden_act"]) != ("relu2", "silu"):
        raise ValueError("the experts are relu^2 and the mixer's gates SiLU")
    if (config["n_group"], config["topk_group"], config["n_shared_experts"]) != (1, 1, 1):
        raise ValueError("no group-limited routing, and one shared expert")
    if config["num_nextn_predict_layers"] or not config["norm_topk_prob"]:
        raise ValueError("no prediction module; the selected weights are renormalised")
    if len(config["hybrid_override_pattern"]) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern names every block")
    held = int(config["n_routed_experts"])
    return SsmAttnMoeConfig(
        vocab_size=int(config["vocab_size"]),
        dim=int(config["hidden_size"]),
        pattern=config["hybrid_override_pattern"],
        ssm_heads=int(config["mamba_num_heads"]),
        ssm_head_dim=int(config["mamba_head_dim"]),
        ssm_groups=int(config["n_groups"]),
        ssm_state=int(config["ssm_state_size"]),
        conv_taps=int(config["conv_kernel"]),
        chunk=int(config["chunk_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        latent_dim=int(config["moe_latent_size"]),
        expert_dim=int(config["moe_intermediate_size"]),
        shared_expert_dim=int(config["moe_shared_expert_intermediate_size"]),
        n_experts=int(config["published"]["n_routed_experts"]),
        held_experts=(int(config["deployment"]["rank"]) * held, held),
        top_k=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=float(config["layer_norm_epsilon"]),
        dtype=jnp.dtype(config["torch_dtype"]),
        remat=True,
        use_flash_attention=bool(config["use_flash_attention"]),
    )


def _places(config: dict) -> list[tuple[int, int, int]]:
    """(run, block of the unit, repetition) of every block in forward order."""
    return [
        (r, j, i)
        for r, (unit, n) in enumerate(model_config(config).runs)
        for i in range(n) for j in range(len(unit))
    ]


def program_tree(flat: dict, config: dict, reference) -> dict:
    """The reference's flat leaves as `models/ssm_attn_moe.py`'s parameter
    tree: a run's units stacked block by block, the selection bias among the
    parameters."""
    layers = [_nested(reference.block_params(flat, prefix)) for prefix, _ in reference.blocks(config)]
    runs = [[[] for _ in unit] for unit, _ in model_config(config).runs]
    for layer, (r, j, _) in zip(layers, _places(config), strict=True):
        runs[r][j].append(layer)
    stack = lambda blocks: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        **{n: flat[n] for n in reference.TOP_LEAVES},
        "runs": [[stack(blocks) for blocks in run] for run in runs],
    }


def reference_leaves(tree: dict, config: dict, reference) -> dict:
    """The program's tree (or one shaped like it) under the reference's
    names, the leaves it compares: the buffer is left out."""
    flat = {n: tree[n] for n in reference.TOP_LEAVES}
    for (prefix, leaves), (r, j, i) in zip(reference.blocks(config), _places(config), strict=True):
        stack = tree["runs"][r][j]
        for name in leaves:
            group, _, last = name.rpartition("/")
            flat[prefix + name] = (stack[group] if group else stack)[last][i]
    return flat


def build(config: dict, traffic: dict, key: jax.Array, sample_x, reference) -> Built:
    import optax

    from deeplearning_cfn_tpu.models import ssm_attn_moe
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = model_config(config)
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = ssm_attn_moe.make_trainer(
        cfg,
        mesh,
        TrainerConfig(
            strategy="fsdp",
            optimizer="adamw",
            learning_rate=float(config["learning_rate"]),
            weight_decay=float(config["weight_decay"]),
            grad_clip_norm=float(config["grad_clip_norm"]),
            log_every=int(traffic["log_every"]),
        ),
    )
    seeded = partial(reference.init_params, cfg=config)
    to_program = partial(program_tree, config=config, reference=reference)
    to_reference = partial(reference_leaves, config=config, reference=reference)

    # Sets trainer.state_shardings; nothing runs.
    jax.eval_shape(trainer.init, key, sample_x)
    shardings = trainer.state_shardings.params
    place = jax.jit(lambda k: to_program(seeded(k)), out_shardings=shardings)

    def fresh_state(key):
        state = trainer.init(key, sample_x)
        model_params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state.params
        )
        state = state.replace(params=None)  # the model's own weights go first
        params = place(key)
        require_same_leaves(model_params, params)
        return state.replace(params=params)

    b1 = float(config["adam_b1"])

    def first_gradient(opt_state):
        # AdamW's first moment starts at zero: after one step it is
        # (1 - b1) times the gradient it was given, which is the clipped one.
        adam = optimizer_state(opt_state, optax.ScaleByAdamState)
        return jax.tree_util.tree_map(lambda m: m.astype(jnp.float32) / (1.0 - b1), adam.mu)

    select = jax.jit(
        lambda params, tokens: ssm_attn_moe.logits(cfg, params, tokens, mesh)["selected"]
    )

    def program_routing(key, tokens):
        """What the program selects at the seeded weights: the weights are
        made again, so this holds nothing of the trainer's state."""
        with jax.set_mesh(mesh):
            return jax.device_get(select(place(key), tokens))

    reference.program_routing = program_routing

    return Built(
        trainer=trainer,
        state=fresh_state(key),
        fresh_state=fresh_state,
        to_reference=to_reference,
        first_gradient=first_gradient,
        seeded=seeded,
    )
