"""Builder for configurations of kind `window_attn_moe`:
`models/window_attn_moe.py`'s decoder (full and sliding-window attention by
`layer_types`, a head count a layer, a rotary rule a kind, a gate a head, a
dense or routed feed-forward by `mlp_layer_types`, the untied head) at the
configuration's sizes through `window_attn_moe.make_trainer`, with the
`TrainerConfig` of the `decoder` kind (fsdp, adamw, weight decay, gradient
clipping, constant rate) and the benchmark's seeded weights in place of the
model's own initialisation.

`num_experts` in the file is the number of experts this chip holds of
`published.num_experts`, and `deployment.rank` which span of them.  The builder
also hands the reference the program's own selection of experts at the seeded
weights (`reference.program_routing`), so that the check can say on how many
assignments the two differ."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.builders.conv_attn_moe import _nested
from benchmarks.probe import Built, optimizer_state, require_same_leaves


def rotary_rule(rope: dict):
    from deeplearning_cfn_tpu.models.window_attn_moe import RotaryRule

    if rope["rope_type"] == "default":
        return RotaryRule(
            theta=float(rope["rope_theta"]), partial=float(rope.get("partial_rotary_factor", 1.0))
        )
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is neither yarn nor default")
    return RotaryRule(
        theta=float(rope["rope_theta"]), partial=float(rope["partial_rotary_factor"]),
        yarn_factor=float(rope["factor"]),
        original_max=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        attention_factor=float(rope["attention_factor"]),
    )


def model_config(config: dict):
    from deeplearning_cfn_tpu.models.window_attn_moe import WindowAttnMoeConfig

    if config["remat_policy"] != "full":
        raise ValueError("models/window_attn_moe.py rematerialises whole blocks or nothing")
    if config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("the attention has no bias and the head is untied")
    if config["moe_apply_router_weight_on_input"]:
        raise ValueError("the router's weight is on the expert's output")
    layers = int(config["num_hidden_layers"])
    lists = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
    if any(len(config[name]) != layers for name in lists):
        raise ValueError(f"{lists} name every one of {layers} layers")
    held = int(config["num_experts"])
    return WindowAttnMoeConfig(
        vocab_size=int(config["vocab_size"]),
        dim=int(config["hidden_size"]),
        layer_types=tuple(config["layer_types"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        heads_per_layer=tuple(int(h) for h in config["num_attention_heads_per_layer"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        sliding_window=int(config["sliding_window"]),
        gating=bool(config["gating"]),
        full_rotary=rotary_rule(config["rope_parameters"]["full_attention"]),
        sliding_rotary=rotary_rule(config["rope_parameters"]["sliding_attention"]),
        mlp_dim=int(config["intermediate_size"]),
        expert_dim=int(config["moe_intermediate_size"]),
        shared_expert_dim=int(config["shared_expert_intermediate_size"]),
        n_experts=int(config["published"]["num_experts"]),
        held_experts=(int(config["deployment"]["rank"]) * held, held),
        top_k=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
        max_seq_len=int(config["max_position_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config["torch_dtype"]),
        remat=True,
        use_flash_attention=bool(config["use_flash_attention"]),
    )


def program_tree(flat: dict, config: dict, reference) -> dict:
    """The reference's flat leaves as `models/window_attn_moe.py`'s parameter
    tree: each run's blocks stacked, the selection bias among the parameters."""
    layers = [_nested(reference.block_params(flat, prefix)) for prefix, _ in reference.blocks(config)]
    runs, start = [], 0
    for _, n in model_config(config).runs:
        runs.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers[start : start + n]))
        start += n
    return {**{n: flat[n] for n in reference.TOP_LEAVES}, "runs": runs}


def reference_leaves(tree: dict, config: dict, reference) -> dict:
    """The program's tree (or one shaped like it) under the reference's
    names, the leaves it compares: the buffer is left out."""
    flat = {n: tree[n] for n in reference.TOP_LEAVES}
    places = [(r, i) for r, (_, n) in enumerate(model_config(config).runs) for i in range(n)]
    for (prefix, leaves), (run, i) in zip(reference.blocks(config), places, strict=True):
        stack = tree["runs"][run]
        for name in leaves:
            group, _, last = name.rpartition("/")
            flat[prefix + name] = (stack[group] if group else stack)[last][i]
    return flat


def build(config: dict, traffic: dict, key: jax.Array, sample_x, reference) -> Built:
    import optax

    from deeplearning_cfn_tpu.models import window_attn_moe
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = model_config(config)
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = window_attn_moe.make_trainer(
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
        lambda params, tokens: window_attn_moe.logits(cfg, params, tokens, mesh)["selected"]
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
