"""Builder for configurations of kind `mamba_attn`: `models/mamba_attn.py`'s
decoder (layers of a Mamba-1 mixer or multi-query attention by
`attn_layer_period` / `attn_layer_offset`, a dense SwiGLU half in every layer,
the tied table) at the configuration's sizes through `mamba_attn.make_trainer`,
with the `TrainerConfig` of the `decoder` kind (fsdp, adamw, weight decay,
gradient clipping, constant rate) and the benchmark's seeded weights in place
of the model's own initialisation.

`vocab_size` in the file is the number of the table's rows this chip holds of
`published.vocab_size` (rows 0 up); `num_hidden_layers` the layers held, the
published layers 0 up, so that the layer order is the published one."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.probe import Built, optimizer_state, require_same_leaves


def model_config(config: dict):
    from deeplearning_cfn_tpu.models.mamba_attn import MambaAttnConfig

    if config["remat_policy"] != "full":
        raise ValueError("models/mamba_attn.py rematerialises whole layers or nothing")
    if config["mamba_proj_bias"] or not config["mamba_conv_bias"] or not config["tie_word_embeddings"]:
        raise ValueError("no projection has a bias, the convolution has one, the table is tied")
    if (config["num_experts"], config["num_experts_per_tok"]) != (1, 1) or config["hidden_act"] != "silu":
        raise ValueError("one expert: every feed-forward half is the dense SwiGLU")
    if config["sliding_window"] is not None:
        raise ValueError("attention over the whole sequence")
    return MambaAttnConfig(
        vocab_size=int(config["vocab_size"]),
        dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        attn_layer_period=int(config["attn_layer_period"]),
        attn_layer_offset=int(config["attn_layer_offset"]),
        ssm_inner=int(config["mamba_expand"]) * int(config["hidden_size"]),
        ssm_state=int(config["mamba_d_state"]),
        conv_taps=int(config["mamba_d_conv"]),
        dt_rank=int(config["mamba_dt_rank"]),
        mlp_dim=int(config["intermediate_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config["torch_dtype"]),
        remat=True,
        use_flash_attention=bool(config["use_flash_attention"]),
    )


def _places(config: dict) -> list[tuple[int, int]]:
    """(run, place in the run) of every layer in forward order."""
    return [(r, i) for r, (_, n) in enumerate(model_config(config).runs) for i in range(n)]


def program_tree(flat: dict, config: dict, reference) -> dict:
    """The reference's flat leaves as `models/mamba_attn.py`'s parameter tree:
    a run's layers stacked leaf by leaf."""
    runs = [[] for _ in model_config(config).runs]
    for (prefix, leaves), (r, _) in zip(reference.layers(config), _places(config), strict=True):
        runs[r].append({n: flat[prefix + n] for n in leaves})
    stack = lambda layers: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {**{n: flat[n] for n in reference.TOP_LEAVES}, "runs": [stack(run) for run in runs]}


def reference_leaves(tree: dict, config: dict, reference) -> dict:
    """The program's tree (or one shaped like it) under the reference's names."""
    flat = {n: tree[n] for n in reference.TOP_LEAVES}
    for (prefix, leaves), (r, i) in zip(reference.layers(config), _places(config), strict=True):
        for name in leaves:
            flat[prefix + name] = tree["runs"][r][name][i]
    return flat


def build(config: dict, traffic: dict, key: jax.Array, sample_x, reference) -> Built:
    import optax

    from deeplearning_cfn_tpu.models import mamba_attn
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = model_config(config)
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = mamba_attn.make_trainer(
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
    place = jax.jit(
        lambda k: to_program(seeded(k)), out_shardings=trainer.state_shardings.params
    )

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

    return Built(
        trainer=trainer,
        state=fresh_state(key),
        fresh_state=fresh_state,
        to_reference=to_reference,
        first_gradient=first_gradient,
        seeded=seeded,
    )
