"""Builder for configurations of kind `looped_decoder`:
`models/looped_decoder.py`'s decoder (one stack of sandwich-normed blocks run
`total_ut_steps` times a step on the same weights, a head and a loss after
every pass, the exit gate's mix of them) at the configuration's sizes through
`looped_decoder.make_trainer`, with the `TrainerConfig` of the `decoder` kind
(fsdp, adamw, weight decay, gradient clipping, constant rate) and the
benchmark's seeded weights in place of the model's own initialisation."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.probe import Built, optimizer_state, require_same_leaves


def model_config(config: dict):
    from deeplearning_cfn_tpu.models.llama import LlamaConfig
    from deeplearning_cfn_tpu.models.looped_decoder import LoopedDecoderConfig

    layers = int(config["num_hidden_layers"])
    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise ValueError("models/llama.py takes head_dim as hidden_size / heads")
    if config["remat_policy"] != "full":
        raise ValueError("models/looped_decoder.py rematerialises whole blocks or nothing")
    if config["rope_scaling"] is not None or config["use_sliding_window"] or config["sliding_window"]:
        raise ValueError("plain rotary positions and attention over the whole sequence")
    if config["layer_types"] != ["full_attention"] * layers or config["hidden_act"] != "silu":
        raise ValueError("every layer attends fully and its feed-forward is SwiGLU")
    decoder = LlamaConfig(
        vocab_size=int(config["vocab_size"]),
        dim=int(config["hidden_size"]),
        n_layers=layers,
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        mlp_dim=int(config["intermediate_size"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config["torch_dtype"]),
        remat=True,
        remat_policy="full",
        tied_embeddings=bool(config["tie_word_embeddings"]),
        use_flash_attention=bool(config["use_flash_attention"]),
    )
    return LoopedDecoderConfig(
        decoder, passes=int(config["total_ut_steps"]), exit_beta=float(config["exit_beta"])
    )


def build(config: dict, traffic: dict, key: jax.Array, sample_x, reference) -> Built:
    import optax

    from deeplearning_cfn_tpu.models import looped_decoder
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = model_config(config)
    layers = cfg.decoder.n_layers
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = looped_decoder.make_trainer(
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

    def to_program(flat: dict) -> dict:
        return {
            **{n: flat[n] for n in reference.TOP_LEAVES},
            "layers": {
                n: jnp.stack([flat[f"layers/{i}/{n}"] for i in range(layers)])
                for n in reference.LAYER_LEAVES
            },
        }

    def to_reference(tree: dict) -> dict:
        flat = {n: tree[n] for n in reference.TOP_LEAVES}
        for n in reference.LAYER_LEAVES:
            for i in range(layers):
                flat[f"layers/{i}/{n}"] = tree["layers"][n][i]
        return flat

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
