"""Builder for configurations of kind `mla_moe`: `models/mla_moe.py`'s decoder
(latent attention, routed experts, multi-token prediction) at the
configuration's sizes through `mla_moe.make_trainer`, with the `TrainerConfig`
of the `decoder` kind (fsdp, adamw, weight decay, gradient clipping, constant
rate) and the benchmark's seeded weights in place of the model's own
initialisation.

`n_routed_experts` in the file is the number of experts this chip holds of
`published.n_routed_experts`, and `deployment.rank` which span of them.  The
builder also hands the reference the program's own selection of experts at
the seeded weights (`reference.program_routing`), so that the check can say on
how many assignments the two differ."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.probe import Built, optimizer_state, require_same_leaves


def model_config(config: dict):
    from deeplearning_cfn_tpu.models.mla_moe import MlaMoeConfig

    if config["remat_policy"] != "full":
        raise ValueError("models/mla_moe.py rematerialises whole blocks or nothing")
    held = int(config["n_routed_experts"])
    qk = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    if int(config["head_dim"]) != qk or int(config["num_key_value_heads"]) != int(
        config["num_attention_heads"]
    ):
        raise ValueError("head_dim is the query/key head size and every head has its own keys")
    return MlaMoeConfig(
        vocab_size=int(config["vocab_size"]),
        dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_dense_layers=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        mlp_dim=int(config["intermediate_size"]),
        expert_dim=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["published"]["n_routed_experts"]),
        held_experts=(int(config["deployment"]["rank"]) * held, held),
        top_k=int(config["num_experts_per_tok"]),
        n_shared_experts=int(config["n_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        scoring_func="sigmoid",
        n_predict=int(config["num_nextn_predict_layers"]),
        mtp_loss_weight=float(config["mtp_loss_weight"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config["torch_dtype"]),
        remat=True,
        use_flash_attention=bool(config["use_flash_attention"]),
    )


def _block_tree(flat: dict, prefix: str, leaves) -> dict:
    tree: dict = {}
    for name in leaves:
        if name.startswith("moe/"):
            tree.setdefault("moe", {})[name[4:]] = flat[prefix + name]
        else:
            tree[name] = flat[prefix + name]
    return tree


def _block_flat(tree: dict, prefix: str, leaves) -> dict:
    return {
        prefix + n: (tree["moe"][n[4:]] if n.startswith("moe/") else tree[n]) for n in leaves
    }


def program_tree(flat: dict, cfg, reference) -> dict:
    """The reference's flat leaves as `models/mla_moe.py`'s parameter tree:
    the blocks stacked, the selection bias among the parameters."""
    held_here = reference.ROUTED_LEAVES + reference.BUFFERS

    def stack(kind, n, leaves):
        blocks = [_block_tree(flat, f"{kind}/{i}/", leaves) for i in range(n)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)

    tree = {n: flat[n] for n in reference.TOP_LEAVES}
    tree["layers"] = stack("layers", cfg.n_routed_layers, held_here)
    if cfg.n_dense_layers:
        tree["dense"] = stack("dense", cfg.n_dense_layers, reference.DENSE_LEAVES)
    if cfg.n_predict:
        tree["mtp"] = {n[4:]: flat[n] for n in reference.MTP_LEAVES}
        tree["mtp"]["block"] = _block_tree(flat, "mtp/block/", held_here)
    return tree


def reference_leaves(tree: dict, cfg, reference) -> dict:
    """The program's tree (or one shaped like it) under the reference's
    names, the leaves it compares: the buffer is left out."""
    flat = {n: tree[n] for n in reference.TOP_LEAVES}
    for kind, n, leaves in (
        ("layers", cfg.n_routed_layers, reference.ROUTED_LEAVES),
        ("dense", cfg.n_dense_layers, reference.DENSE_LEAVES),
    ):
        for i in range(n):
            one = jax.tree_util.tree_map(lambda a: a[i], tree[kind])
            flat.update(_block_flat(one, f"{kind}/{i}/", leaves))
    if cfg.n_predict:
        flat.update({n: tree["mtp"][n[4:]] for n in reference.MTP_LEAVES})
        flat.update(_block_flat(tree["mtp"]["block"], "mtp/block/", reference.ROUTED_LEAVES))
    return flat


def build(config: dict, traffic: dict, key: jax.Array, sample_x, reference) -> Built:
    import optax

    from deeplearning_cfn_tpu.models import mla_moe
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = model_config(config)
    mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
    trainer = mla_moe.make_trainer(
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
    to_program = partial(program_tree, cfg=cfg, reference=reference)
    to_reference = partial(reference_leaves, cfg=cfg, reference=reference)

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
        lambda params, tokens, targets: mla_moe.logits(cfg, params, tokens, targets, mesh)[
            "selected"
        ]
    )

    def program_routing(key, tokens, targets):
        """What the program selects at the seeded weights: the weights are
        made again, so this holds nothing of the trainer's state."""
        with jax.set_mesh(mesh):
            return jax.device_get(select(place(key), tokens, targets))

    reference.program_routing = program_routing

    return Built(
        trainer=trainer,
        state=fresh_state(key),
        fresh_state=fresh_state,
        to_reference=to_reference,
        first_gradient=first_gradient,
        seeded=seeded,
    )
