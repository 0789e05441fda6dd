"""Builder for configurations of kind `resnet`: `models/resnet.py`'s ResNet
under the trainer as `examples.resnet_imagenet` sets it up (label
smoothing, uint8 input normalised inside the step, Nesterov momentum),
data-parallel over every chip, with the benchmark's seeded weights in
place of the model's own initialisation."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.probe import Built, optimizer_state, require_same_leaves


def build(config: dict, traffic: dict, key: jax.Array, sample_x, reference) -> Built:
    import optax
    from flax.traverse_util import flatten_dict, unflatten_dict
    from jax.sharding import NamedSharding, PartitionSpec

    from deeplearning_cfn_tpu.models.resnet import ResNet
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

    mesh = build_mesh(MeshSpec.data_parallel(len(jax.devices())))
    model = ResNet(
        stage_sizes=tuple(config["stage_sizes"]),
        num_classes=int(config["num_classes"]),
        num_filters=int(config["num_filters"]),
        dtype=jnp.dtype(config["dtype"]),
        norm=config["norm"],
    )
    trainer = Trainer(
        model,
        mesh,
        TrainerConfig(
            strategy="dp",
            optimizer="momentum",
            learning_rate=float(config["learning_rate"]),
            momentum=float(config["momentum"]),
            has_train_arg=True,
            label_smoothing=float(config["label_smoothing"]),
            log_every=int(traffic["log_every"]),
            input_stats=(tuple(config["input_mean"]), tuple(config["input_std"])),
        ),
    )
    seeded = partial(reference.init_params, cfg=config)
    place = jax.jit(seeded, out_shardings=NamedSharding(mesh, PartitionSpec()))

    def fresh_state(key):
        state = trainer.init(key, sample_x)
        params = unflatten_dict(place(key), sep="/")
        require_same_leaves(state.params, params)
        return state.replace(params=params)

    def first_gradient(opt_state):
        # Momentum starts at zero, so after one step the trace is the gradient.
        return optimizer_state(opt_state, optax.TraceState).trace

    return Built(
        trainer=trainer,
        state=fresh_state(key),
        fresh_state=fresh_state,
        to_reference=lambda tree: flatten_dict(tree, sep="/"),
        first_gradient=first_gradient,
        seeded=seeded,
    )
