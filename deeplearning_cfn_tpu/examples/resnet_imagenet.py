"""Distributed ResNet ImageNet training — the flagship throughput workload.

Analog of the reference's two heavyweight paths: the Horovod ResNet-50
synthetic benchmark (README.md:149-163) and the MXNet ResNet-152
dist_device_sync job (README.md:139).  One SPMD program replaces both; the
``--depth`` flag selects the family member.

Run: ``python -m deeplearning_cfn_tpu.examples.resnet_imagenet --depth 50 --steps 50``
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from deeplearning_cfn_tpu.examples.common import (
    base_parser,
    default_mesh,
    device_image_pipeline,
    image_pipeline,
    maybe_init_distributed,
    metrics_sink,
    param_probe,
    run_report,
)
from deeplearning_cfn_tpu.models.resnet import ResNet50, ResNet101, ResNet152
from deeplearning_cfn_tpu.train.data import SyntheticDataset
from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig

DEPTHS = {50: ResNet50, 101: ResNet101, 152: ResNet152}
# Distinct synthetic batches cycled by a throughput run (bench.py's pool).
SYNTHETIC_POOL_BATCHES = 4


def main(argv: list[str] | None = None) -> dict:
    from deeplearning_cfn_tpu.examples.common import first_step_clock

    t_main = first_step_clock()
    p = base_parser(__doc__)
    p.add_argument("--depth", type=int, choices=sorted(DEPTHS), default=50)
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--bf16", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--norm", choices=["batch", "group"], default="batch",
                   help="normalization layer: BatchNorm (default) or "
                        "GroupNorm-32 (no running stats; measured ~3%% "
                        "slower at the bench shape — BENCH_NOTES r4 — "
                        "but the standard choice for small-per-device-"
                        "batch fine-tuning)")
    p.add_argument("--eval_steps", type=int, default=0,
                   help="held-out eval batches after training (0 = skip; "
                        "reads --data_dir's val/test split when staged).  "
                        "In --target_accuracy mode this sizes only the "
                        "fast mid-run monitor; the gate itself confirms "
                        "on the full split (--full_eval)")
    p.add_argument("--target_accuracy", type=float, default=None,
                   help="stop when held-out top-1 reaches this — the "
                        "north star's 76%% time-to-accuracy mode (eval "
                        "runs every --eval_every steps)")
    p.add_argument("--full_eval", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="score the target gate (and the final claimed "
                        "eval) on the ENTIRE staged val split — a 16k "
                        "subsample has ~±0.3%% noise at the 76.0 "
                        "boundary, and the reference's published numbers "
                        "are whole-dataset (README.md:141).  The "
                        "--eval_steps subsample remains the mid-run "
                        "monitor; synthetic runs are unaffected")
    p.add_argument("--eval_every", type=int, default=0,
                   help="steps between held-out top-1 evals in "
                        "--target_accuracy mode (default: --steps/10)")
    args = p.parse_args(argv)
    maybe_init_distributed()
    batch = args.global_batch_size or 32 * len(jax.devices())
    lr = args.learning_rate or 0.1
    mesh = default_mesh(args.strategy)
    model = DEPTHS[args.depth](
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32, norm=args.norm
    )
    # Synthetic input the way records arrive: uint8 over PCIe, normalized
    # inside the step.  Throughput runs cycle a small pregenerated pool —
    # sampling 128 x 224 x 224 x 3 normals per step on the host held a
    # v5e to ~150 img/s (CHANGES.md PR 21); time-to-accuracy runs keep
    # fresh samples, where cycling would be wrong for the loss curve.
    ds = SyntheticDataset.imagenet_like(
        batch_size=batch,
        image_size=args.image_size,
        dtype="uint8",
        pool_batches=None if args.target_accuracy else SYNTHETIC_POOL_BATCHES,
    )
    from deeplearning_cfn_tpu.examples.common import (
        make_lr_schedule,
        open_checkpointer,
    )

    ckpt, start_step = open_checkpointer(args)
    # Device-resident pipeline: uint8 records stream raw (compact PCIe
    # payload), normalize + flip/crop run inside the jitted step
    # (train/pipeline.py, train/augment.py).
    batches, input_stats, augment = device_image_pipeline(
        args, (args.image_size, args.image_size, 3), ds,
        start_step=start_step,
    )

    trainer = Trainer(
        model,
        mesh,
        TrainerConfig(
            strategy=args.strategy,
            learning_rate=lr,
            # The 76%-top-1 recipe: --lr_schedule step reproduces the
            # reference's stepped decay (run.sh:93); cosine is the
            # better modern default.  Constant LR cannot converge
            # ResNet-50 (VERDICT r3 missing #3), and neither does a
            # decay-free run — the canonical 90-epoch recipe carries
            # weight decay 1e-4 on kernels only (--weight_decay; norm
            # scales/biases are mask-excluded).
            lr_schedule=make_lr_schedule(args, lr),
            weight_decay=args.weight_decay or 0.0,
            has_train_arg=True,
            label_smoothing=0.1,
            grad_accum_steps=args.grad_accum,
            log_every=args.log_every,
            # uint8 records normalize inside the jitted step (fast path).
            input_stats=input_stats,
            # Flip/crop as a seeded on-device stage (train steps only).
            augment=augment,
        ),
    )
    sample = next(iter(batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    if ckpt is not None:
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state, _ = restored
    # MFU numerator chosen centrally by the trainer: cost analysis here
    # (no Pallas ops in this model, so XLA's flop count is complete); the
    # AOT compile inside is the one fit()'s first dispatch reuses.
    logger = trainer.throughput_logger(
        jnp.asarray(sample.x),
        examples_per_step=batch,
        name=f"resnet{args.depth}",
        sink=metrics_sink(args, f"resnet{args.depth}"),
        log_every=args.log_every,
        state=state,
        sample_y=jnp.asarray(sample.y),
    )

    def eval_source():
        """A fresh held-out top-1 eval stream (single-pass loaders are
        exhausted per eval round, so each round re-opens)."""
        from deeplearning_cfn_tpu.examples.common import has_heldout_split

        shape = (args.image_size, args.image_size, 3)
        if args.data_dir:
            eval_batches, _ = image_pipeline(args, shape, ds, eval_mode=True)
            split = "heldout" if has_heldout_split(args.data_dir) else "train"
        else:
            # template_seed pins the TASK to the training set's (whose
            # templates follow its seed=0); only the sample stream
            # differs — without it the "held-out" accuracy would measure
            # a different classification problem entirely.
            eval_ds = SyntheticDataset(
                shape=shape, num_classes=1000, batch_size=batch,
                seed=10_000, template_seed=0, dtype=ds.dtype,
            )
            eval_batches, split = eval_ds.batches, "heldout-synthetic"
        return eval_batches, split

    result: dict = {}
    probe = param_probe(state)
    if args.target_accuracy:
        # Time-to-accuracy mode (the CIFAR walkthrough's shape,
        # README.md:141, pointed at ImageNet top-1): train in chunks, run
        # held-out eval between them, stop at the target.
        eval_every = args.eval_every or max(1, args.steps // 10)
        eval_steps = args.eval_steps or 16
        train_iter = iter(batches(args.steps))
        losses: list[float] = []
        evals: list[dict] = []
        reached = False
        done = 0
        while done < args.steps and not reached:
            chunk = min(eval_every, args.steps - done)
            state, chunk_losses = trainer.fit(
                state, train_iter, steps=chunk, logger=logger,
                checkpointer=ckpt, prefetch_workers=args.prefetch_workers,
            )
            losses.extend(chunk_losses)
            done += chunk
            eval_batches, split = eval_source()
            ev = trainer.evaluate(
                state, eval_batches(eval_steps), steps=eval_steps
            )
            evals.append({"step": done, "split": split, **ev})
            hit = float(ev.get("accuracy", 0.0)) >= args.target_accuracy
            if hit and args.full_eval and split == "heldout":
                # The subsample only MONITORS; the claim is scored on the
                # whole split (the reference's published numbers are
                # whole-dataset, README.md:141 — and at the 76.0 boundary
                # a 16k subsample carries ~±0.3% sampling noise, enough
                # to stop early below the real target).  steps=None
                # consumes the single-pass eval stream to exhaustion,
                # tail batch included (drop_remainder=False).
                full_batches, _ = eval_source()
                full = trainer.evaluate(state, full_batches(None))
                evals.append({"step": done, "split": "heldout-full", **full})
                reached = (
                    float(full.get("accuracy", 0.0)) >= args.target_accuracy
                )
            else:
                reached = hit
        result["eval_history"] = evals
        result["target_reached"] = reached
        result["eval"] = evals[-1]
    else:
        state, losses = trainer.fit(
            state, batches(args.steps), steps=args.steps, logger=logger,
            checkpointer=ckpt, prefetch_workers=args.prefetch_workers,
        )
        if args.eval_steps:
            eval_batches, split = eval_source()
            if args.full_eval and split == "heldout":
                # The final claimed number covers the whole split.
                result["eval"] = {
                    "split": "heldout-full",
                    **trainer.evaluate(state, eval_batches(None)),
                }
            else:
                result["eval"] = {
                    "split": split,
                    **trainer.evaluate(
                        state, eval_batches(args.eval_steps),
                        steps=args.eval_steps,
                    ),
                }
    if ckpt is not None:
        ckpt.save(int(jax.device_get(state.step)), state)
        ckpt.close()
    result.update(
        {
            "final_loss": losses[-1],
            "steps": len(losses),
            "history": logger.history,
            "first_step_s": first_step_clock(trainer, t_main),
            **run_report(trainer, state, losses, probe),
        }
    )
    return result


if __name__ == "__main__":
    print(main())
