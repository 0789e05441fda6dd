"""Latent-attention, routed-experts causal-LM training (models/mla_moe.py).

``--size stage`` is one chip's share of a four-chip expert-parallel stage of
GLM-4.7-Flash at its published widths (hidden 2048, 20 heads of 192 + 64 / 256,
latent ranks 768 and 512, 64 sigmoid-routed experts of 1536, top 4, a shared
expert, one multi-token-prediction module): ``--layers`` blocks of which the
first is dense, ``--experts_held`` of the 64 experts of every routed layer
starting at ``--rank`` times that many, ``--vocab_rows`` rows of the 154,880.
The defaults are the benchmark's cell (`glm-4.7-flash.train-s8192`: 5 layers,
16 experts, 38,720 rows; 1.16 B parameters, which one 16 GB chip trains with
adamw at 2 x 8192 tokens).  ``--size tiny`` smokes the identical code path.

Run: ``python -m deeplearning_cfn_tpu.examples.mla_moe_train --size tiny --steps 20``
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning_cfn_tpu.examples.common import (
    base_parser,
    first_step_clock,
    make_lr_schedule,
    maybe_init_distributed,
    metrics_sink,
    open_checkpointer,
    param_probe,
    run_report,
)
from deeplearning_cfn_tpu.models import mla_moe
from deeplearning_cfn_tpu.models.llama import attention_kind
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.data import SyntheticTokenDataset
from deeplearning_cfn_tpu.train.trainer import TrainerConfig


def size_config(args) -> mla_moe.MlaMoeConfig:
    if args.size == "tiny":
        return mla_moe.MlaMoeConfig.tiny(max_seq_len=args.seq_len)
    return mla_moe.MlaMoeConfig(
        vocab_size=args.vocab_rows,
        n_layers=args.layers,
        held_experts=(args.rank * args.experts_held, args.experts_held),
    )


def main(argv: list[str] | None = None) -> dict:
    t_main = first_step_clock()
    p = base_parser(__doc__)
    p.add_argument("--size", choices=["tiny", "stage"], default="tiny")
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--layers", type=int, default=5, help="blocks, the first one dense")
    p.add_argument("--experts_held", type=int, default=16, help="of the 64 of each routed layer")
    p.add_argument("--rank", type=int, default=0, help="which span of experts this program holds")
    p.add_argument("--vocab_rows", type=int, default=38720, help="of the 154,880 published")
    args = p.parse_args(argv)
    maybe_init_distributed()

    n = len(jax.devices())
    mesh = build_mesh(MeshSpec.fsdp_parallel(n))
    cfg = size_config(args)
    batch = args.global_batch_size or n
    lr = args.learning_rate or 3e-4
    trainer = mla_moe.make_trainer(
        cfg,
        mesh,
        TrainerConfig(
            strategy="fsdp",
            optimizer="adamw",
            learning_rate=lr,
            lr_schedule=make_lr_schedule(args, lr),
            weight_decay=args.weight_decay if args.weight_decay is not None else 0.1,
            grad_clip_norm=1.0,
            grad_accum_steps=args.grad_accum,
            log_every=args.log_every,
        ),
    )
    ds = SyntheticTokenDataset(seq_len=args.seq_len, vocab_size=cfg.vocab_size, batch_size=batch)
    ckpt, _ = open_checkpointer(args)
    sample = next(iter(ds.batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    if ckpt is not None:
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state, _ = restored
    logger = trainer.throughput_logger(
        jnp.asarray(sample.x),
        examples_per_step=batch * args.seq_len,  # tokens/sec
        name="mla_moe",
        sink=metrics_sink(args, "mla_moe"),
        log_every=args.log_every,
    )
    probe = param_probe(state)
    state, losses = trainer.fit(
        state, ds.batches(args.steps), steps=args.steps, logger=logger, checkpointer=ckpt
    )
    if ckpt:
        ckpt.save(int(state.step), state)
        ckpt.close()
    from deeplearning_cfn_tpu.obs.tracing import counters

    counted = {k: v for k, v in counters().items() if k.startswith("moe.")}
    return {
        "final_loss": losses[-1],
        "steps": len(losses),
        "mesh": {"fsdp": n},
        "attention": attention_kind(cfg, mesh, args.seq_len),
        "params": mla_moe.param_count(cfg),
        "experts_held": list(cfg.routed.span),
        # Per step: what the routed layers were sent and what they dropped (0).
        "routing": {k: v["total"] / v["count"] for k, v in counted.items() if v["count"]},
        "first_step_s": first_step_clock(trainer, t_main),
        "history": logger.history,
        **run_report(trainer, state, losses, probe),
    }


if __name__ == "__main__":
    print(main())
