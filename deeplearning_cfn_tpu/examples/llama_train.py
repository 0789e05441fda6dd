"""Llama causal-LM training — FSDP x TP x SP over the provisioned slice.

The BASELINE.json flagship: "Llama-3 8B (FSDP-style param sharding via pjit
on the provisioned v5p slice)".  ``--size 8b`` selects the real shape;
``--size 435m`` is the measured single-chip benchmark shape
(docs/BENCH_NOTES.md); ``--size tiny`` smokes the identical code path on
small hardware.

Run: ``python -m deeplearning_cfn_tpu.examples.llama_train --size tiny --steps 20``
"""

from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

from deeplearning_cfn_tpu.examples.common import (
    base_parser,
    maybe_init_distributed,
    metrics_sink,
    param_probe,
    run_report,
)
from deeplearning_cfn_tpu.models import llama
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.data import SyntheticTokenDataset
from deeplearning_cfn_tpu.train.trainer import TrainerConfig


def token_record_batches(
    args, cfg, batch: int, eval_mode: bool = False, start_step: int = 0
):
    """Token DLC1 records (``dlcfn convert --format text``) as causal-LM
    batches when --data_dir is set; None = synthetic."""
    from deeplearning_cfn_tpu.examples.common import token_record_loader
    from deeplearning_cfn_tpu.train.datasets import token_batches

    loaded = token_record_loader(
        args, batch, cfg.vocab_size, eval_mode, start_step=start_step
    )
    if loaded is None:
        return None
    loader, spec, _ = loaded
    return lambda steps: token_batches(loader, spec, steps)


def size_config(size: str, seq_len: int) -> llama.LlamaConfig:
    """The ``--size`` ladder: name -> LlamaConfig at ``seq_len``."""
    if size == "8b":
        return llama.LlamaConfig.llama3_8b()
    if size == "3b":
        # The adafactor rung: pass --optimizer adafactor — adamw's moment
        # state cannot hold this on a 16 GiB chip (llama_memory).
        return llama.LlamaConfig.b3(seq_len=seq_len)
    if size == "1b":
        return llama.LlamaConfig.b1(seq_len=seq_len)
    if size == "435m":
        return llama.LlamaConfig.m435(seq_len=seq_len)
    return llama.LlamaConfig.tiny(vocab_size=512, seq_len=seq_len)


def main(argv: list[str] | None = None) -> dict:
    from deeplearning_cfn_tpu.examples.common import first_step_clock

    t_main = first_step_clock()
    p = base_parser(__doc__)
    p.add_argument("--size", choices=["tiny", "435m", "1b", "3b", "8b"], default="tiny")
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--optimizer", choices=["adamw", "adafactor"], default="adamw",
                   help="adafactor = factored second moments, no first "
                        "moment: the memory-lean rung that pushes the "
                        "16 GiB-chip model ladder past adamw's ~1.1B cap")
    p.add_argument("--fsdp", type=int, default=None, help="fsdp axis size (default: all devices)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ring_attention", action="store_true")
    p.add_argument("--pp", type=int, default=1, help="pipeline stages (GPipe)")
    p.add_argument("--pp_microbatches", type=int, default=0)
    p.add_argument("--experts", type=int, default=0, help="MoE experts (0 = dense)")
    p.add_argument("--ep", type=int, default=1, help="expert-parallel axis size")
    p.add_argument("--eval_steps", type=int, default=0,
                   help="held-out batches for corpus perplexity after "
                        "training (0 = skip; reads the val/test split of "
                        "--data_dir when staged)")
    args = p.parse_args(argv)
    maybe_init_distributed()

    n = len(jax.devices())
    tp, sp, pp, ep = args.tp, args.sp, args.pp, args.ep
    fsdp = args.fsdp or max(1, n // (tp * sp * pp * ep))
    dp = max(1, n // (fsdp * tp * sp * pp * ep))
    mesh = build_mesh(MeshSpec(dp=dp, fsdp=fsdp, pp=pp, sp=sp, tp=tp, ep=ep))

    cfg = size_config(args.size, args.seq_len)
    if args.ring_attention:
        cfg = dataclasses.replace(cfg, use_ring_attention=True)
    if args.experts:
        cfg = dataclasses.replace(cfg, n_experts=args.experts)
    if pp > 1:
        cfg = dataclasses.replace(
            cfg, pp_stages=pp, pp_microbatches=args.pp_microbatches
        )

    # Default batch: divisible by the data shards AND the pipeline
    # microbatch count (pp layouts with dp*fsdp == 1 would otherwise
    # default to batch 1 and fail microbatch splitting).
    microbatches = (args.pp_microbatches or pp) if pp > 1 else 1
    batch = args.global_batch_size or max(1, dp * fsdp) * microbatches
    from deeplearning_cfn_tpu.examples.common import make_lr_schedule

    # Per-optimizer default: adafactor's factored/clipped updates want a
    # much larger step than adam-family.  On-chip LR sweep at the 2.9B
    # rung (equal token budget, held-out ppl): 3e-4 -> 31.8, 1e-3 -> 13.0,
    # 3e-3 -> 9.6, 1e-2 -> 7.2, 3e-2 -> 8.3 — the knee is 1e-2
    # (docs/BENCH_NOTES.md round-5 quality table).
    lr = args.learning_rate or (1e-2 if args.optimizer == "adafactor" else 3e-4)
    trainer = llama.make_trainer(
        cfg,
        mesh,
        TrainerConfig(
            strategy="fsdp",
            optimizer=args.optimizer,
            learning_rate=lr,
            # --lr_schedule cosine = the standard LM recipe (linear
            # warmup + cosine decay); default stays constant so short
            # benchmark runs are comparable across rounds.
            lr_schedule=make_lr_schedule(args, lr),
            weight_decay=args.weight_decay if args.weight_decay is not None else 0.1,
            grad_clip_norm=1.0,
            grad_accum_steps=args.grad_accum,
            log_every=args.log_every,
        ),
    )
    ds = SyntheticTokenDataset(
        seq_len=args.seq_len, vocab_size=cfg.vocab_size, batch_size=batch
    )
    from deeplearning_cfn_tpu.examples.common import open_checkpointer

    ckpt, start_step = open_checkpointer(args)
    batches = (
        token_record_batches(args, cfg, batch, start_step=start_step)
        or ds.batches
    )
    sample = next(iter(batches(1)))
    state = trainer.init(jax.random.key(0), jnp.asarray(sample.x))
    if ckpt is not None:
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state, _ = restored
    # MFU numerator (analytic 6N — flash paths are invisible to cost
    # analysis) is chosen centrally by the trainer.
    logger = trainer.throughput_logger(
        jnp.asarray(sample.x),
        examples_per_step=batch * args.seq_len,  # tokens/sec
        name="llama",
        sink=metrics_sink(args, "llama"),
        log_every=args.log_every,
    )
    probe = param_probe(state)
    state, losses = trainer.fit(
        state, batches(args.steps), steps=args.steps, logger=logger, checkpointer=ckpt
    )
    if ckpt:
        ckpt.save(int(state.step), state)
        ckpt.close()
    result = {
        "final_loss": losses[-1],
        "steps": len(losses),
        "mesh": {"dp": dp, "fsdp": fsdp, "pp": pp, "sp": sp, "tp": tp, "ep": ep},
        "attention": llama.attention_kind(cfg, mesh, args.seq_len),
        "params": llama.param_count(cfg),
        "first_step_s": first_step_clock(trainer, t_main),
        "history": logger.history,
        **run_report(trainer, state, losses, probe),
    }
    if args.eval_steps:
        import math

        eval_batches = token_record_batches(args, cfg, batch, eval_mode=True)
        if eval_batches is None:
            eval_ds = SyntheticTokenDataset(
                seq_len=args.seq_len, vocab_size=cfg.vocab_size,
                batch_size=batch, seed=10_000,
            )
            eval_batches, split = eval_ds.batches, "heldout-synthetic"
        else:
            from deeplearning_cfn_tpu.examples.common import has_heldout_split

            split = "heldout" if has_heldout_split(args.data_dir) else "train"
        ev = trainer.evaluate(state, eval_batches(args.eval_steps), steps=args.eval_steps)
        # exp(mean nll), not mean of per-batch exp: the standard corpus
        # perplexity definition.  Capped exponent: a diverged run's finite
        # loss > ~709 would otherwise OverflowError away the whole result.
        ev["perplexity"] = (
            math.exp(min(ev["loss"], 700.0)) if "loss" in ev else None
        )
        result["eval"] = {"split": split, **ev}
    return result


if __name__ == "__main__":
    print(main())
