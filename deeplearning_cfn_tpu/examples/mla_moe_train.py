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

from deeplearning_cfn_tpu.examples.common import base_parser, first_step_clock, train_expert_stage
from deeplearning_cfn_tpu.models import mla_moe


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
    return train_expert_stage(args, mla_moe, size_config(args), "mla_moe", t_main)


if __name__ == "__main__":
    print(main())
