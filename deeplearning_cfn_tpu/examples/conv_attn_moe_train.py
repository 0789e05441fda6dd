"""Short-convolution / attention, routed-experts causal-LM training
(models/conv_attn_moe.py).

``--size stage`` is one chip's share of a four-chip expert-parallel stage of
LFM2-8B-A1B at its published widths (hidden 2048, gated short convolutions of
3 taps three layers in four beside 32 / 8 normalised heads of 64, 32
sigmoid-routed experts of 1792, top 4, no shared expert, the table tied to the
head): ``--layers`` of the published 24 layers starting at ``--first_layer``
(the layers before the published ``num_dense_layers`` = 2 keep their dense
feed-forward), ``--experts_held`` of the 32 experts of every routed layer
starting at ``--rank`` times that many, ``--vocab_rows`` rows of the 65,536.
The defaults are the benchmark's cell (`lfm2-8b-a1b.train-s8192`: published
layers 1-13, 8 experts, 16,384 rows; 1.33 B parameters, which one 16 GB chip
trains with adamw at 2 x 8192 tokens).  ``--size tiny`` smokes the identical
code path.

Run: ``python -m deeplearning_cfn_tpu.examples.conv_attn_moe_train --size tiny --steps 20``
"""

from __future__ import annotations

from deeplearning_cfn_tpu.examples.common import base_parser, first_step_clock, train_expert_stage
from deeplearning_cfn_tpu.models import conv_attn_moe


def size_config(args) -> conv_attn_moe.ConvAttnMoeConfig:
    if args.size == "tiny":
        return conv_attn_moe.ConvAttnMoeConfig.tiny(max_seq_len=args.seq_len)
    published = conv_attn_moe.ConvAttnMoeConfig()
    first = args.first_layer
    return conv_attn_moe.ConvAttnMoeConfig(
        vocab_size=args.vocab_rows,
        layer_types=published.layer_types[first : first + args.layers],
        n_dense_layers=max(0, min(published.n_dense_layers - first, args.layers)),
        held_experts=(args.rank * args.experts_held, args.experts_held),
    )


def main(argv: list[str] | None = None) -> dict:
    t_main = first_step_clock()
    p = base_parser(__doc__)
    p.add_argument("--size", choices=["tiny", "stage"], default="tiny")
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--first_layer", type=int, default=1, help="of the published 24, from 0")
    p.add_argument("--layers", type=int, default=13, help="how many of them from there")
    p.add_argument("--experts_held", type=int, default=8, help="of the 32 of each routed layer")
    p.add_argument("--rank", type=int, default=0, help="which span of experts this program holds")
    p.add_argument("--vocab_rows", type=int, default=16384, help="of the 65,536 published")
    args = p.parse_args(argv)
    cfg = size_config(args)
    return {
        **train_expert_stage(args, conv_attn_moe, cfg, "conv_attn_moe", t_main),
        "layers": {
            "conv": cfg.layer_types.count("conv"),
            "full_attention": cfg.layer_types.count("full_attention"),
            "dense": cfg.n_dense_layers,
        },
    }


if __name__ == "__main__":
    print(main())
