"""Window-and-full attention, routed-experts causal-LM training
(models/window_attn_moe.py).

``--size stage`` is one chip's share of a four-chip expert-parallel stage of
Laguna-XS.2 at its published widths (hidden 2048; three layers in four attend
over a window of 512 keys with 64 query heads and plain rotary, the fourth
over everything with 48 and half-rotated YaRN, all over 8 key/value heads of
128; a sigmoid gate a head on the attention output; 256 sigmoid-routed experts
of 512, top 8, and a shared one; an untied head): ``--layers`` of the published
40 layers starting at ``--first_layer`` (published layer 0 keeps its dense
feed-forward of 8192), ``--experts_held`` of the 256 experts of every routed
layer starting at ``--rank`` times that many, ``--vocab_rows`` rows of the
100,352.  The defaults are the benchmark's cell (`laguna-xs.2.train-s8192`:
published layers 0-4, 64 experts, 25,088 rows; 1.15 B parameters, which one
16 GB chip trains with adamw at 2 x 8192 tokens).  ``--size tiny`` smokes the
identical code path.

Run: ``python -m deeplearning_cfn_tpu.examples.window_attn_moe_train --size tiny --steps 20``
"""

from __future__ import annotations

from deeplearning_cfn_tpu.examples.common import base_parser, first_step_clock, train_expert_stage
from deeplearning_cfn_tpu.models import window_attn_moe


def size_config(args) -> window_attn_moe.WindowAttnMoeConfig:
    if args.size == "tiny":
        return window_attn_moe.WindowAttnMoeConfig.tiny(max_seq_len=args.seq_len)
    published = window_attn_moe.WindowAttnMoeConfig.published()
    stage = slice(args.first_layer, args.first_layer + args.layers)
    return window_attn_moe.WindowAttnMoeConfig(
        vocab_size=args.vocab_rows,
        layer_types=published.layer_types[stage],
        mlp_layer_types=published.mlp_layer_types[stage],
        heads_per_layer=published.heads_per_layer[stage],
        held_experts=(args.rank * args.experts_held, args.experts_held),
    )


def main(argv: list[str] | None = None) -> dict:
    t_main = first_step_clock()
    p = base_parser(__doc__)
    p.add_argument("--size", choices=["tiny", "stage"], default="tiny")
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--first_layer", type=int, default=0, help="of the published 40, from 0")
    p.add_argument("--layers", type=int, default=5, help="how many of them from there")
    p.add_argument("--experts_held", type=int, default=64, help="of the 256 of each routed layer")
    p.add_argument("--rank", type=int, default=0, help="which span of experts this program holds")
    p.add_argument("--vocab_rows", type=int, default=25088, help="of the 100,352 published")
    args = p.parse_args(argv)
    cfg = size_config(args)
    return {
        **train_expert_stage(args, window_attn_moe, cfg, "window_attn_moe", t_main),
        "layers": {
            "full_attention": cfg.layer_types.count("full_attention"),
            "sliding_attention": cfg.layer_types.count("sliding_attention"),
            "dense": cfg.mlp_layer_types.count("dense"),
        },
    }


if __name__ == "__main__":
    print(main())
