"""How the seeded router of `glm-4.7-flash.train-s8192` spreads its tokens, on
the chip at the cell's size: for several seeds and several scales of the
attention's output projection, each routed block's share of assignments that
go to the 16 held experts (a quarter if all 64 are equally popular) and its
most loaded expert over the mean.  The seeded weights of
`benchmarks/reference/mla_moe.py` (`wo` at 0.03 n / sqrt(fan_in), the selection
bias at 0.01 n) were chosen from its output.  One forward pass a row, no
training; through chiprun, one JSON line per row.

    chiprun -- python3 scripts/chip_routing_balance.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

SEEDS = (3260000301, 3260000302, 3260000303, 3260000304)
WO_SCALES = (1.0, 0.25, 0.1, 0.03)  # of n / sqrt(fan_in)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import traffic_gen
    from benchmarks.job import seed_key
    from benchmarks.manifest import Manifest
    from deeplearning_cfn_tpu.models import mla_moe

    if jax.devices()[0].platform != "tpu":
        print("chip_routing_balance: needs a TPU", file=sys.stderr)
        return 1
    manifest = Manifest()
    config = manifest.config("glm-4.7-flash")
    traffic = manifest.json("traffic", "train-s8192")
    reference = manifest.module("reference", "mla_moe")
    builder = manifest.module("builders", "mla_moe")
    cfg = builder.model_config(config)
    drawn = float(np.asarray(reference.init_leaf(jax.random.key(0), "dense/0/wo", config)).std())
    drawn *= np.sqrt(cfg.n_heads * cfg.v_head_dim)  # the file's own scale of `wo`

    @jax.jit
    def selected(key, scale, tokens, targets):
        flat = reference.init_params(key, config)
        flat = {
            k: (v * scale).astype(v.dtype) if k.endswith("/wo") else v for k, v in flat.items()
        }
        params = builder.program_tree(flat, cfg, reference)
        return mla_moe.logits(cfg, params, tokens, targets)["selected"]

    first, count = cfg.routed.span
    for seed in SEEDS:
        x, y = traffic_gen.make_pool(traffic, config, seed)[0]
        for wo in WO_SCALES:
            chosen = np.asarray(selected(seed_key(seed), wo / drawn, jnp.asarray(x), jnp.asarray(y)))
            blocks = []
            for block in chosen:
                load = np.bincount(block.reshape(-1), minlength=cfg.n_routed_experts)
                held = load[first : first + count]
                blocks.append([round(float(held.sum() / load.sum()), 4),
                               round(float(held.max() / max(held.mean(), 1)), 3)])
            share = float(np.mean([b[0] for b in blocks]))
            print(json.dumps({"seed": seed, "wo_scale": wo, "held_share": round(share, 4),
                              "held_share_and_max_over_mean_by_block": blocks},
                             allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
