"""How the router of `lfm2-8b-a1b.train-s8192` spreads its tokens, on the chip
at the cell's size: for several seeds, each routed layer's share of assignments
that go to the 8 held experts at the seeded weights (a quarter if all 32 are
equally popular) with its most loaded held expert over the mean, and then the
same over all routed layers step by step through the trainer's own step, which
shows whether training moves the router towards or away from the held experts.
The seeded weights of `benchmarks/reference/conv_attn_moe.py` (`conv_out` and
`wo` at 0.03 n / sqrt(fan_in), the selection bias at 0.01 n) were checked with
its output.  Through chiprun, one JSON line per row.

    chiprun -- python3 scripts/chip_conv_attn_routing.py [steps] [seed ...]
"""

from __future__ import annotations

import itertools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

SEEDS = (3310000301, 3310000302, 3310000303)


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import traffic_gen
    from benchmarks.job import seed_key
    from benchmarks.manifest import Manifest
    from deeplearning_cfn_tpu.models import conv_attn_moe
    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("chip_conv_attn_routing: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    steps = int(argv[0]) if argv else 40
    seeds = tuple(int(a) for a in argv[1:]) or SEEDS
    manifest = Manifest()
    config = manifest.config("lfm2-8b-a1b")
    traffic = manifest.json("traffic", "train-s8192")
    reference = manifest.module("reference", "conv_attn_moe")
    builder = manifest.module("builders", "conv_attn_moe")
    cfg = builder.model_config(config)
    first, count = cfg.routed.span
    built = None
    for seed in seeds:
        key = seed_key(seed)
        pool = traffic_gen.make_pool(traffic, config, seed)
        if built is None:
            built = builder.build(config, traffic, key, pool[0][0], reference)
            state, built.state = built.state, None
            trainer = built.trainer
            select = jax.jit(
                lambda p, t: conv_attn_moe.logits(cfg, p, t, trainer.mesh)["selected"]
            )
        else:
            state = built.fresh_state(key)
        with jax.set_mesh(trainer.mesh):
            chosen = np.asarray(select(state.params, jnp.asarray(pool[0][0])))
        layers = []
        for layer in chosen:
            load = np.bincount(layer.reshape(-1), minlength=cfg.n_experts)
            held = load[first : first + count]
            layers.append([round(float(held.sum() / load.sum()), 4),
                           round(float(held.max() / max(held.mean(), 1)), 3)])
        print(json.dumps({"seed": seed, "seeded_held_share": round(float(np.mean([b[0] for b in layers])), 4),
                          "held_share_and_max_over_mean_by_layer": layers}, allow_nan=False),
              flush=True)
        rows = []
        for step, (x, y) in zip(range(steps), itertools.cycle(pool)):
            x, y = (jax.device_put(a, trainer.batch_sharding) for a in (x, y))
            state, metrics = trainer.train_step(state, x, y)
            rows.append((metrics["loss"], metrics["counters"]))
        for step, (loss, c) in enumerate(jax.device_get(rows)):
            if step < 4 or step % 4 == 3:
                print(json.dumps({
                    "seed": seed, "step": step + 1, "loss": round(float(loss), 4),
                    "held_share": round(float(c["moe.assignments_held"] / c["moe.assignments"]), 4),
                    "load_max_over_mean": round(float(c["moe.expert_load_max"] / c["moe.expert_load_mean"]), 3),
                    "rows_run_over_held": round(float(c["moe.rows_run"] / c["moe.assignments_held"]), 3),
                    "slots_read_over_held": round(float(c["moe.slots_read"] / c["moe.assignments_held"]), 3),
                }, allow_nan=False), flush=True)
        del state, rows
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
