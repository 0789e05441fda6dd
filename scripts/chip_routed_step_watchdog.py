"""Does the step of `nemotron-3-super-120b-a12b.train-s8192x1` finish on the
chip?  The cell's trainer at the cell's widths and tokens over a shorter
pattern of blocks (two `EM` units and the attention block by default: a
rematerialised routed block inside a `scan` of two), compiled, then three steps
under a watchdog thread: where a step does not come back within the limit the
script says so and exits 3, in about two minutes all told where a hung cell run
holds the chip until its time limit.  PR 48 found a step that never finishes
with `ops/moe.routed_experts`'s weighted pass over a token's live slots alone,
though the layer by itself ran (PERF.md section 6, PR 48; ROADMAP S3 (a)): run
this before a cell on any change to the routed layer's slots.  Through
chiprun, one JSON line a row.

    chiprun -- python3 scripts/chip_routed_step_watchdog.py [pattern] [limit seconds]
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CONFIG, TRAFFIC = "nemotron-3-super-120b-a12b", "train-s8192x1"


def main(argv: list[str]) -> int:
    import jax

    from benchmarks.manifest import Manifest
    from deeplearning_cfn_tpu.models import ssm_attn_moe
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.metrics import json_safe
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    if jax.devices()[0].platform != "tpu":
        print("chip_routed_step_watchdog: needs a TPU", file=sys.stderr)
        return 1
    pattern = argv[0] if argv else "EMEM*"
    limit = float(argv[1]) if len(argv) > 1 else 45.0
    manifest = Manifest()
    config, traffic = manifest.config(CONFIG), manifest.json("traffic", TRAFFIC)
    cfg = manifest.module("builders", config["kind"]).model_config(config)
    cfg = dataclasses.replace(cfg, pattern=pattern)
    mesh = build_mesh(MeshSpec.fsdp_parallel(1), jax.devices()[:1])
    trainer = ssm_attn_moe.make_trainer(cfg, mesh, TrainerConfig(
        strategy="fsdp", optimizer="adamw", learning_rate=config["learning_rate"],
        weight_decay=config["weight_decay"], grad_clip_norm=config["grad_clip_norm"],
        log_every=traffic["log_every"]))
    shape = (traffic["global_batch"], traffic["seq_len"])
    tokens = jax.random.randint(jax.random.key(1), shape, 0, cfg.vocab_size)
    state = jax.block_until_ready(trainer.init(jax.random.key(0), tokens))
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step = trainer.step_fn.lower(state, tokens, tokens).compile()
    print(json.dumps({"pattern": pattern, "compile_s": round(time.perf_counter() - t0, 1)},
                     allow_nan=False), flush=True)
    came_back = threading.Event()

    def watch():
        while True:
            came_back.clear()
            if not came_back.wait(limit):
                print(json.dumps({"pattern": pattern, "hung": True, "after_s": limit},
                                 allow_nan=False), flush=True)
                os._exit(3)  # the main thread is inside block_until_ready

    threading.Thread(target=watch, daemon=True).start()
    for i in range(3):
        t0 = time.perf_counter()
        state, metrics = step(state, tokens, tokens)
        loss = float(metrics["loss"])
        came_back.set()
        print(json.dumps(json_safe({
            "pattern": pattern, "step": i, "s": round(time.perf_counter() - t0, 2), "loss": loss,
            "counters": {k: float(v) for k, v in metrics["counters"].items()},
        }), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)  # past the watchdog's thread
