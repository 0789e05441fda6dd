"""Reads the two numbers every limit in `limits/` is set from, for one
cell, over many seeds in one process:

- *sound*: the program's first steps (the same `Trainer`, the same
  `fit`, the cell's batch) against the float32 reference;
- *control*: the reference computed in the nearest precision below the
  configuration's (fp8 operands for bfloat16), put in the program's place.

A limit belongs above the largest sound value and below the smallest
control value; the control has to fail one of a cell's numbers, not each.
`python -m benchmarks.control --workload <cell> --seeds 101 102 ...`; on the
chip it needs no measured window.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import math
import sys
import time


NUMBERS = ("loss_gap", "grad_norm_gap", "grad_sketch_gap", "head_sketch_gap", "update_norm_gap")
OPEN_LIMITS = dict.fromkeys(NUMBERS, math.inf)


class CellReader:
    """Reads seed after seed of one cell through one trainer, so that the
    step compiles once."""

    def __init__(self, manifest, workload: str):
        cell = manifest.workload(workload)
        self.config = manifest.config(cell["config"])
        self.traffic = manifest.json("traffic", cell["traffic"])
        self.builder = manifest.module("builders", self.config["kind"])
        self.reference = manifest.module("reference", self.config["kind"])
        self.steps = int(self.traffic["check_steps"])
        self.built = None

    def read(self, seed: int, control: bool = True) -> dict:
        """One seed's rows: sound (program against reference) and control
        (fp8 against reference), as check.compare gives them, no limit
        applied."""
        import jax

        from benchmarks import check, traffic_gen
        from benchmarks.job import seed_key
        from benchmarks.probe import StateProbe
        from deeplearning_cfn_tpu.train.data import Batch

        key = seed_key(seed)
        pool = traffic_gen.make_pool(self.traffic, self.config, seed)
        if self.built is None:
            self.built = self.builder.build(
                self.config, self.traffic, key, pool[0][0], self.reference
            )
            state, self.built.state = self.built.state, None
        else:
            state = self.built.fresh_state(key)
        probe = StateProbe(self.built, key, self.steps)
        batches = (Batch(x, y) for x, y in itertools.cycle(pool))
        trainer = self.built.trainer
        state, losses = trainer.fit(state, batches, steps=self.steps, checkpointer=probe)
        program = {"loss": losses, **probe.readings()}
        sharding = trainer.batch_sharding if len(jax.devices()) > 1 else None
        del state, probe
        gc.collect()
        t = time.perf_counter()
        followed = self.reference.follow(
            key, self.config, pool, self.steps, batch_sharding=sharding
        )
        out = {
            "seed": seed,
            "reference_seconds": time.perf_counter() - t,
            "sound": check.compare(program, followed, OPEN_LIMITS),
        }
        self.last = {"seed": seed, "program": program, "reference": followed}
        if control:
            lowered = self.reference.follow(
                key, self.config, pool, self.steps, precision="fp8", batch_sharding=sharding
            )
            out["control"] = check.compare(lowered, followed, OPEN_LIMITS)
            self.last["control"] = lowered
        return out


def main(argv: list[str] | None = None) -> int:
    from benchmarks.manifest import DEFAULT, Manifest

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, default=3,
                   help="how many of the seeds also run the control")
    p.add_argument("--dump", default=None,
                   help="a .jsonl file for every seed's losses and norms leaf by leaf")
    args = p.parse_args(argv)
    import jax

    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    manifest = Manifest(DEFAULT)
    reader = CellReader(manifest, args.workload)
    rows = []
    for n, seed in enumerate(args.seeds):
        row = reader.read(seed, control=n < args.control_seeds)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.dump:
            with open(args.dump, "a") as f:
                f.write(json.dumps(reader.last) + "\n")
    summary = {}
    for name in NUMBERS:
        sound = [r["value"] for row in rows for r in row["sound"] if r["name"] == name]
        low = [r["value"] for row in rows for r in row.get("control", []) if r["name"] == name]
        summary[name] = {
            "sound_max": max(sound), "control_min": min(low) if low else None,
            "sound": sound, "control": low,
        }
    print(json.dumps({"workload": args.workload, "device": str(jax.devices()[0].device_kind), "summary": summary}))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
