"""The one generator of training traffic: a traffic file's parameters and
a seed in, a pool of host batches out.

A pool of a few pregenerated batches is cycled for ever, so the host draws
nothing inside the measured window and every seed does the same amount of
work.  Images and labels are drawn directly as integers, tokens uniformly
over the vocabulary; the rows all differ.  The program receives only the
batches.
"""

from __future__ import annotations

import numpy as np


def make_pool(traffic: dict, config: dict, seed: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """`pool_batches` pairs (x, y) for a `train` traffic file."""
    if traffic["kind"] != "train":
        raise ValueError(f"traffic kind {traffic['kind']!r} is not 'train'")
    rng = np.random.default_rng(int(seed))
    batch = int(traffic["global_batch"])
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        if traffic["input"] == "images":
            size = int(config["image_size"])
            x = rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8)
            y = rng.integers(0, int(config["num_classes"]), (batch,), dtype=np.int32)
        elif traffic["input"] == "tokens":
            x = rng.integers(
                0, int(config["vocab_size"]), (batch, int(traffic["seq_len"])), dtype=np.int32
            )
            # Next-token targets; the wrapped last one is masked by the loss.
            y = np.roll(x, -1, axis=1)
        else:
            raise ValueError(f"unknown traffic input {traffic['input']!r}")
        pool.append((x, y))
    return pool
