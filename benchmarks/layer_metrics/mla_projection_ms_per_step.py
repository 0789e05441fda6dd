"""Trainer: device time of the latent attention's projections around the
core (`attn/q_down`, `attn/q_up`, `attn/kv_down`, `attn/kv_up` with their latent
norms, and `attn/rope` with the concatenations that build the heads), per
executed program of the traced window on device 0, in milliseconds."""

from benchmarks import moe_reduce


def read(run: dict) -> float | None:
    return moe_reduce.scope_ms_per_step(
        run, ("attn",), ("q_down", "q_up", "kv_down", "kv_up", "rope")
    )
