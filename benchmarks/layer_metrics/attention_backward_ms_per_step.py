"""Kernels: device time of the flash attention's backward pass (plain XLA
under the scope `attn_bwd` in `ops/pallas_attention.py`), per executed
program of the traced window on device 0, in milliseconds."""

from benchmarks import scope_reduce


def read(run: dict) -> float | None:
    out = scope_reduce.reduced(run)
    if out is None or not out["programs"] or not out["attention_backward_s"]:
        return None
    return 1e3 * out["attention_backward_s"] / out["programs"]
