"""FLOPs and bytes of one backward pass of the flash attention (all of its
kernels together), for its roofline: causal attention of B sequences of S
tokens, H query heads sharing KV key/value heads of size hd, in a 2-byte type.

The least the algorithm needs, not what an implementation spends: five block
matmuls per score (q k^T to recompute the probabilities, dout v^T, and the
three products that give dv, dk, dq), 2*hd each, over the causal half of
S x S.  A design that splits the pass into a dk/dv kernel and a dq kernel
recomputes two of them (seven), and is measured against the same five: its
share then cannot pass 5/7 of what its tiles reach, and can never pass 100%.
Bytes: q, k, v, out, dout read once and dq, dk, dv written once, plus the
float32 log-sum-exp read and the float32 delta = rowsum(out * dout) written
and read."""

from __future__ import annotations

BLOCK_MATMULS = 5


def flops(b: int, s: int, h: int, hd: int) -> float:
    return 2.0 * BLOCK_MATMULS * hd * b * h * s * s / 2


def bytes_moved(b: int, s: int, h: int, kv: int, hd: int, itemsize: int = 2) -> float:
    tensors = b * s * hd * (4 * h + 4 * kv) * itemsize  # q, out, dout, dq; k, v, dk, dv
    statistics = b * h * s * 4 * 3  # lse read, delta written and read
    return float(tensors + statistics)
