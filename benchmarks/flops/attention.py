"""FLOPs and bytes of one call of the flash-attention forward kernel, for
its roofline: causal attention of B sequences of S tokens, H query heads
sharing KV key/value heads of size hd, in a 2-byte type.

FLOPs: QK^T and PV, 2*hd each per score, over the causal half of S x S.
Bytes: the least the kernel can move, q, k, v read once and o written
once; the float32 log-sum-exp it keeps for the backward pass is counted
too."""

from __future__ import annotations


def flops(b: int, s: int, h: int, hd: int) -> float:
    return 2.0 * 2 * hd * b * h * s * s / 2


def bytes_moved(b: int, s: int, h: int, kv: int, hd: int, itemsize: int = 2) -> float:
    return float(b * s * hd * (2 * h + 2 * kv) * itemsize + b * h * s * 4)
