"""Bytes and FLOPs of the gated short convolution's core, for its roofline:
`tokens` tokens of `d` channels through `z = B * u`, a depthwise causal filter
of `taps` taps and the gate `C * c`, in a 2-byte type.  The count reads the
work, whatever implements it (fused XLA or a kernel).

Bytes, the least a pass can move: a forward pass reads B, C and u and writes
the result, 4 T d; a backward pass reads B, C, u and the result's gradient and
writes the three gradients, 7 T d (the filter and its gradient, `taps` x d, are
nothing beside them).  FLOPs: the product, `taps` multiply-adds and the gate,
2 * taps + 2 an element forward; the backward pass about twice that.  At two
bytes an element the core is memory-bound by three orders of magnitude."""

from __future__ import annotations


def bytes_moved(
    tokens: int, d: int, forward_passes: int = 1, backward_passes: int = 1, itemsize: int = 2
) -> float:
    return float((4 * forward_passes + 7 * backward_passes) * tokens * d * itemsize)


def flops(
    tokens: int, d: int, taps: int, forward_passes: int = 1, backward_passes: int = 1
) -> float:
    return float((2 * taps + 2) * tokens * d * (forward_passes + 2 * backward_passes))
