"""FLOPs and bytes of the routed experts' grouped matmuls, for their roofline:
`assignments` rows (token-to-expert assignments to experts held here), each
through one expert's SwiGLU of hidden size d and width m, in a 2-byte type.

FLOPs: three matmuls of d x m per row, 2 FLOPs a weight: 6 d m forward; the
backward pass computes the gradient with respect to the rows and to the
weights, each as large again.  A rematerialised forward pass is one more
forward pass; `passes` says how many forward and backward passes the events in
the denominator hold.
Bytes: the least a pass can move.  The held experts' weights are read once a
pass (a backward pass reads them and writes their gradient); a row is read and
its result written, d each (the width-m intermediates can stay on the chip),
and a backward pass reads the row and the result's gradient and writes the
row's gradient."""

from __future__ import annotations


def flops(assignments: float, d: int, m: int, forward_passes: int = 1, backward_passes: int = 1) -> float:
    return 6.0 * d * m * assignments * (forward_passes + 2 * backward_passes)


def bytes_moved(
    assignments: float, experts: int, d: int, m: int, forward_passes: int = 1,
    backward_passes: int = 1, itemsize: int = 2,
) -> float:
    weights = 3 * experts * d * m * itemsize
    forward = weights + 2 * assignments * d * itemsize
    backward = 2 * weights + 3 * assignments * d * itemsize
    return float(forward_passes * forward + backward_passes * backward)
