"""FLOPs and bytes of latent experts' grouped matmuls, for their roofline:
`assignments` rows (token-to-expert assignments to experts held here), each
through one expert of two matrices with relu^2 between, latent width l and
expert width m, in a 2-byte type.

FLOPs: two matmuls of l x m per row, 2 FLOPs a weight: 4 l m forward; the
backward pass computes the gradient with respect to the rows and to the
weights, each as large again.  A rematerialised forward pass is one more
forward pass; `passes` says how many forward and backward passes the events in
the denominator hold.
Bytes: the least a pass can move.  The held experts' weights are read once a
pass (a backward pass reads them and writes their gradient); a row is read and
its result written, l each (the width-m intermediate can stay on the chip),
and a backward pass reads the row and the result's gradient and writes the
row's gradient."""

from __future__ import annotations


def flops(assignments: float, l: int, m: int, forward_passes: int = 1, backward_passes: int = 1) -> float:
    return 4.0 * l * m * assignments * (forward_passes + 2 * backward_passes)


def bytes_moved(
    assignments: float, experts: int, l: int, m: int, forward_passes: int = 1,
    backward_passes: int = 1, itemsize: int = 2,
) -> float:
    weights = 2 * experts * l * m * itemsize
    forward = weights + 2 * assignments * l * itemsize
    backward = 2 * weights + 3 * assignments * l * itemsize
    return float(forward_passes * forward + backward_passes * backward)
