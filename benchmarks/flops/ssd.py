"""Bytes and FLOPs of the state-space scan, for its roofline: `tokens` tokens
through Mamba-2's recurrence at `heads` heads of `head_dim` channels, `groups`
groups of B and C, a state of `state` a channel, in a 2-byte type.  The count
reads the work of the *recurrence*, the fewest any form does, whatever
implements it (the chunked matrix form in XLA, or a kernel): a form that does
more (a chunk's L matrix, C B^T) is not credited for it, so no implementation
can read over 100%.

FLOPs: a head and token updates its state [P, N] (a multiply for the decay
and a multiply-add for x (x) B, counted 2) and reads it out against C (2):
4 N P forward; the backward pass twice that.  Bytes, the least a pass can
move: a forward pass reads x, B, C and dt and writes y once; a backward pass
reads those and y's gradient and writes the four gradients.  (The chunks'
states, 64 x 4 MB a sequence, stay out: a fused form never writes them.)"""

from __future__ import annotations


def flops(
    tokens: int, heads: int, head_dim: int, state: int, forward_passes: int = 1,
    backward_passes: int = 1,
) -> float:
    return 4.0 * state * head_dim * heads * tokens * (forward_passes + 2 * backward_passes)


def bytes_moved(
    tokens: int, heads: int, head_dim: int, groups: int, state: int, forward_passes: int = 1,
    backward_passes: int = 1, itemsize: int = 2,
) -> float:
    x, bc, dt = heads * head_dim, 2 * groups * state, heads
    forward = (x + bc + dt) + x  # x, B, C, dt read; y written
    backward = (x + bc + dt) + x + (x + bc + dt)  # those and dy read; four gradients written
    return float((forward_passes * forward + backward_passes * backward) * tokens * itemsize)
