"""Bytes and FLOPs of the selective scan, for its roofline: `tokens` tokens
through Mamba-1's recurrence at `channels` channels and a state of `state` a
channel, B and C shared by all channels.  The count reads the work of the
*recurrence*, the fewest any form does, whatever implements it (a `lax.scan`, an
associative scan, a kernel): a form that does more (a history of states, B and
C spread over lanes, a forward pass run again inside the backward) is not
credited for it, so no implementation can read over 100%.

FLOPs: a channel and token updates its state [N] (a multiply for the decay and
a multiply-add for dt x B, counted 2) and reads it out against C (2): 4 N
forward; the backward pass twice that.  Bytes, the least a pass can move: a
forward pass reads x, B and C in `itemsize` bytes and dt in float32 and writes
y once; a backward pass reads those and y's gradient and writes the four
gradients (dt's in float32).  (The chunks' boundary states, 64 x 0.3 MB a
sequence, stay out: they are the implementation's.)"""

from __future__ import annotations


def flops(
    tokens: int, channels: int, state: int, forward_passes: int = 1, backward_passes: int = 1
) -> float:
    return 4.0 * state * channels * tokens * (forward_passes + 2 * backward_passes)


def bytes_moved(
    tokens: int, channels: int, state: int, forward_passes: int = 1, backward_passes: int = 1,
    itemsize: int = 2,
) -> float:
    read = (channels + 2 * state) * itemsize + channels * 4  # x, B, C; dt in float32
    forward = read + channels * itemsize  # y written
    backward = read + channels * itemsize + read  # those and dy read; four gradients written
    return float((forward_passes * forward + backward_passes * backward) * tokens)
