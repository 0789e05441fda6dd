"""Model FLOPs of one training example for configurations of kind `resnet`:
forward and backward, no recomputation.

A convolution of a kh x kw kernel from cin to cout channels onto an
Hout x Wout map takes kh*kw*cin*cout*Hout*Wout multiply-adds, two FLOPs
each; the backward pass computes the gradient with respect to the input
and to the kernel, each as large again, so training is three times the
forward pass.  BatchNorm, ReLU, pooling and the loss are not matmul work
and are left out, as is the first convolution's input gradient, which is
never computed (0.24 of 24.6 GFLOP at 224 px)."""

from __future__ import annotations

import math


def forward_macs(config: dict) -> int:
    size = int(config["image_size"])
    f = int(config["num_filters"])
    hw = math.ceil(size / 2)
    macs = 7 * 7 * 3 * f * hw * hw
    hw = math.ceil(hw / 2)  # max-pool
    cin = f
    for i, blocks in enumerate(config["stage_sizes"]):
        width = f * 2**i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = math.ceil(hw / stride)
            macs += cin * width * hw * hw  # 1x1 at the input resolution
            macs += 9 * width * width * out * out  # the strided 3x3 (v1.5)
            macs += width * 4 * width * out * out
            if cin != 4 * width or stride != 1:
                macs += cin * 4 * width * out * out
            cin, hw = 4 * width, out
    return macs + cin * int(config["num_classes"])


def per_example(config: dict, traffic: dict) -> float:
    first_conv_input_grad = 2 * 7 * 7 * 3 * int(config["num_filters"]) * math.ceil(
        int(config["image_size"]) / 2
    ) ** 2
    return 3 * 2 * forward_macs(config) - first_conv_input_grad
