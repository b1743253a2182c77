"""CIFAR-style basic-block ResNet forward FLOPs per sample from its layer table."""

from __future__ import annotations

from . import conv_flops


def forward_flops(model: dict) -> int:
    h, w, c_in = model["image"]
    planes = model["stem"]
    total = conv_flops(h, w, 3, c_in, planes)
    for si, (width, n) in enumerate(zip(model["widths"], model["num_blocks"])):
        for i in range(n):
            stride = 2 if (si > 0 and i == 0) else 1
            h, w = h // stride, w // stride
            total += conv_flops(h, w, 3, planes, width) + conv_flops(h, w, 3, width, width)
            if stride != 1 or planes != width:
                total += conv_flops(h, w, 1, planes, width)
            planes = width
    return total + 2 * planes * (h // 4) * (w // 4) * model["num_classes"]
