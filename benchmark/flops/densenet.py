"""DenseNet-BC forward FLOPs per sample from its layer table."""

from __future__ import annotations

import math

from . import conv_flops


def forward_flops(model: dict) -> int:
    h, w, c_in = model["image"]
    g = model["growth_rate"]
    planes = 2 * g
    total = conv_flops(h, w, 3, c_in, planes)
    for bi, nblock in enumerate(model["nblocks"]):
        for _ in range(nblock):
            total += conv_flops(h, w, 1, planes, 4 * g) + conv_flops(h, w, 3, 4 * g, g)
            planes += g
        if bi != len(model["nblocks"]) - 1:
            out = int(math.floor(planes * model["reduction"]))
            total += conv_flops(h, w, 1, planes, out)
            planes, h, w = out, h // 2, w // 2
    return total + 2 * planes * (h // 4) * (w // 4) * model["num_classes"]
