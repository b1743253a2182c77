"""CIFAR-style ResNet of basic blocks with GroupNorm (He et al. 2016; the
paper's ``Net/Resnet.py``): 3x3 stem of 64 channels with GN-ReLU; four stages
of ``num_blocks`` basic blocks at ``widths`` channels, the first block of
stages 2-4 at stride 2; a block is 3x3 conv-GN-ReLU-3x3 conv-GN plus the
shortcut (1x1 conv-GN where stride or width changes), then ReLU; 4x4 average
pool and the classifier.

Parameters arrive as the tree the program's model keeps them in."""

from __future__ import annotations

import jax.numpy as jnp

from .common import avg_pool, conv, dense, group_norm


def forward(params, x, model: dict, precision: str = "f32"):
    p = params["params"]
    x = group_norm(conv(x, p["Conv_0"]["kernel"], pad=1, precision=precision),
                   p["GroupNorm_0"], relu=True)
    block = 0
    for si, (width, n) in enumerate(zip(model["widths"], model["num_blocks"])):
        for i in range(n):
            q = p[f"BasicBlock_{block}"]
            block += 1
            stride = 2 if (si > 0 and i == 0) else 1
            out = conv(x, q["Conv_0"]["kernel"], stride=stride, pad=1, precision=precision)
            out = group_norm(out, q["GroupNorm_0"], relu=True)
            out = group_norm(conv(out, q["Conv_1"]["kernel"], pad=1, precision=precision),
                             q["GroupNorm_1"])
            if stride != 1 or x.shape[-1] != width:
                x = group_norm(conv(x, q["Conv_2"]["kernel"], stride=stride,
                                    precision=precision), q["GroupNorm_2"])
            x = jnp.maximum(out + x, 0.0)
    return dense(avg_pool(x, 4).reshape(x.shape[0], -1), p["Dense_0"], precision)


def param_shapes(model: dict):
    """The parameter tree's shapes (float32), for a run that has no program
    to ask."""
    import jax

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def gn(c):
        return {"bias": f32(c), "scale": f32(c)}

    planes = model["stem"]
    p = {"Conv_0": {"kernel": f32(3, 3, model["image"][2], planes)}, "GroupNorm_0": gn(planes)}
    block, side = 0, model["image"][0]
    for si, (width, n) in enumerate(zip(model["widths"], model["num_blocks"])):
        for i in range(n):
            stride = 2 if (si > 0 and i == 0) else 1
            side //= stride
            q = {"Conv_0": {"kernel": f32(3, 3, planes, width)}, "GroupNorm_0": gn(width),
                 "Conv_1": {"kernel": f32(3, 3, width, width)}, "GroupNorm_1": gn(width)}
            if stride != 1 or planes != width:
                q["Conv_2"] = {"kernel": f32(1, 1, planes, width)}
                q["GroupNorm_2"] = gn(width)
            p[f"BasicBlock_{block}"] = q
            block += 1
            planes = width
    side //= 4
    p["Dense_0"] = {"kernel": f32(planes * side * side, model["num_classes"]),
                    "bias": f32(model["num_classes"])}
    return {"params": p}
