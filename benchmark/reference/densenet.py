"""DenseNet-BC with GroupNorm (Huang et al. 2017; the paper's ``Net/Densenet.py``):
3x3 stem of ``2 * growth`` channels; dense blocks of bottleneck layers
(GN-ReLU-1x1 conv to ``4 * growth``, GN-ReLU-3x3 conv to ``growth``, the new
channels concatenated IN FRONT of the input); between blocks a transition
(GN-ReLU-1x1 conv to ``reduction`` of the channels, 2x2 average pool); then
GN-ReLU, 4x4 average pool and the classifier.

Parameters arrive as the tree the program's model keeps them in: layers of a
kind are numbered in order of use (``DenseBottleneck_17``, ``Transition_1``)."""

from __future__ import annotations

import jax.numpy as jnp

from .common import avg_pool, conv, dense, group_norm


def forward(params, x, model: dict, precision: str = "f32"):
    p = params["params"]
    x = conv(x, p["Conv_0"]["kernel"], pad=1, precision=precision)
    layer = 0
    for bi, nblock in enumerate(model["nblocks"]):
        for _ in range(nblock):
            q = p[f"DenseBottleneck_{layer}"]
            layer += 1
            out = conv(group_norm(x, q["GroupNorm_0"], relu=True), q["Conv_0"]["kernel"],
                       precision=precision)
            out = conv(group_norm(out, q["GroupNorm_1"], relu=True), q["Conv_1"]["kernel"],
                       pad=1, precision=precision)
            x = jnp.concatenate([out, x], axis=-1)
        if bi != len(model["nblocks"]) - 1:
            q = p[f"Transition_{bi}"]
            x = conv(group_norm(x, q["GroupNorm_0"], relu=True), q["Conv_0"]["kernel"],
                     precision=precision)
            x = avg_pool(x, 2)
    x = avg_pool(group_norm(x, p["GroupNorm_0"], relu=True), 4)
    return dense(x.reshape(x.shape[0], -1), p["Dense_0"], precision)


def param_shapes(model: dict):
    """The parameter tree's shapes (float32), for a run that has no program
    to ask."""
    import math

    import jax

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def gn(c):
        return {"bias": f32(c), "scale": f32(c)}

    g, c_in = model["growth_rate"], model["image"][2]
    planes = 2 * g
    p = {"Conv_0": {"kernel": f32(3, 3, c_in, planes)}}
    layer = 0
    for bi, nblock in enumerate(model["nblocks"]):
        for _ in range(nblock):
            p[f"DenseBottleneck_{layer}"] = {
                "GroupNorm_0": gn(planes), "Conv_0": {"kernel": f32(1, 1, planes, 4 * g)},
                "GroupNorm_1": gn(4 * g), "Conv_1": {"kernel": f32(3, 3, 4 * g, g)},
            }
            layer += 1
            planes += g
        if bi != len(model["nblocks"]) - 1:
            out = int(math.floor(planes * model["reduction"]))
            p[f"Transition_{bi}"] = {"GroupNorm_0": gn(planes),
                                     "Conv_0": {"kernel": f32(1, 1, planes, out)}}
            planes = out
    side = model["image"][0] // 2 ** (len(model["nblocks"]) - 1) // 4
    p["GroupNorm_0"] = gn(planes)
    p["Dense_0"] = {"kernel": f32(planes * side * side, model["num_classes"]),
                    "bias": f32(model["num_classes"])}
    return {"params": p}
