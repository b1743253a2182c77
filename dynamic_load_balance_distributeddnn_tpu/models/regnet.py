"""RegNetX/Y with SE blocks and GroupNorm (reference: Net/RegNet.py).

Constructors X_200MF / X_400MF / Y_400MF mirror Net/RegNet.py:108-141;
`-m regnet` selects RegNetY-400MF (dbs.py:359).
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.models.common import group_norm


class GroupedConv(nn.Module):
    """3×3 grouped convolution with an optional per-group decomposition.

    XLA:CPU pathologically compiles ``feature_group_count > 1`` convolutions
    — a single RegNetY-400MF fwd+bwd jit was observed 77+ minutes into one
    compile on the CPU tier (round 4), while XLA:TPU compiles the
    same graph in seconds. ``decompose=True`` emits ``groups`` plain convs
    over channel slices instead — that IS the definition of grouped
    convolution (each group is an independent conv), so the math is
    unchanged and the parameter is the same single fused ``kernel`` of shape
    ``(3, 3, in//groups, features)`` that ``nn.Conv(feature_group_count=g)``
    would create; only the emitted HLO differs.

    ``decompose=None`` (default) resolves at trace time: decompose iff the
    backend is CPU, overridable with DBS_DECOMPOSE_GROUPED_CONV=0/1.
    """

    features: int
    strides: int
    groups: int
    decompose: Optional[bool] = None

    @nn.compact
    def __call__(self, x):
        in_ch = x.shape[-1]
        assert in_ch % self.groups == 0 and self.features % self.groups == 0
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (3, 3, in_ch // self.groups, self.features),
        )
        kernel = kernel.astype(x.dtype)
        dec = self.decompose
        if dec is None:
            env = os.environ.get("DBS_DECOMPOSE_GROUPED_CONV", "")
            if env in ("0", "1"):
                dec = env == "1"
            else:
                dec = jax.default_backend() == "cpu"
        dn = jax.lax.conv_dimension_numbers(x.shape, kernel.shape, ("NHWC", "HWIO", "NHWC"))
        pad = ((1, 1), (1, 1))
        strides = (self.strides, self.strides)
        if not dec or self.groups == 1:
            return jax.lax.conv_general_dilated(
                x, kernel, strides, pad,
                feature_group_count=self.groups, dimension_numbers=dn,
            )
        in_g = in_ch // self.groups
        out_g = self.features // self.groups
        outs = [
            jax.lax.conv_general_dilated(
                x[..., g * in_g : (g + 1) * in_g],
                kernel[..., g * out_g : (g + 1) * out_g],
                strides, pad, dimension_numbers=dn,
            )
            for g in range(self.groups)
        ]
        return jnp.concatenate(outs, axis=-1)


class SE(nn.Module):
    """Squeeze-and-Excitation (Net/RegNet.py:10-23)."""

    se_planes: int

    @nn.compact
    def __call__(self, x):
        in_planes = x.shape[-1]
        s = jnp.mean(x, axis=(1, 2), keepdims=True)
        s = nn.relu(nn.Conv(self.se_planes, (1, 1))(s))
        s = nn.sigmoid(nn.Conv(in_planes, (1, 1))(s))
        return x * s


class RegNetBlock(nn.Module):
    w_out: int
    stride: int
    group_width: int
    bottleneck_ratio: float
    se_ratio: float

    @nn.compact
    def __call__(self, x):
        w_in = x.shape[-1]
        w_b = int(round(self.w_out * self.bottleneck_ratio))
        num_groups = w_b // self.group_width

        out = nn.Conv(w_b, (1, 1), use_bias=False)(x)
        out = group_norm(w_b, relu=True)(out)
        out = GroupedConv(features=w_b, strides=self.stride, groups=num_groups)(out)
        out = group_norm(w_b, relu=True)(out)
        if self.se_ratio > 0:
            out = SE(se_planes=int(round(w_in * self.se_ratio)))(out)
        out = nn.Conv(self.w_out, (1, 1), use_bias=False)(out)
        out = group_norm(self.w_out)(out)

        if self.stride != 1 or w_in != self.w_out:
            sc = nn.Conv(self.w_out, (1, 1), strides=self.stride, use_bias=False)(x)
            sc = group_norm(self.w_out)(sc)
        else:
            sc = x
        return nn.relu(out + sc)


class RegNet(nn.Module):
    cfg: Mapping
    num_classes: int = 10

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(64, (3, 3), padding=1, use_bias=False)(x)
        x = group_norm(64, relu=True)(x)
        for idx in range(4):
            depth = self.cfg["depths"][idx]
            width = self.cfg["widths"][idx]
            stride = self.cfg["strides"][idx]
            for i in range(depth):
                x = RegNetBlock(
                    w_out=width,
                    stride=stride if i == 0 else 1,
                    group_width=self.cfg["group_width"],
                    bottleneck_ratio=self.cfg["bottleneck_ratio"],
                    se_ratio=self.cfg["se_ratio"],
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


def RegNetX_200MF(num_classes=10):
    return RegNet(
        dict(
            depths=[1, 1, 4, 7],
            widths=[24, 56, 152, 368],
            strides=[1, 1, 2, 2],
            group_width=8,
            bottleneck_ratio=1,
            se_ratio=0,
        ),
        num_classes,
    )


def RegNetX_400MF(num_classes=10):
    return RegNet(
        dict(
            depths=[1, 2, 7, 12],
            widths=[32, 64, 160, 384],
            strides=[1, 1, 2, 2],
            group_width=16,
            bottleneck_ratio=1,
            se_ratio=0,
        ),
        num_classes,
    )


def RegNetY_400MF(num_classes=10):
    return RegNet(
        dict(
            depths=[1, 2, 7, 12],
            widths=[32, 64, 160, 384],
            strides=[1, 1, 2, 2],
            group_width=16,
            bottleneck_ratio=1,
            se_ratio=0.25,
        ),
        num_classes,
    )
