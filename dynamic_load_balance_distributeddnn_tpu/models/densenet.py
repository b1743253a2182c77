"""DenseNet-BC with GroupNorm (reference: Net/Densenet.py).

Constructors 121/169/201/161 mirror Net/Densenet.py:87-100; `-m densenet`
selects DenseNet-121 with growth 32 (dbs.py:353) — the model of the canonical
README recipe and the benchmark north star.

The dense block is the literal per-layer channel concat, the reference shape
(``torch.cat([out, x], 1)``, Net/Densenet.py:20): XLA:TPU fuses that chain.
"""

from __future__ import annotations

import math
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.models.common import group_norm


class DenseBottleneck(nn.Module):
    """GN→relu→1×1 conv→GN→relu→3×3 conv producing ``growth_rate`` new
    channels (Net/Densenet.py:9-21). The concat with the input lives in
    ``DenseNet`` (see module docstring); this module returns only the new
    features."""

    growth_rate: int

    @nn.compact
    def __call__(self, x):
        in_planes = x.shape[-1]
        out = nn.Conv(4 * self.growth_rate, (1, 1), use_bias=False)(
            group_norm(in_planes, relu=True)(x)
        )
        out = nn.Conv(self.growth_rate, (3, 3), padding=1, use_bias=False)(
            group_norm(4 * self.growth_rate, relu=True)(out)
        )
        return out


class Transition(nn.Module):
    out_planes: int

    @nn.compact
    def __call__(self, x):
        in_planes = x.shape[-1]
        out = nn.Conv(self.out_planes, (1, 1), use_bias=False)(
            group_norm(in_planes, relu=True)(x)
        )
        return nn.avg_pool(out, (2, 2), strides=(2, 2))


class DenseNet(nn.Module):
    nblocks: Sequence[int]
    growth_rate: int = 12
    reduction: float = 0.5
    num_classes: int = 10

    def _dense_block(self, x, nblock: int):
        """One dense block; returns the full-width feature map equal to the
        reference's nested ``cat([out, x], C)`` chain."""
        for _ in range(nblock):
            out = DenseBottleneck(growth_rate=self.growth_rate)(x)
            # NHWC concat on channels (reference cats on dim 1 in NCHW,
            # Net/Densenet.py:20)
            x = jnp.concatenate([out, x], axis=-1)
        return x

    @nn.compact
    def __call__(self, x, train: bool = False):
        g = self.growth_rate
        num_planes = 2 * g
        x = nn.Conv(num_planes, (3, 3), padding=1, use_bias=False)(x)
        for bi, nblock in enumerate(self.nblocks):
            x = self._dense_block(x, nblock)
            num_planes += nblock * g
            if bi != len(self.nblocks) - 1:
                out_planes = int(math.floor(num_planes * self.reduction))
                x = Transition(out_planes=out_planes)(x)
                num_planes = out_planes
        x = group_norm(num_planes, relu=True)(x)
        x = nn.avg_pool(x, (4, 4), strides=(4, 4))
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(self.num_classes)(x)


def DenseNet121(num_classes=10, **kw):
    return DenseNet((6, 12, 24, 16), growth_rate=32, num_classes=num_classes, **kw)


def DenseNet169(num_classes=10, **kw):
    return DenseNet((6, 12, 32, 32), growth_rate=32, num_classes=num_classes, **kw)


def DenseNet201(num_classes=10, **kw):
    return DenseNet((6, 12, 48, 32), growth_rate=32, num_classes=num_classes, **kw)


def DenseNet161(num_classes=10, **kw):
    return DenseNet((6, 12, 36, 24), growth_rate=48, num_classes=num_classes, **kw)
