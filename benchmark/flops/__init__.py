"""FLOP functions, one per model family, found by the ``family`` key of a
configuration's ``model`` group. Each counts, from the layer table alone,
the multiply-adds of one sample's forward pass through the convolutions and
matrix products (2 FLOP each); normalisation, activations, pooling and the
loss are left out, as is everything a program adds of its own (recomputation,
padding rows, an injected load). A training step is 3 x forward."""

from __future__ import annotations

import importlib

TRAIN_OVER_FORWARD = 3


def conv_flops(h_out: int, w_out: int, k: int, c_in: int, c_out: int) -> int:
    return 2 * h_out * w_out * k * k * c_in * c_out


def forward_flops_per_sample(model: dict) -> int:
    return int(importlib.import_module(f"{__package__}.{model['family']}").forward_flops(model))


def train_flops_per_sample(model: dict) -> int:
    return TRAIN_OVER_FORWARD * forward_flops_per_sample(model)
