"""On-device image augmentation.

The reference augments on the host through torchvision transforms
(RandomCrop(32, padding=4), RandomHorizontalFlip, Normalize —
dataloader.py:72-77). A per-image Python loop is exactly what a TPU host
should not be doing, so here the raw uint8 batch is shipped to the device and
the crop/flip/normalize run inside the jitted train step.

The crop is written over the whole batch, never per row: each of the
``2*pad + 1`` possible offsets along an axis is a static slice of the padded
batch, kept for the rows that drew it. A per-row dynamic slice under
``vmap`` is a gather, and XLA:TPU lowers that gather to a ``while`` over the
rows, one image a trip: a third of the device's time at 4,096 rows
(PERF.md, PR 25). Static slices and selects stay elementwise, so the batch
keeps the lane dimension XLA gives it and there is no loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def normalize_images(x_u8: jnp.ndarray, mean, std) -> jnp.ndarray:
    """uint8 NHWC -> float32 normalized with dataset stats
    (dataloader.py:63/76/91)."""
    x = x_u8.astype(jnp.float32) / 255.0
    mean = jnp.asarray(mean, dtype=jnp.float32)
    std = jnp.asarray(std, dtype=jnp.float32)
    return (x - mean) / std


def _crop_axis(xp: jnp.ndarray, off: jnp.ndarray, size: int, axis: int) -> jnp.ndarray:
    """``size`` entries of ``xp`` along ``axis`` from each row's own ``off``:
    every possible offset as a static slice, selected by a per-row mask. A
    pure selection, so the result is the per-row crop bit for bit."""
    off = off.reshape((-1,) + (1,) * (xp.ndim - 1))
    out = jax.lax.slice_in_dim(xp, 0, size, axis=axis)
    for k in range(1, xp.shape[axis] - size + 1):
        out = jnp.where(off == k, jax.lax.slice_in_dim(xp, k, k + size, axis=axis), out)
    return out


def augment_images(
    x_u8: jnp.ndarray,
    rng: jax.Array,
    mean,
    std,
    pad: int = 4,
    flip: bool = True,
) -> jnp.ndarray:
    """Random crop (with ``pad`` px reflection-free zero padding) + horizontal
    flip + normalize, one independent draw per example."""
    b, h, w, _ = x_u8.shape
    k_crop, k_flip = jax.random.split(rng)
    x = normalize_images(x_u8, mean, std)
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    offs = jax.random.randint(k_crop, (b, 2), 0, 2 * pad + 1)
    x = _crop_axis(xp, offs[:, 0], h, axis=1)
    x = _crop_axis(x, offs[:, 1], w, axis=2)
    if flip:
        do = jax.random.bernoulli(k_flip, 0.5, (b,))
        x = jnp.where(do[:, None, None, None], x[:, :, ::-1, :], x)
    return x
