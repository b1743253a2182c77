"""The on-device augmentation (ops/augment.py): the whole-batch crop is the
per-row crop bit for bit, draws what the benchmark's reference draws, and
holds no per-row primitive (gather, dynamic_slice, while, scan) that XLA:TPU
would lower to a loop over the rows."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.ops.augment import augment_images, normalize_images

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MEAN, STD = (0.4914, 0.4822, 0.4465), (0.2023, 0.1994, 0.2010)
BATCHES = (1, 32, 128, 1024)
KEYS = (0, 7, 2147484001)


def per_row_augment(x_u8, rng, mean, std, pad=4, flip=True):
    """The formulation ``augment_images`` had before PR 25, kept here as the
    reference: one ``dynamic_slice`` per row under ``vmap``."""
    b, h, w, _ = x_u8.shape
    k_crop, k_flip = jax.random.split(rng)
    x = normalize_images(x_u8, mean, std)
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    offs = jax.random.randint(k_crop, (b, 2), 0, 2 * pad + 1)

    def crop_one(img, off):
        return jax.lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, img.shape[-1]))

    x = jax.vmap(crop_one)(xp, offs)
    if flip:
        do = jax.random.bernoulli(k_flip, 0.5, (b,))
        x = jnp.where(do[:, None, None, None], x[:, :, ::-1, :], x)
    return x


def images(b, h=32, w=32, c=3, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (b, h, w, c), dtype=np.uint8)


def draws(rng, b, pad):
    """The offsets and flips ``rng`` gives ``b`` rows, drawn as the function
    draws them."""
    k_crop, k_flip = jax.random.split(rng)
    offs = np.asarray(jax.random.randint(k_crop, (b, 2), 0, 2 * pad + 1))
    do = np.asarray(jax.random.bernoulli(k_flip, 0.5, (b,)))
    return offs, do


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("flip", [True, False], ids=["flip", "noflip"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("b", BATCHES)
def test_bitwise_equal_to_the_per_row_crop(b, dtype, flip):
    x = images(b, seed=b)
    for seed in KEYS:
        rng = jax.random.PRNGKey(seed)
        new = augment_images(x, rng, MEAN, STD, pad=4, flip=flip).astype(dtype)
        old = per_row_augment(x, rng, MEAN, STD, pad=4, flip=flip).astype(dtype)
        assert new.shape == old.shape == x.shape and new.dtype == old.dtype
        assert np.array_equal(bits(new), bits(old)), (b, seed)


@pytest.mark.parametrize(
    "b,h,w,c,pad",
    [(8, 28, 20, 1, 2), (5, 16, 32, 3, 1), (4, 8, 8, 3, 0), (16, 32, 32, 3, 6)],
    ids=["28x20x1-pad2", "16x32x3-pad1", "8x8x3-pad0", "32x32x3-pad6"],
)
def test_bitwise_equal_on_other_shapes_and_pads(b, h, w, c, pad):
    x = images(b, h, w, c, seed=pad)
    mean, std = MEAN[:c], STD[:c]
    rng = jax.random.PRNGKey(11)
    new = augment_images(x, rng, mean, std, pad=pad)
    old = per_row_augment(x, rng, mean, std, pad=pad)
    assert np.array_equal(bits(new), bits(old))


@pytest.mark.parametrize("flip", [True, False], ids=["flip", "noflip"])
@pytest.mark.parametrize("b", (1, 32, 128))
def test_each_row_is_the_hand_crop_at_its_drawn_offset(b, flip):
    pad = 4
    x = images(b, seed=3)
    rng = jax.random.PRNGKey(5)
    got = np.asarray(augment_images(x, rng, MEAN, STD, pad=pad, flip=flip))
    offs, do = draws(rng, b, pad)
    norm = np.asarray(normalize_images(x, MEAN, STD))
    padded = np.zeros((b, 32 + 2 * pad, 32 + 2 * pad, 3), np.float32)
    padded[:, pad:-pad, pad:-pad] = norm
    for r in range(b):
        oy, ox = offs[r]
        want = padded[r, oy : oy + 32, ox : ox + 32]
        if flip and do[r]:
            want = want[:, ::-1]
        assert np.array_equal(got[r], want), r


@pytest.mark.parametrize("b", (32, 1024))
def test_equals_the_benchmark_reference_on_the_same_key(b):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        from benchmark.reference import common
    except ImportError as e:
        pytest.skip(f"the benchmark's reference cannot be imported: {e}")
    mean, std = common.NORM_STATS["cifar10"]
    x = images(b, seed=9)
    for seed in KEYS:
        rng = common.step_key(seed % 2**26, 0, 1)
        ours = augment_images(x, rng, mean, std)
        theirs = common.augment(x, rng, mean, std)
        assert np.array_equal(bits(ours), bits(theirs)), seed


def primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    primitives(inner, found)
    return found


PER_ROW = {"gather", "dynamic_slice", "dynamic_update_slice", "while", "scan", "scatter"}


@pytest.mark.parametrize("flip", [True, False], ids=["flip", "noflip"])
@pytest.mark.parametrize("b", (32, 4096))
def test_no_per_row_primitive_in_the_jaxpr(b, flip):
    x = jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.uint8)
    rng = jax.random.PRNGKey(0)
    new = jax.make_jaxpr(lambda x, k: augment_images(x, k, MEAN, STD, flip=flip))(x, rng)
    assert not primitives(new.jaxpr) & PER_ROW
    assert {"slice", "select_n", "pad"} <= primitives(new.jaxpr)


def test_the_guard_sees_the_per_row_crop_as_a_gather():
    x = jax.ShapeDtypeStruct((32, 32, 32, 3), jnp.uint8)
    old = jax.make_jaxpr(lambda x, k: per_row_augment(x, k, MEAN, STD))(x, jax.random.PRNGKey(0))
    assert "gather" in primitives(old.jaxpr)


@pytest.mark.parametrize("axis", [0, 1], ids=["rows", "columns"])
@pytest.mark.parametrize("end", ["low", "high"])
def test_offsets_at_both_ends_show_the_zero_border(axis, end):
    """Offset 0 puts the ``pad`` zero lines of the padding first along that
    axis, offset ``2*pad`` puts them last; a flip mirrors the columns' side.
    Every normalised pixel is non-zero (no uint8 / 255 equals a channel's
    mean), so a zero line is the border and nothing else."""
    pad, b = 4, 1024
    x = images(b, seed=1)
    rng = jax.random.PRNGKey(2)
    got = np.asarray(augment_images(x, rng, MEAN, STD, pad=pad))
    offs, do = draws(rng, b, pad)
    rows = np.flatnonzero(offs[:, axis] == (0 if end == "low" else 2 * pad))
    assert len(rows) > 20
    first = end == "low"
    for r in rows:
        img = got[r] if axis == 0 else got[r].transpose(1, 0, 2)
        border_first = first if axis == 0 or not do[r] else not first
        border, inside = (img[:pad], img[pad]) if border_first else (img[-pad:], img[-pad - 1])
        assert not border.any(), r
        # the first line inside is image, but for the other axis's own border
        assert inside.all(axis=-1).sum() == 32 - abs(int(offs[r, 1 - axis]) - pad), r
