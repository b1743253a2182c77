"""The plain reference of a training cell: forward, loss, gradient and the
SGD-with-momentum update in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision, no kernels, no scan, no sharding.

It imports nothing of the program. What it shares with the program is the
*recipe* a training job is defined by, copied under ``benchmark/`` so that
no later PR can move it. The part of the recipe that knows what a row is
(which rows or tokens a step trains on, their weights, a per-worker clip)
lives with its task, ``benchmark/tasks/<task>.py``; here are the numerics
every task shares, the images' crop with its key (``tests/test_augment.py``
holds the program's crop to them), the optimizer and the comparison. A
family's layer equations live beside this file (``densenet.py``,
``resnet.py``, ``transformer.py``) and are found by the ``family`` key of a
configuration's ``model`` group. A family may also bring two hooks:
``loss(params, x, y, weights, model, precision)`` (a token family whose
training loss has terms beside the next-token loss) and
``init_std(path, shape)`` (the draw of its leaves, ``harness.make_weights``).

``precision`` selects what the same equations are computed in:

``"f32"``   the reference.
``"fp8"``   the control: every convolution and matrix product sees its two
            inputs rounded to float8 (e4m3, one scale per tensor) — the
            nearest precision below the bfloat16 the configurations state.
            A comparison that cannot tell this from the reference is too
            loose to guard a cell.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
MOMENTUM = 0.9
GN_EPS = 1e-6  # flax.linen.GroupNorm's default, which the program's models use
NORM_STATS = {  # the per-channel statistics CIFAR-10 images are normalised by
    "cifar10": ((0.4914, 0.4822, 0.4465), (0.2023, 0.1994, 0.2010)),
}
_E4M3_MAX = 448.0


# ----------------------------------------------------------------- numerics


def _fp8(t):
    """Round ``t`` to float8 e4m3 under one scale for the tensor; gradients
    pass straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-12) / _E4M3_MAX
    q = (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return t + lax.stop_gradient(q - t)


def _bf16(t):
    return t + lax.stop_gradient(t.astype(jnp.bfloat16).astype(jnp.float32) - t)


def _operands(precision, *ts):
    if precision == "fp8":
        return tuple(_fp8(t) for t in ts)
    if precision == "bf16":  # what the configurations state, emulated: for tests
        return tuple(_bf16(t) for t in ts)
    if precision != "f32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return ts


def conv(x, kernel, stride=1, pad=0, precision="f32"):
    x, kernel = _operands(precision, x, kernel)
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def dense(x, p, precision="f32"):
    x, k = _operands(precision, x, p["kernel"])
    return jnp.dot(x, k, precision=HIGHEST) + p["bias"]


def einsum(spec, a, b, precision="f32"):
    """A product of two tensors (a projection, attention's scores), its
    inputs rounded as ``precision`` says."""
    a, b = _operands(precision, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def group_norm(x, p, relu=False, groups=32):
    """GroupNorm over (H, W, C/G) per sample and group, then scale and bias.
    The group count is ``gcd(32, C)``, as the program's models choose it."""
    n, h, w, c = x.shape
    g = math.gcd(groups, c)
    xg = x.reshape(n, h * w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 3), keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + GN_EPS)).reshape(n, h, w, c)
    y = y * p["scale"] + p["bias"]
    return jnp.maximum(y, 0.0) if relu else y


def avg_pool(x, k):
    n, h, w, c = x.shape
    return x.reshape(n, h // k, k, w // k, k, c).mean(axis=(2, 4))


def cross_entropy(logits, labels):
    """One loss per label: ``logits`` ``[..., classes]``, ``labels`` ``[...]``."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), axis=-1)
    return logz - gold[..., 0]


def family(model: dict):
    """The module that holds ``forward(params, x, model, precision)`` for the
    configuration's ``model["family"]``."""
    return importlib.import_module(f"{__package__}.{model['family']}")


def init_std(model: dict):
    """The family's ``init_std(path, shape)`` hook, or ``None``."""
    return getattr(family(model), "init_std", None)


# ------------------------------------------- the images' crop and its key


def augment(x_u8, key, mean, std, pad=4):
    """Normalise, zero-pad by ``pad``, crop at a random offset and flip with
    probability one half: one independent draw per row of ``x_u8``."""
    b, h, w, _ = x_u8.shape
    k_crop, k_flip = jax.random.split(key)
    x = (x_u8.astype(jnp.float32) / 255.0 - jnp.asarray(mean, jnp.float32)) / jnp.asarray(
        std, jnp.float32
    )
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    offs = jax.random.randint(k_crop, (b, 2), 0, 2 * pad + 1)
    x = jax.vmap(
        lambda img, off: lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, img.shape[-1]))
    )(xp, offs)
    flip = jax.random.bernoulli(k_flip, 0.5, (b,))
    return jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)


def step_key(job_seed: int, epoch: int, step: int):
    """The key of one augmentation draw, over all rows of a step (the workers
    share one chip and are drawn together): the job's seed and epoch, the
    chip's place in the job (0), the step."""
    base = jax.random.fold_in(jax.random.PRNGKey(0), jnp.int32(job_seed * 31 + epoch))
    return jax.random.fold_in(jax.random.fold_in(base, 0), jnp.int32(step))


# ------------------------------------------------------------ optimizer


@jax.jit
def sgd_step(params, trace, grads, lr):
    """SGD with momentum as ``optax.sgd`` does it: ``(trace, params)`` after
    one update."""
    return (
        jax.tree_util.tree_map(lambda t_, g_: g_ + MOMENTUM * t_, trace, grads),
        jax.tree_util.tree_map(
            lambda p_, t_, g_: p_ - lr * (g_ + MOMENTUM * t_), params, trace, grads
        ),
    )


@jax.jit
def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


# ------------------------------------------------------------ comparison


def leaf_norms(tree) -> np.ndarray:
    return np.array(
        [
            float(np.linalg.norm(np.asarray(leaf, np.float64).ravel()))
            for leaf in jax.tree_util.tree_leaves(tree)
        ]
    )


def tree_sub(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b
    )


def leaf_gaps(got: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """Gap between two norms of each kept leaf, measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some leaves' norms are all but nought)."""
    floor = float(np.median(ref[keep]))
    gap = np.abs(got - ref) / np.maximum(np.maximum(ref, floor), 1e-30)
    return gap[keep]


def compare(got: dict, ref: dict, params0) -> dict:
    """The numbers a cell's ``correct`` rests on. ``got`` and ``ref`` hold
    ``loss``, ``trace`` and ``params`` after the first epoch (``ref`` also
    ``first_grad``); ``params0`` is what both started from. ``*_gap`` is the
    worst leaf's, ``*_gap_median`` the median leaf's.

    Leaves whose first gradient in the reference is under a thousandth of
    the median leaf's move by round-off alone and are left out."""
    g1 = leaf_norms(ref["first_grad"])
    keep = g1 >= 1e-3 * float(np.median(g1))
    if not keep.any():
        raise ValueError("the reference's first gradient is nought in every leaf")
    u_got, u_ref = (leaf_norms(tree_sub(t["params"], params0)) for t in (got, ref))
    m_got, m_ref = (leaf_norms(t["trace"]) for t in (got, ref))
    update, moment = leaf_gaps(u_got, u_ref, keep), leaf_gaps(m_got, m_ref, keep)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(ref["params"])[0]]
    kept = [n for n, k in zip(names, keep) if k]
    return {
        "loss_gap": abs(got["loss"] - ref["loss"]) / max(abs(ref["loss"]), 1e-30),
        "update_gap": float(update.max()),
        "moment_gap": float(moment.max()),
        "update_gap_median": float(np.median(update)),
        "moment_gap_median": float(np.median(moment)),
        "update_gap_leaf": kept[int(update.argmax())],
        "moment_gap_leaf": kept[int(moment.argmax())],
        "leaves_left_out": int((~keep).sum()),
    }
