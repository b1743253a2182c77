"""The plain reference of a training cell: forward, loss, gradient and the
SGD-with-momentum update in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision, no kernels, no scan, no sharding.

It imports nothing of the program. What it shares with the program is the
*recipe* a training job is defined by, copied here so that no later PR can
move it: which rows a step trains on (the fixed-seed partition and the
per-epoch visit order), the random crop and flip each row gets, the loss,
and the optimizer. A family's layer equations live beside this file
(``densenet.py``, ``resnet.py``) and are found by the ``family`` key of a
configuration's ``model`` group.

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
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32), axis=-1)
    return logz - gold[:, 0]


def family(model: dict):
    """The module that holds ``forward(params, x, model, precision)`` for the
    configuration's ``model["family"]``."""
    return importlib.import_module(f"{__package__}.{model['family']}")


# ------------------------------------------------------- the job's recipe


def epoch_rows(n_train: int, world_size: int, batch: int, seed: int, epoch: int):
    """Rows of every step of an epoch under the even split:
    ``rows[step][worker]`` is an index vector of ``batch / world_size`` rows.

    A fixed-seed permutation of the rows is cut into one contiguous shard per
    worker; each epoch visits a shard in an order drawn from (seed, epoch,
    worker); a step takes the next ``batch / world_size`` rows of every
    shard. (The job definition of the paper's ``dataloader.py``, as the
    program implements it in ``data/partitioner.py``.)"""
    per = batch // world_size
    order = np.random.RandomState(seed).permutation(n_train)
    shard = int(n_train / world_size)
    steps = -(-shard // per)
    visits = []
    for r in range(world_size):
        owned = order[r * shard:(r + 1) * shard]
        visit = np.random.RandomState(
            (seed * 1000003 + epoch * 9176 + r) % (2**32)
        ).permutation(len(owned))
        visits.append(owned[visit])
    return [[v[s * per:(s + 1) * per] for v in visits] for s in range(steps)]


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


# ------------------------------------------------------------ one epoch


def _block_fn(model: dict, precision: str):
    """Gradient of ``weight`` x the summed loss of a block of rows (the weight
    is an argument, so every batch size and fault shares one program)."""
    fwd = family(model).forward

    def loss_sum(params, x, y, weight):
        losses = cross_entropy(fwd(params, x, model, precision), y)
        return jnp.sum(losses) * weight, jnp.sum(losses)

    return jax.jit(jax.value_and_grad(loss_sum, has_aux=True))


def train_epoch(
    params,
    train_x: np.ndarray,
    train_y: np.ndarray,
    model: dict,
    job: dict,
    *,
    precision: str = "f32",
    fault: str = "",
    block_rows: int = 512,
    device=None,
):
    """Follow the job's first epoch from ``params`` (a host tree) and return
    what is compared: the mean loss of the epoch's rows, the first step's
    gradient, the momentum and the parameters after the last step.

    ``job``: ``n_train, world_size, batch, seed, epoch, lr, dataset``; the
    workers share one chip, so all rows of a step are drawn together.

    ``fault`` plants, in this reference, a fault a program could have:
    ``"half_batch"`` leaves out every second row of each step and takes the
    mean over the rest; ``"state_unchanged"`` computes every step and throws
    its update away."""
    device = device or jax.devices()[0]
    mean, std = NORM_STATS[job["dataset"]]
    batch, ws = int(job["batch"]), int(job["world_size"])
    put = lambda a: jax.device_put(a, device)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: put(np.asarray(a, np.float32)), params)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    aug = jax.jit(lambda x, k: augment(x, k, mean, std))
    if fault not in ("", "half_batch", "state_unchanged"):
        raise ValueError(f"unknown fault {fault!r}")
    weight = jnp.float32(1.0 / (batch // 2 if fault == "half_batch" else batch))
    grad_fn = _block_fn(model, precision)
    sgd = jax.jit(
        lambda p, t, g, lr: (
            jax.tree_util.tree_map(lambda t_, g_: g_ + MOMENTUM * t_, t, g),
            jax.tree_util.tree_map(
                lambda p_, t_, g_: p_ - lr * (g_ + MOMENTUM * t_), p, t, g
            ),
        )
    )
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    loss_total, rows_total, first_grad = 0.0, 0, None
    steps = epoch_rows(job["n_train"], ws, batch, job["seed"], job["epoch"])
    for s, by_worker in enumerate(steps):
        rows = np.concatenate(by_worker)
        x = aug(put(train_x[rows]), step_key(job["seed"], job["epoch"], s))
        y = put(train_y[rows].astype(np.int32))
        if fault == "half_batch":
            x, y = x[::2], y[::2]
        grads = None
        for lo in range(0, x.shape[0], block_rows):
            (_, lsum), g = grad_fn(params, x[lo:lo + block_rows], y[lo:lo + block_rows], weight)
            grads = g if grads is None else add(grads, g)
            loss_total += float(lsum)
            rows_total += int(min(block_rows, x.shape[0] - lo))
        if first_grad is None:
            first_grad = jax.device_get(grads)
        if fault != "state_unchanged":
            trace, params = sgd(params, trace, grads, jnp.float32(job["lr"]))
    return {
        "loss": loss_total / max(rows_total, 1),
        "first_grad": first_grad,
        "trace": jax.device_get(trace),
        "params": jax.device_get(params),
    }


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
