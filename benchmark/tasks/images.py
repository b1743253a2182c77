"""The ``images`` task: labelled uint8 images, one class a row, a global
batch of rows split over the workers. Rows from the seed, the
``DatasetBundle`` the trainer is handed, the plan's arithmetic, and the
job's recipe as the plain reference follows it (moved here from
``harness.py`` and ``reference/common.py``, unchanged; the crop, its key and
the channel statistics stay in ``reference/common.py``, where
``tests/test_augment.py`` holds the program's crop to them)."""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common
from benchmark.reference.common import NORM_STATS, augment, cross_entropy, step_key

FAULTS = ("half_batch", "state_unchanged")


# ------------------------------------------------------ sizes and the plan


def job_sizes(argv: List[str]) -> dict:
    """Batch and rows an epoch, read back from the argv the job is run with."""
    def after(flag):
        return argv[argv.index(flag) + 1]

    return {"batch": int(after("-b")), "n_train": int(after("--n_train")),
            "bucket": int(after("--bucket"))}


def job_keys(config: dict, sizes: dict) -> dict:
    return {"n_train": sizes["n_train"], "batch": sizes["batch"], "dataset": config["dataset"]}


def plan_batches(shares: List[float], sizes: dict) -> List[int]:
    return [int(round(s * sizes["batch"])) for s in shares]


def epoch_samples(shares: List[float], sizes: dict) -> int:
    """Rows an epoch trains on: each worker owns ``int(share * n)`` rows of
    the fixed permutation and visits each once."""
    return int(sum(int(s * sizes["n_train"]) for s in shares))


def plan_errors(epochs: List[dict], sizes: dict) -> Dict[str, float]:
    """The two exact checks on every epoch of the window: the plan's widths
    sum to the global batch, and the epoch runs ``n_train / batch`` steps
    (every row placed once). An epoch that recorded no plan fails both."""
    want_steps = sizes["n_train"] // sizes["batch"]
    sums = [abs(sum(e["batches"]) - sizes["batch"]) if e.get("batches") else sizes["batch"]
            for e in epochs]
    steps = [abs(e["steps"] - want_steps) if e.get("batches") else want_steps for e in epochs]
    return {"plan_sum_err": float(max(sums, default=sizes["batch"])),
            "steps_err": float(max(steps, default=want_steps))}


# ------------------------------------------------- inputs from the seed


def make_rows(seed: int, sizes: dict, n_test: int, model: dict) -> dict:
    """CIFAR-shaped rows that all differ, with a label a model can learn
    (the top-left patch carries the class), drawn in bulk from the seed."""
    rng = np.random.default_rng([int(seed), 0xDA7A])
    h, w, c = model["image"]
    num_classes = model["num_classes"]

    def gen(n):
        x = rng.integers(0, 256, size=(n, h, w, c), dtype=np.uint8)
        y = rng.integers(0, num_classes, size=n).astype(np.int32)
        step = 255 // num_classes
        x[:, : h // 4, : w // 4, :] = (y * step + step // 2).astype(np.uint8)[:, None, None, None]
        return x, y

    train_x, train_y = gen(sizes["n_train"])
    test_x, test_y = gen(n_test)
    return {"train_x": train_x, "train_y": train_y, "test_x": test_x, "test_y": test_y,
            "num_classes": num_classes}


def bundle(rows: dict, config: dict, cfg):
    """The ``DatasetBundle`` the trainer reads its rows from."""
    from dynamic_load_balance_distributeddnn_tpu.data.datasets import DatasetBundle

    mean, std = NORM_STATS[config["dataset"]]
    return DatasetBundle(
        name=cfg.dataset,
        train_x=rows["train_x"], train_y=rows["train_y"],
        test_x=rows["test_x"], test_y=rows["test_y"],
        num_classes=int(rows["num_classes"]),
        mean=tuple(mean), std=tuple(std), synthetic=True,
    )


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


def _block_fn(model: dict, precision: str):
    """Gradient of ``weight`` x the summed loss of a block of rows (the weight
    is an argument, so every batch size and fault shares one program)."""
    fwd = common.family(model).forward

    def loss_sum(params, x, y, weight):
        losses = cross_entropy(fwd(params, x, model, precision), y)
        return jnp.sum(losses) * weight, jnp.sum(losses)

    return jax.jit(jax.value_and_grad(loss_sum, has_aux=True))


def train_epoch(
    params,
    rows: dict,
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
    train_x, train_y = rows["train_x"], rows["train_y"]
    mean, std = NORM_STATS[job["dataset"]]
    batch, ws = int(job["batch"]), int(job["world_size"])
    put = lambda a: jax.device_put(a, device)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: put(np.asarray(a, np.float32)), params)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    aug = jax.jit(lambda x, k: augment(x, k, mean, std))
    if fault not in ("",) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    weight = jnp.float32(1.0 / (batch // 2 if fault == "half_batch" else batch))
    grad_fn = _block_fn(model, precision)
    sgd, add = common.sgd_step, common.tree_add
    loss_total, rows_total, first_grad = 0.0, 0, None
    steps = epoch_rows(job["n_train"], ws, batch, job["seed"], job["epoch"])
    for s, by_worker in enumerate(steps):
        idx = np.concatenate(by_worker)
        x = aug(put(train_x[idx]), step_key(job["seed"], job["epoch"], s))
        y = put(train_y[idx].astype(np.int32))
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
