"""The ``tokens`` task: a language-model job over one stream of token ids.

``-b`` is the number of *columns*, ``--n_train`` the train stream's length in
tokens, ``--bptt`` the window (the configuration's ``model["seq_len"]``). **A
sample is one window of ``--bptt`` target tokens in one column**, so an
epoch's samples are its true target tokens / ``--bptt`` and tokens/s =
``samples_per_s`` x ``--bptt``.

The recipe, the reference's own copy of what the program does in
``train/lm_engine.py`` (``_build_plan``, ``_build_windows``) and its step
library: the stream is cut contiguously by share (worker ``r`` gets the next
``int(share_r * n)`` tokens); a worker folds its slice into its columns
(column ``j`` is the ``j``-th contiguous chunk); step ``s`` is every worker's
window ``s`` of ``--bptt`` tokens with the next token as target; a token's
weight is ``share_r`` / the worker's true tokens in that window; **each
worker's gradient is clipped to ``--grad_clip`` (0.25 where the flag is 0)
before the workers are summed**; then SGD with momentum 0.9. It imports
nothing of the program.

A family's ``forward(params, x, model, precision)`` takes int32 ``[rows,
seq]`` and returns float32 logits ``[rows, seq, vocab]``. Where its training
loss has terms beside the next-token loss it brings ``loss(params, x, y,
weights, model, precision) -> (objective, token_losses)``: the scalar that is
differentiated and the ``[rows, seq]`` next-token losses the epoch's mean
loss is taken over. The hook is called on a block of columns at a time and
the blocks' objectives are added, so every term has to be a sum over rows.
"""

from __future__ import annotations

import types
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common

FAULTS = ("half_batch", "state_unchanged", "no_clip")
DEFAULT_CLIP = 0.25  # what the program clips to where --grad_clip is 0
FOLLOW = 0.5         # probability that a token follows its predecessor


# ------------------------------------------------------ sizes and the plan


def job_sizes(argv: List[str]) -> dict:
    """Columns, tokens an epoch, bucket, window and clip, read back from the
    argv the job is run with."""
    def after(flag, default=None):
        if flag not in argv and default is not None:
            return default
        return argv[argv.index(flag) + 1]

    return {"batch": int(after("-b")), "n_train": int(after("--n_train")),
            "bucket": int(after("--bucket")), "bptt": int(after("--bptt")),
            "grad_clip": float(after("--grad_clip", "0"))}


def job_keys(config: dict, sizes: dict) -> dict:
    return {k: sizes[k] for k in ("n_train", "batch", "bptt", "grad_clip")}


def integer_split(shares, batch: int) -> List[int]:
    """Columns per worker: floors of ``share * batch``, then one more to the
    largest remainders that are a half or more, while columns are left (the
    paper's rule, ``balance/solver.py:integer_batch_split``)."""
    shares = np.asarray(shares, np.float64)
    ideal = shares * batch / shares.sum()
    floors = np.floor(ideal)
    remainder = ideal - floors
    short = int(batch - floors.sum())
    if short > 0:
        top = np.argsort(remainder, kind="stable")[-short:]
        floors[top[remainder[top] >= 0.5]] += 1
    return [int(f) for f in floors]


def plan_batches(shares: List[float], sizes: dict) -> List[int]:
    return integer_split(shares, sizes["batch"])


def epoch_plan(shares, sizes: dict) -> List[dict]:
    """Per worker: its token range ``[lo, hi)`` of the stream, its columns,
    the rows of its folded slice, its windows and its true target tokens."""
    plan, lo = [], 0
    for share, cols in zip(shares, plan_batches(shares, sizes)):
        cols = max(cols, 1)
        length = int(share * sizes["n_train"])
        nbatch = length // cols
        windows = -(-(nbatch - 1) // sizes["bptt"]) if nbatch > 1 else 0
        plan.append({"lo": lo, "hi": lo + length, "share": float(share), "cols": cols,
                     "nbatch": nbatch, "windows": windows,
                     "targets": cols * max(nbatch - 1, 0)})
        lo += length
    return plan


def epoch_steps(plan: List[dict]) -> int:
    return max(max(w["windows"] for w in plan), 1)


def epoch_samples(shares: List[float], sizes: dict) -> float:
    """Windows of ``--bptt`` target tokens an epoch trains on: its true
    target tokens / ``--bptt``."""
    return sum(w["targets"] for w in epoch_plan(shares, sizes)) / sizes["bptt"]


def plan_errors(epochs: List[dict], sizes: dict) -> Dict[str, float]:
    """The two exact checks on every epoch of the window. ``plan_sum_err``:
    the plan covers the global batch, that is its shares x ``-b`` sum to
    ``-b`` to the nearest column. (Not the integer columns themselves: the
    paper's rule, :func:`integer_split`, gives a remainder under a half no
    column, so a plan the balancer has moved may run a column short of
    ``-b``, [3, 1, 1, 2] of 8; its weights still sum to 1 and its samples
    count the tokens it did train on.) ``steps_err``: the epoch ran the
    number of windows the recipe gives for its plan. An epoch that recorded
    no plan fails both."""
    sums, steps = [], []
    for e in epochs:
        if not e.get("batches") or not e.get("shares"):
            sums.append(sizes["batch"])
            steps.append(1)
            continue
        sums.append(abs(round(sum(e["shares"]) * sizes["batch"]) - sizes["batch"])
                    + max(sum(e["batches"]) - sizes["batch"], 0))
        steps.append(abs(e["steps"] - epoch_steps(epoch_plan(e["shares"], sizes))))
    return {"plan_sum_err": float(max(sums, default=sizes["batch"])),
            "steps_err": float(max(steps, default=1))}


# ------------------------------------------------- inputs from the seed


def _stream(rng, n: int, vocab: int, perm: np.ndarray) -> np.ndarray:
    """A first-order chain: with probability :data:`FOLLOW` the next id is
    ``perm[last]``, else a fresh uniform draw. In bulk: a token ``k`` places
    after the last fresh draw is ``perm`` applied ``k`` times to that draw,
    and ``k`` is geometric, so the loop below runs some ``log2(n)`` times."""
    fresh = rng.random(n) >= FOLLOW
    fresh[:1] = True
    draws = rng.integers(0, vocab, size=n, dtype=np.int64)
    at = np.arange(n)
    last = np.maximum.accumulate(np.where(fresh, at, 0))
    hops = at - last
    ids = draws[last]
    for k in range(1, int(hops.max(initial=0)) + 1):
        sel = hops >= k
        ids[sel] = perm[ids[sel]]
    return ids.astype(np.int32)


def make_rows(seed: int, sizes: dict, n_test: int, model: dict) -> dict:
    """A train stream of ``--n_train`` ids in ``[0, vocab_size)`` and a
    validation and a test stream of ``n_test``, with structure a model can
    learn (the loss falls from ln V), drawn in bulk from the seed."""
    rng = np.random.default_rng([int(seed), 0x70CE])
    vocab = int(model["vocab_size"])
    perm = rng.permutation(vocab)
    return {"train": _stream(rng, sizes["n_train"], vocab, perm),
            "valid": _stream(rng, n_test, vocab, perm),
            "test": _stream(rng, n_test, vocab, perm), "vocab_size": vocab}


def bundle(rows: dict, config: dict, cfg):
    """What ``LMTrainer._setup_data`` reads of a ``Corpus``."""
    return types.SimpleNamespace(
        train=rows["train"], valid=rows["valid"], test=rows["test"],
        ntokens=int(rows["vocab_size"]), synthetic=True,
        notes=["the benchmark's seeded token stream (benchmark/tasks/tokens.py)"])


# ------------------------------------------------------- the job's recipe


def fold(stream: np.ndarray, cols: int) -> np.ndarray:
    """``[rows, cols]``: column ``j`` is the ``j``-th contiguous chunk of the
    stream; tokens that do not fill a row are dropped."""
    nbatch = len(stream) // cols
    return stream[: nbatch * cols].reshape(cols, nbatch).T


def window(data: np.ndarray, s: int, bptt: int):
    """Window ``s`` of a folded slice as ``(x, y, mask)``, each ``[cols,
    bptt]``: inputs, the next token of each, and which places hold a token
    (the last window of a slice may be short; a slice that has run out of
    windows gives an empty one)."""
    nbatch, cols = data.shape
    seq = int(np.clip(nbatch - 1 - s * bptt, 0, bptt))
    x = np.zeros((cols, bptt), np.int32)
    y = np.zeros((cols, bptt), np.int32)
    m = np.zeros((cols, bptt), np.float32)
    x[:, :seq] = data[s * bptt:s * bptt + seq].T
    y[:, :seq] = data[s * bptt + 1:s * bptt + 1 + seq].T
    m[:, :seq] = 1.0
    return x, y, m


def epoch_windows(stream: np.ndarray, shares, sizes: dict, half: bool = False):
    """``steps[s][r] = (x, y, weights)`` of worker ``r`` in step ``s``:
    weights are ``share_r`` / the worker's true tokens in the window, so a
    step's weights sum to 1 while every worker still has a window. ``half``
    leaves out every second column and takes the mean over the rest."""
    plan = epoch_plan(shares, sizes)
    folded = [fold(stream[w["lo"]:w["hi"]], w["cols"]) for w in plan]
    steps = []
    for s in range(epoch_steps(plan)):
        row = []
        for w, data in zip(plan, folded):
            x, y, m = window(data, s, sizes["bptt"])
            if half:
                x, y, m = x[::2], y[::2], m[::2]
            row.append((x, y, m * np.float32(w["share"] / max(float(m.sum()), 1.0))))
        steps.append(row)
    return steps


def _block_fn(model: dict, precision: str):
    """Gradient of a block of columns' weighted loss, with the block's sum of
    token losses and its count of true tokens."""
    fam = common.family(model)

    def default_loss(params, x, y, weights, model, precision):
        losses = common.cross_entropy(fam.forward(params, x, model, precision), y)
        return jnp.sum(losses * weights), losses

    loss = getattr(fam, "loss", default_loss)

    def objective(params, x, y, weights):
        value, losses = loss(params, x, y, weights, model, precision)
        mask = (weights > 0).astype(jnp.float32)
        return value, (jnp.sum(losses * mask), jnp.sum(mask))

    return jax.jit(jax.value_and_grad(objective, has_aux=True))


def _clip_fn(clip: float):
    """A worker's gradient of its weighted loss, clipped as the program does
    it: divided by the worker's weight (the gradient of its mean loss),
    scaled so that its global norm is at most ``clip``, multiplied back."""
    def run(grads, w_sum):
        w_r = jnp.maximum(w_sum, 1e-12)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g / w_r))
                            for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        return jax.tree_util.tree_map(lambda g: g * scale, grads)

    return jax.jit(run)


def train_epoch(
    params,
    rows: dict,
    model: dict,
    job: dict,
    *,
    precision: str = "f32",
    fault: str = "",
    block_rows: int = 4,
    device=None,
):
    """Follow the job's first epoch from ``params`` (a host tree) and return
    what is compared: the epoch's mean loss a target token, the first step's
    gradient (the clipped workers summed), the momentum and the parameters
    after the last step.

    ``job``: ``n_train, world_size, batch, bptt, grad_clip, seed, epoch,
    lr`` and optionally ``shares`` (the even split where absent). A worker's
    gradient is taken ``block_rows`` columns at a time, so that one block's
    activations are all that is held beside the parameters, the momentum and
    two gradients.

    ``fault`` plants, in this reference, a fault a program could have:
    ``"half_batch"`` leaves out every second column of every worker and takes
    the mean over the rest; ``"state_unchanged"`` computes every step and
    throws its update away; ``"no_clip"`` sums the workers' gradients
    unclipped."""
    device = device or jax.devices()[0]
    if fault not in ("",) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ws = int(job["world_size"])
    shares = job.get("shares") or [1.0 / ws] * ws
    sizes = {k: job[k] for k in ("n_train", "batch", "bptt")}
    clip = float(job["grad_clip"]) if float(job["grad_clip"]) > 0 else DEFAULT_CLIP
    put = lambda a: jax.device_put(a, device)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: put(np.asarray(a, np.float32)), params)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    grad_fn, clip_fn = _block_fn(model, precision), _clip_fn(clip)
    sgd, add = common.sgd_step, common.tree_add
    loss_total, tokens_total, first_grad = 0.0, 0.0, None
    stream = np.asarray(rows["train"][: job["n_train"]])
    for step in epoch_windows(stream, shares, sizes, half=fault == "half_batch"):
        grads = None
        for x, y, w in step:
            if not w.any():
                continue  # this worker's slice has run out of windows
            mine = None
            for lo in range(0, x.shape[0], block_rows):
                cut = slice(lo, lo + block_rows)
                (_, (lsum, count)), g = grad_fn(params, put(x[cut]), put(y[cut]), put(w[cut]))
                mine = g if mine is None else add(mine, g)
                loss_total += float(lsum)
                tokens_total += float(count)
            if fault != "no_clip":
                mine = clip_fn(mine, jnp.float32(w.sum()))
            grads = mine if grads is None else add(grads, mine)
        if grads is None:
            continue
        if first_grad is None:
            first_grad = jax.device_get(grads)
        if fault != "state_unchanged":
            trace, params = sgd(params, trace, grads, jnp.float32(job["lr"]))
    return {
        "loss": loss_total / max(tokens_total, 1.0),
        "first_grad": first_grad,
        "trace": jax.device_get(trace),
        "params": jax.device_get(params),
    }
