"""Routed experts for a chip that holds a share of them.

``route`` scores every token against ALL published experts (float32 product,
sigmoid or softmax, top-k, as the published code does it) and ``expert_ffn`` is told
which experts this chip holds: it computes what those give and nothing for
the rest, which other chips of the deployment would add. No token routed to a
held expert is dropped, whatever the routing, and the work follows the tokens
that arrive:

- the ``N * k`` (token, choice) pairs are sorted by held expert, pairs routed
  elsewhere last; ``group_sizes`` are the held experts' arrivals;
- the sorted pairs are taken ``chunk`` rows at a time (a ``lax.scan`` whose
  body is a ``jax.checkpoint``): a chunk's tokens are gathered and go through
  three grouped products (``lax.ragged_dot``, which XLA:TPU compiles to a
  grouped matmul that visits only the tiles the groups fill): gate, up, down;
  each row is weighted by its routing weight and added to its token's row;
- a chunk that starts past the last arrival is skipped (``lax.cond``).

``chunk`` is static: twice the expected arrivals (``N * k * held /
experts``), so an even routing takes one chunk and the worst (every pair on a
held expert) takes them all: an uneven routing costs time, never tokens, and
the memory held is one chunk's whatever arrives.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}


def route(
    m: jnp.ndarray, w_router: jnp.ndarray, expert_bias: Optional[jnp.ndarray], k: int,
    route_norm: bool, route_scale: float, score_func: str = "sigmoid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(chosen experts [N, k], their weights [N, k] float32)`` of tokens
    ``m`` ``[N, d]``. Scores are ``score_func`` (``sigmoid``, or ``softmax``
    over all experts) of ``m @ w_router`` in float32; the ``k`` largest of
    ``scores + expert_bias`` (of the scores, for a model that has no bias:
    ``None``) are chosen; the weights are the scores at the chosen experts,
    divided by their sum where ``route_norm``, times ``route_scale``."""
    scores = SCORES[score_func](
        jnp.dot(m.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
    )
    ranked = scores if expert_bias is None else scores + expert_bias.astype(jnp.float32)
    _, chosen = lax.top_k(ranked, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if route_norm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * route_scale


def chunk_rows(n_pairs: int, held: int, experts: int, tile: int = 512) -> int:
    """Twice the expected arrivals, rounded up to whole tiles, at most all pairs."""
    want = -(-2 * n_pairs * held // experts)
    return min(-(-want // tile) * tile, n_pairs)


def expert_ffn(
    m: jnp.ndarray, chosen: jnp.ndarray, weights: jnp.ndarray, first: int,
    w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray, experts: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What the held experts ``[first, first + held)`` add to tokens ``m``
    ``[N, d]``: ``sum over held choices of weight * down(silu(gate x) * up
    x)``. ``w_gate`` and ``w_up`` are ``[held, d, f]``, ``w_down`` ``[held,
    f, d]``; ``experts`` is the published count ``chosen`` ranges over.
    Returns the sum ``[N, d]`` and the arrivals ``[held + 1]`` (float32; the
    last entry counts the pairs routed to experts held elsewhere)."""
    n, k = chosen.shape
    held = w_gate.shape[0]
    n_pairs = n * k
    local = chosen - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1).astype(jnp.int32)
    arrivals = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    total = n_pairs - arrivals[held]
    ends = jnp.cumsum(arrivals[:held])
    starts = ends - arrivals[:held]
    rows_per = chunk_rows(n_pairs, held, experts)
    n_chunks = -(-n_pairs // rows_per)
    order = jnp.argsort(key, stable=True)
    order = jnp.pad(order, (0, n_chunks * rows_per - n_pairs))
    flat_w = weights.reshape(-1)

    def one_chunk(out, lo):
        def run():
            rows = lax.dynamic_slice_in_dim(order, lo, rows_per)
            tok = rows // k
            live = lo + jnp.arange(rows_per) < total
            # the part of each held expert's group that lies in this chunk
            sizes = jnp.clip(ends, lo, lo + rows_per) - jnp.clip(starts, lo, lo + rows_per)
            # rows past the last arrival are masked going in as well as coming
            # out: XLA:TPU's grouped product leaves the rows outside every
            # group unwritten, in the backward pass too, and without this
            # select their garbage is scatter-added into the tokens' gradient
            # (a gradient 5e7 times too large on the chip, right on the CPU)
            xs = jnp.where(live[:, None], m[tok], 0)
            hidden = jax.nn.silu(lax.ragged_dot(xs, w_gate, sizes)) * lax.ragged_dot(
                xs, w_up, sizes)
            y = lax.ragged_dot(hidden, w_down, sizes).astype(jnp.float32)
            y = jnp.where(live[:, None], y * flat_w[rows][:, None], 0.0)
            return out.at[tok].add(y)

        return lax.cond(lo < total, run, lambda: out), None

    out, _ = lax.scan(jax.checkpoint(one_chunk), jnp.zeros((n, m.shape[1]), jnp.float32),
                      jnp.arange(n_chunks) * rows_per)
    return out.astype(m.dtype), arrivals.astype(jnp.float32)
