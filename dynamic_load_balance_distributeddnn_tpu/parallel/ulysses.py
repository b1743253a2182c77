"""Ulysses-style sequence parallelism — attention-head all-to-all.

The second sequence-parallel strategy next to ring attention
(parallel/ring.py), after DeepSpeed-Ulysses: tokens arrive sequence-sharded
[B, H, T/n, D]; one ``all_to_all`` re-shards to head-sharded [B, H/n, T, D],
each device runs FULL attention for its head subset locally, and the inverse
``all_to_all`` restores sequence sharding. Two all-to-alls per attention
instead of n-1 ppermute hops; requires ``num_heads % n_devices == 0``.

The reference has no sequence parallelism at all (SURVEY §5.7/§2.3 — its LM
path is bptt=35 truncation); both strategies here are the long-context
capability built TPU-first over ICI collectives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import axis_size, shard_map
from dynamic_load_balance_distributeddnn_tpu.parallel.ring import reference_attention

SEQ_AXIS = "data"


def ulysses_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
) -> jnp.ndarray:
    """Attention over a sequence sharded on ``axis_name`` via head all-to-all.

    q, k, v: local blocks [B, H, T_local, D] (call from inside shard_map).
    Returns the local output block [B, H, T_local, D]. H must divide by the
    axis size.
    """
    n = axis_size(axis_name)
    h = q.shape[1]
    assert h % n == 0, f"num_heads {h} must divide by axis size {n}"

    def to_heads(x):
        # scatter heads, gather sequence: [B, H, T/n, D] -> [B, H/n, T, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qg, kg, vg = to_heads(q), to_heads(k), to_heads(v)
    og = reference_attention(qg, kg, vg, causal=causal)
    # scatter sequence, gather heads: [B, H/n, T, D] -> [B, H, T/n, D]
    return jax.lax.all_to_all(
        og, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def make_ulysses_attention_fn(
    mesh: Mesh, axis_name: str = SEQ_AXIS, causal: bool = True
):
    """jit-ready global-array wrapper: q,k,v [B, H, T_global, D] sharded on T."""

    fn = shard_map(
        functools.partial(
            ulysses_self_attention, axis_name=axis_name, causal=causal
        ),
        mesh=mesh,
        in_specs=(
            P(None, None, axis_name, None),
            P(None, None, axis_name, None),
            P(None, None, axis_name, None),
        ),
        out_specs=P(None, None, axis_name, None),
        check_vma=False,
    )
    return jax.jit(fn)
