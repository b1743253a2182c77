"""Causal grouped-query attention in flash form, with an optional window.

``q`` is ``[B, T, H, D]``, ``k`` and ``v`` ``[B, T, Hkv, D]`` as the model
holds them; seen as ``[B, T, H*D]`` a head is ``D`` neighbouring lanes, so no
operand is transposed or repeated. The grid is ``(batch, key-value head, query
block, query head of the group)``: one program takes ``block_q`` queries of
one head against its key-value head's whole K and V, whose block index does
not move over the last two axes, so they are fetched once a key-value head
and stay in VMEM for all its query heads and blocks (1 MB each at 4,096 keys
of 128). Inside, a loop walks the key tiles the mask leaves: the tiles
between the window's lower edge and the diagonal unmasked, the ones the mask
cuts (the edge, the diagonal) masked, the rest never touched. Score tiles
live in VMEM; the softmax keeps a running (max, sum) in float32. Products
take the operands' dtype (bfloat16 in: bfloat16 products, float32
accumulation, ``p`` and ``ds`` cast to bfloat16 before their products); the
scale ``1/sqrt(D)`` is applied to the float32 scores.

The backward pass is one kernel over the same grid: it replays the tiles
transposed (keys in sublanes, so the saved per-row log-sum-exp and ``delta =
rowsum(dO * O)`` broadcast along lanes as stored), adds ``dK`` and ``dV`` up
in a float32 ``[T, D]`` scratch over the key-value head's query blocks and
heads, and ``dQ`` over the loop. The forward saves ``o`` and the log-sum-exp,
nothing of size T^2.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_VMEM_LIMIT = 64 * 2**20  # of the core's 128 MiB; the default scope is 16


def _tile_ranges(q0, block_q: int, block_k: int, window: Optional[int]):
    """Key tiles of the queries ``[q0, q0 + block_q)``: ``(lo, full_lo,
    full_hi, hi)``. Tiles in ``[lo, hi)`` hold a visible key, those in
    ``[full_lo, full_hi)`` only visible ones."""
    q1 = q0 + block_q
    hi = (q1 + block_k - 1) // block_k
    full_hi = (q0 + 1) // block_k
    if window is None:
        return 0, 0, full_hi, hi
    lo = jnp.maximum(q0 - window + 1, 0) // block_k
    full_lo = jnp.maximum(q1 - 1 - window + block_k, 0) // block_k
    full_lo = jnp.clip(full_lo, lo, hi)
    return lo, full_lo, jnp.clip(full_hi, full_lo, hi), hi


def _visible(q0, k0, shape, q_axis: int, window: Optional[int]):
    """The mask of one tile: queries from ``q0`` along ``q_axis``, keys from
    ``k0`` along the other."""
    ahead = (q0 - k0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    mask = ahead >= 0
    if window is not None:
        mask = jnp.logical_and(mask, ahead < window)
    return mask


def _walk(ranges, tile, carry):
    """``tile(j, carry, masked)`` over the whole tiles, two a trip (so that the
    second one's products can overlap the first one's softmax) and the odd one
    alone, then over the cut tiles below and above them. The order is free: a
    row that a cut tile leaves empty before any of its keys came counts that
    tile's keys at weight one, and the first real score fades them to nothing
    (every row has its own key)."""
    lo, full_lo, full_hi, hi = ranges
    whole = functools.partial(tile, masked=False)
    pairs = (full_hi - full_lo) // 2
    carry = jax.lax.fori_loop(
        0, pairs, lambda n, c: whole(full_lo + 2 * n + 1, whole(full_lo + 2 * n, c)), carry)
    carry = jax.lax.fori_loop(full_lo + 2 * pairs, full_hi, whole, carry)
    below = full_lo - lo

    def cut(n, carry):
        return tile(jnp.where(n < below, lo + n, full_hi + n - below), carry, masked=True)

    return jax.lax.fori_loop(0, below + hi - full_hi, cut, carry)


def _fused_attention_fwd(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, window, block_q, block_k):
    q0 = pl.program_id(2) * block_q
    q = q_ref[0]

    def tile(j, carry, masked):
        m, l, acc = carry
        keys = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(q0, j * block_k, s.shape, 0, window), s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m - m_new)
        l = l * fade + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * fade + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = _walk(_tile_ranges(q0, block_q, block_k, window), tile, (
        jnp.full((block_q, 1), _NEG, jnp.float32), jnp.zeros((block_q, 1), jnp.float32),
        jnp.zeros(q.shape, jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l))[:, 0]


def _fused_attention_bwd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                         dk_acc, dv_acc, *, scale, window, block_q, block_k):
    i, g = pl.program_id(2), pl.program_id(3)
    q0 = i * block_q

    @pl.when(jnp.logical_and(i == 0, g == 0))
    def _start():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, do = q_ref[0], do_ref[0]
    lse, delta = lse_ref[0, 0], delta_ref[0, 0]  # [1, block_q]

    def tile(j, dq, masked):
        keys = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        # transposed: [block_k, block_q]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(q0, j * block_k, s.shape, 1, window), s, _NEG)
        p = jnp.exp(s - lse)
        dv_acc[keys, :] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[keys, :] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32)
        return dq + jnp.dot(ds.T.astype(k.dtype), k, preferred_element_type=jnp.float32)

    dq = _walk(_tile_ranges(q0, block_q, block_k, window), tile, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(i == pl.num_programs(2) - 1, g == pl.num_programs(3) - 1))
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _call(kernel, q, k, ins, outs, scratch, semantics, window, block_q, block_k, interpret):
    """``kernel`` over the grid ``(batch, key-value head, query block, query
    head of the group)``. ``ins`` and ``outs`` name each operand's kind:
    "rows" (like q, ``[B, T, H*D]``: one head's query block a program), "keys"
    (like k, ``[B, T, Hkv*D]``: the key-value head's whole ``[T, D]``, fetched
    once a head) or "stats" (per-row statistics ``[B, H, 1, T]``)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    groups = h // hkv
    if t % block_q or t % block_k or h % hkv:
        raise ValueError(f"T={t} must divide by the blocks ({block_q}, {block_k}), H={h} by Hkv={hkv}")
    specs = {
        "rows": (pl.BlockSpec((1, block_q, d), lambda b, h, i, g: (b, i, h * groups + g)),
                 jax.ShapeDtypeStruct((b, t, h * d), q.dtype)),
        "keys": (pl.BlockSpec((1, t, d), lambda b, h, i, g: (b, 0, h)),
                 jax.ShapeDtypeStruct((b, t, hkv * d), k.dtype)),
        "stats": (pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, g: (b, h * groups + g, 0, i)),
                  jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32)),
    }
    return pl.pallas_call(
        functools.partial(kernel, scale=1.0 / math.sqrt(d), window=window, block_q=block_q,
                          block_k=block_k),
        grid=(b, hkv, t // block_q, groups),
        in_specs=[specs[n][0] for n in ins],
        out_specs=[specs[n][0] for n in outs],
        out_shape=[specs[n][1] for n in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=kernel.__name__.lstrip("_"),
    )


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def _forward(q, k, v, window, block_q, block_k, interpret):
    o, lse = _call(
        _fused_attention_fwd, q, k, ("rows", "keys", "keys"), ("rows", "stats"), [],
        ("parallel",) * 4, window, block_q, block_k, interpret,
    )(_flat(q), _flat(k), _flat(v))
    return o.reshape(q.shape), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, window, block_q, block_k, interpret):
    return _forward(q, k, v, window, block_q, block_k, interpret)[0]


def _attention_fwd(q, k, v, window, block_q, block_k, interpret):
    o, lse = _forward(q, k, v, window, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(window, block_q, block_k, interpret, saved, do):
    q, k, v, o, lse = saved
    t, d = q.shape[1], q.shape[3]
    delta = jnp.einsum("bthd,bthd->bht", do.astype(jnp.float32), o.astype(jnp.float32))
    acc = pltpu.VMEM((t, d), jnp.float32)
    dq, dk, dv = _call(
        _fused_attention_bwd, q, k, ("rows", "keys", "keys", "rows", "stats", "stats"),
        ("rows", "keys", "keys"), [acc, acc],
        # dK and dV add up over the key-value head's query blocks and heads
        ("parallel", "parallel", "arbitrary", "arbitrary"), window, block_q, block_k, interpret,
    )(_flat(q), _flat(k), _flat(v), _flat(do), lse, delta[:, :, None, :])
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_attention.defvjp(_attention_fwd, _attention_bwd)


def fused_causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window: Optional[int] = None,
    block_q: int = 512, block_k: Optional[int] = None, interpret: bool = False,
) -> jnp.ndarray:
    """Causal softmax attention, scale ``1/sqrt(D)``; with ``window``, a query
    at ``i`` sees the keys ``j`` with ``0 <= i - j < window``. ``T`` must
    divide by both blocks and ``D`` by 128; K and V of one head (``T * D``
    elements each) have to fit VMEM several times over."""
    return _attention(q, k, v, window, block_q, block_k or block_q, interpret)
