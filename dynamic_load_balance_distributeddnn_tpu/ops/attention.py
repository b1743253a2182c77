"""Decoder attention parts: RMSNorm, rotary positions and causal grouped-query
attention with an optional window, in two forms of one algorithm.

*Fused* (``ops/pallas/fused_attention.py``): a flash-form kernel that keeps the
scores in VMEM. It serves a call whose operands are bfloat16, whose head size
divides by 128 and whose ``T`` divides by its blocks, when the program is
lowered for a TPU. *Blocked*, in plain XLA, serves every other call (float32
operands, odd shapes, the CPU). The choice is made from the call itself and
from the platform the program is lowered for, never from the process's default
backend: a program compiled for a described chip takes the path the chip
would. Each lowered call leaves one ``attention_path`` instant in the tracer.

The blocked form: at thousands of tokens the ``[heads, T, T]`` scores cannot be materialised
(2 columns x 32 heads x 4096^2 x 4 B = 4.3 GB a layer), so the queries are
taken ``block_q`` at a time against the static slice of keys they can see:
``[0, q_end)`` under the causal mask alone, ``(q_start - window, q_end)``
with a window. Each block is a ``jax.checkpoint`` and the blocks are tied one
after the other, so either pass holds one block's scores at a time and the
backward pass recomputes them from q, k and v. Blocks the mask empties are
never computed; inside the kept slices the mask does the rest. Every product
is XLA's own.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer
from dynamic_load_balance_distributeddnn_tpu.ops.pallas.fused_attention import (
    fused_causal_attention,
)

_NEG = -1e30
# the fused form's query and key blocks, by scripts/kernel_bench.py's attention
# case on a v5e (PERF.md section 6, PR 28)
FUSED_BLOCK = 512
# its K and V of one head stay in VMEM, twice each for the pipeline, beside the
# backward pass's float32 dK and dV: 4 MB is 16,384 keys of 128
_FUSED_KV_BYTES = 4 * 2**20


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, reduced in
    float32, returned in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions over the whole head: ``x`` is ``[B, T, H, D]``, the
    pair of channel ``i`` is ``i + D/2`` (the ``rotate_half`` convention)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


def key_range(q0: int, q1: int, window: Optional[int], align: int = 128):
    """The static slice of keys the queries ``[q0, q1)`` can see."""
    if window is None:
        return 0, q1
    return max(0, (q0 - window + 1) // align * align), q1


def _block(q, k, v, q0: int, k0: int, window: Optional[int]):
    """One block of queries ``[B, bq, Hkv, G, D]`` against keys and values
    ``[B, bk, Hkv, D]``; ``q0`` and ``k0`` are their first positions."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    qpos = q0 + jnp.arange(q.shape[1])[:, None]
    kpos = k0 + jnp.arange(k.shape[1])[None, :]
    mask = qpos >= kpos
    if window is not None:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)


# An identity on q that says, when its program is lowered, which form the call
# took: only there is the platform known.
_path_p = Primitive("attention_path")
_path_p.def_impl(lambda x, **_: x)
_path_p.def_abstract_eval(lambda x, **_: x)
ad.primitive_jvps[_path_p] = lambda primals, tangents, **params: (
    _path_p.bind(*primals, **params), tangents[0])
batching.defvectorized(_path_p)


def _path_lowering(ctx, x, *, path, why, window):
    aval = ctx.avals_in[0]
    get_tracer().instant("attention_path", cat="dispatch", args={
        "path": path, "why": why or ",".join(ctx.module_context.platforms),
        "window": window, "t": aval.shape[1], "dtype": str(aval.dtype)})
    return [x]


# not cacheable: the rule speaks, once for every call it lowers
mlir.register_lowering(_path_p, _path_lowering, cacheable=False)


def _why_not_fused(q, k, v) -> Optional[str]:
    """What about the call itself keeps the fused form from serving it."""
    t, d = q.shape[1], q.shape[-1]
    for x in (q, k, v):
        if x.dtype != jnp.bfloat16:
            return str(x.dtype)
    if d % 128:
        return "head_dim"
    if t % FUSED_BLOCK or t * d * 2 > _FUSED_KV_BYTES:
        return "t"
    return None


def blocked_causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window: Optional[int] = None,
    block_q: int = 256,
) -> jnp.ndarray:
    """Causal softmax attention, scale ``1/sqrt(D)``. ``q`` is ``[B, T, H,
    D]``, ``k`` and ``v`` ``[B, T, Hkv, D]`` with ``H`` a multiple of ``Hkv``
    (query head ``h`` reads key head ``h // (H / Hkv)``). With ``window``, a
    query at ``i`` sees the keys ``j`` with ``0 <= i - j < window``. Fused
    where the kernel serves the call (see the module's text), else blocked
    over ``block_q`` queries."""

    def form(path, why=""):
        fn = fused_causal_attention if path == "fused" else _blocked
        block = FUSED_BLOCK if path == "fused" else block_q
        return lambda q, k, v: fn(
            _path_p.bind(q, path=path, why=why, window=window), k, v, window, block)

    why = _why_not_fused(q, k, v)
    if why:
        return form("blocked", why)(q, k, v)
    return jax.lax.platform_dependent(q, k, v, tpu=form("fused"), default=form("blocked"))


def _blocked(q, k, v, window: Optional[int], block_q: int) -> jnp.ndarray:
    b, t, h, d = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, h // hkv, d)
    block_q = min(block_q, t)
    outs = []
    for q0 in range(0, t, block_q):
        q1 = min(q0 + block_q, t)
        k0, k1 = key_range(q0, q1, window)
        # prevent_cse stays on: the blocks are unrolled code, not a scan's
        # body, and XLA would merge the recomputation with the forward pass
        # and keep every block's scores alive
        fn = jax.checkpoint(lambda qq, kk, vv, q0=q0, k0=k0: _block(qq, kk, vv, q0, k0, window))
        qb = q[:, q0:q1]
        if outs:
            # one block after the other, forward and backward: without the
            # tie XLA runs independent blocks side by side and holds several
            # blocks' float32 scores at once (8 x 320 MB a layer at 4,096)
            qb, outs[-1] = jax.lax.optimization_barrier((qb, outs[-1]))
        outs.append(fn(qb, k[:, k0:k1], v[:, k0:k1]))
    return jnp.concatenate(outs, axis=1).reshape(b, t, h, d)
