"""Linear attention by the gated delta rule, and the short causal convolution
that goes before it.

Per head, with a state ``S`` ``[key, value]`` that starts at 0 in every window::

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;  o_t = S^T q_t

``gated_delta_rule`` computes this ``chunk`` tokens at a time. Inside a chunk
of ``C`` tokens, with ``G`` the running sum of ``g`` from the chunk's start
and ``S0`` the state the chunk is handed::

    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)            for j < i, else 0
    W = (I + A)^-1 (beta exp(G) K);  U0 = (I + A)^-1 (beta V)
    U = U0 - W S0
    O = (exp(G) Q) S0 + tril(exp(G_i - G_j) (q_i . k_j)) U
    S <- exp(G_C) S0 + (exp(G_C - G) K)^T U

One algorithm in two forms. *Fused* (``ops/pallas/delta_rule.py``): a kernel a
direction that keeps a chunk's system and the head's state in VMEM; it serves
a call whose operands are bfloat16, whose heads are 128 wide and whose ``T``
divides by its block, when the program is lowered for a TPU. *Chunked*, in
plain XLA, serves every other call (float32 operands, other shapes, the CPU):
everything above ``U`` is the same for every ``S0``, so it is computed for all
chunks at once; a ``lax.scan`` over the chunks then carries ``S`` in float32
through three products a chunk, its body a ``jax.checkpoint`` (the backward
pass is JAX's, through the scan, and holds the states between chunks and one
chunk's intermediates). The choice is made from the call itself and from the
platform the program is lowered for, as ``ops/attention.py`` makes its own.

In both forms ``g``, its running sum and the inverse of the unit
lower triangular ``I + A`` (a triangular solve against the identity) are
float32 whatever the operands' dtype; a decay is always ``exp`` of a
difference of running sums that is at most 0, never a ratio of exponentials
(at ``g`` = -20 a token the running sum passes -1,000 inside a chunk, where
``exp`` is 0 and its reciprocal infinite). The products take the operands'
dtype with float32 accumulation. Each lowered call leaves one
``linear_attention_path`` instant in the tracer, with the form it took
(``path``) and why (``why``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir
from jax.scipy.linalg import solve_triangular

from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer
from dynamic_load_balance_distributeddnn_tpu.ops.pallas.delta_rule import PAIR, fused_delta_rule

CHUNK = 64
COLUMNS = 2  # columns whose chunks the chunked form works on at once
# tokens a step of the fused form's grid at most (a shorter window takes the
# largest power of two that divides it), by scripts/kernel_bench.py's
# delta-rule case on a v5e (PERF.md section 6, PR 33)
FUSED_BLOCK = 512


def causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution, no bias: ``x`` is ``[B, T, C]``, ``w``
    ``[K, C]``, ``y_t = sum_i w_i x_(t - K + 1 + i)`` with nothing before the
    window's start. ``K`` shifted multiply-adds, summed in float32."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    w = w.astype(jnp.float32)
    y = padded[:, :t] * w[0]
    for i in range(1, taps):
        y = y + padded[:, i:i + t] * w[i]
    return y.astype(x.dtype)


# An identity on q that says, when its program is lowered, which form the call
# took, as ops/attention.py's `attention_path` does.
_path_p = Primitive("linear_attention_path")
_path_p.def_impl(lambda x, **_: x)
_path_p.def_abstract_eval(lambda x, **_: x)
ad.primitive_jvps[_path_p] = lambda primals, tangents, **params: (
    _path_p.bind(*primals, **params), tangents[0])
batching.defvectorized(_path_p)


def _path_lowering(ctx, x, *, path, why, chunk, heads):
    aval = ctx.avals_in[0]
    get_tracer().instant("linear_attention_path", cat="dispatch", args={
        "path": path, "why": why or ",".join(ctx.module_context.platforms),
        "chunk": chunk, "t": aval.shape[1], "dtype": str(aval.dtype), "heads": heads})
    return [x]


mlir.register_lowering(_path_p, _path_lowering, cacheable=False)


def _why_not_fused(q, k, v, chunk: int) -> Optional[str]:
    """What about the call itself keeps the fused form from serving it."""
    for x in (q, k, v):
        if x.dtype != jnp.bfloat16:
            return str(x.dtype)
    if q.shape[-1] != 128 or v.shape[-1] != 128:
        return "head_dim"
    if chunk != CHUNK:
        return "chunk"
    if math.gcd(q.shape[1], FUSED_BLOCK) % PAIR:  # two chunks a system
        return "t"
    return None


def gated_delta_rule(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray,
    chunk: int = CHUNK,
) -> jnp.ndarray:
    """The recurrence of the module's text over ``T`` tokens from a zero
    state. ``q`` and ``k`` are ``[B, T, Hk, Dk]`` (normalised and scaled by
    the caller), ``v`` ``[B, T, H, Dv]`` with ``H`` a multiple of ``Hk``
    (value head ``h`` reads key head ``h // (H / Hk)``), ``g`` (the log of the
    decay, at most 0) and ``beta`` ``[B, T, H]``; returns ``[B, T, H, Dv]`` in
    ``v``'s dtype. ``T`` must divide by ``chunk``. Fused where the kernel
    serves the call (see the module's text), else chunked."""
    t, h = q.shape[1], v.shape[2]
    if t % chunk:
        raise ValueError(f"gated_delta_rule: T={t} must divide by the chunk ({chunk})")
    if h % q.shape[2]:
        raise ValueError(f"gated_delta_rule: {h} value heads over {q.shape[2]} key heads")

    def form(path, why=""):
        fn = (functools.partial(fused_delta_rule, block_t=math.gcd(t, FUSED_BLOCK))
              if path == "fused"
              else functools.partial(_chunked, chunk=chunk))
        return lambda q, k, v, g, beta: fn(
            _path_p.bind(q, path=path, why=why, chunk=chunk, heads=h), k, v, g, beta)

    why = _why_not_fused(q, k, v, chunk)
    if why:
        return form("chunked", why)(q, k, v, g, beta)
    return jax.lax.platform_dependent(q, k, v, g, beta, tpu=form("fused"),
                                      default=form("chunked"))


def _chunked(q, k, v, g, beta, chunk: int) -> jnp.ndarray:
    """The chunked form. The columns are taken ``COLUMNS`` at a time, one
    group after the other, so that what is held for all chunks at once is two
    columns' whatever ``B`` is (ten columns of 4,096 are evaluated at once,
    in float32)."""
    b, group = q.shape[0], v.shape[2] // q.shape[2]
    if group > 1:
        q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)
    args = (q, k, v, g, beta)
    columns = COLUMNS if b % COLUMNS == 0 else 1
    if b == columns:
        return _delta_rule(*args, chunk)
    o = jax.lax.map(lambda xs: _delta_rule(*xs, chunk),
                    tuple(x.reshape(b // columns, columns, *x.shape[1:]) for x in args))
    return o.reshape(b, *o.shape[2:])


def _delta_rule(q, k, v, g, beta, chunk: int) -> jnp.ndarray:
    b, t, h, dk = q.shape
    n, dt, f32 = t // chunk, v.dtype, jnp.float32

    def chunked(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v = chunked(q), chunked(k), chunked(v)
    g, beta = chunked(g.astype(f32)), chunked(beta.astype(f32))  # [N, B, H, C]
    run = jnp.cumsum(g, axis=-1)
    ahead = run[..., :, None] - run[..., None, :]  # G_i - G_j
    i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(i >= j, ahead, -jnp.inf))  # 0 above the diagonal

    def scaled(x, by):  # x's rows times a float32 factor, back in the operands' dtype
        return (x.astype(f32) * by[..., None]).astype(dt)

    def product(spec, a, c):
        return jnp.einsum(spec, a, c, preferred_element_type=f32)

    pairs = "nbhik,nbhjk->nbhij"
    below = jnp.where(i > j, beta[..., None] * decay * product(pairs, k, k), 0.0)
    # (I + A)^-1, solved in float32; its products are the operands' dtype's
    inverse = solve_triangular(below, jnp.broadcast_to(jnp.eye(chunk, dtype=f32), below.shape),
                               lower=True, unit_diagonal=True).astype(dt)
    w = product("nbhij,nbhjk->nbhik", inverse, scaled(k, beta * jnp.exp(run))).astype(dt)
    u0 = product("nbhij,nbhjv->nbhiv", inverse, scaled(v, beta)).astype(dt)
    mix = (decay * product(pairs, q, k)).astype(dt)
    q_in = scaled(q, jnp.exp(run))
    k_out = scaled(k, jnp.exp(run[..., -1:] - run))
    keep = jnp.exp(run[..., -1])[..., None, None]  # the chunk's whole decay

    def one_chunk(state, xs):
        w, u0, mix, q_in, k_out, keep = xs
        s = state.astype(dt)
        u = (u0.astype(f32) - product("bhck,bhkv->bhcv", w, s)).astype(dt)
        o = product("bhck,bhkv->bhcv", q_in, s) + product("bhij,bhjv->bhiv", mix, u)
        state = keep * state + product("bhck,bhcv->bhkv", k_out, u)
        return state, o.astype(dt)

    _, o = jax.lax.scan(jax.checkpoint(one_chunk), jnp.zeros((b, h, dk, v.shape[-1]), f32),
                        (w, u0, mix, q_in, k_out, keep))
    # [N, B, H, C, Dv] -> [B, T, H, Dv]
    return jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(b, t, h, -1)
