"""Quantized gradient wire formats for cross-link collectives.

Generalizes the fused path's original int8 ``_compressed_psum``
(train/steps.py) into a reusable wire layer the hierarchical ICI/DCN
combine rides (ISSUE 12):

* ``"fp32"`` — the identity wire: full-precision psum, zero residual. The
  hierarchical structure still pays off on bandwidth-asymmetric links (only
  1/D of the tree crosses DCN), and this wire is the bitwise-parity
  reference the tests pin against the flat combine.
* ``"int8"`` — 127 quantization levels, shared per-hop ``pmax`` scale,
  STOCHASTIC rounding: ``E[dequant] == value`` exactly (the unbiasedness
  the tests assert), so convergence needs no correction — the error-
  feedback residual still captures each step's realized rounding error.
* ``"int4"`` — 7 levels, round-to-NEAREST: biased per step (cheaper — no
  per-element rng — and a stand-in for any aggressive biased compressor,
  e.g. top-magnitude), made convergent by the error-feedback residual
  carried in the TrainState: ``e' = v - dequant(quant(v))`` is added back
  into the next step's pre-quantization value, so quantization error
  accumulates into the weights instead of being lost (EF-SGD).

The integer sum crosses the link in the narrowest dtype that cannot
overflow ``n_participants * levels`` — int16 for the int8 wire (the
original convention: half the f32 bytes), int8 for the int4 wire on meshes
up to 18 hosts (a quarter).
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

AxisName = Union[str, Tuple[str, ...]]

WIRE_FORMATS = ("fp32", "int8", "int4")
_LEVELS = {"int8": 127, "int4": 7}


def wire_levels(wire: str) -> int:
    return _LEVELS[wire]


def wire_sum_dtype(wire: str, n_participants: int):
    """Narrowest integer dtype whose range holds the worst-case wire sum."""
    if n_participants * _LEVELS[wire] <= 127:
        return jnp.int8
    if n_participants * _LEVELS[wire] <= 32767:
        return jnp.int16
    return jnp.int32


def wire_payload_bytes(wire: str, n_participants: int) -> int:
    """Per-element bytes a reduction in this wire format moves across the
    link (the dtype the SUM travels in — quantized values are widened to it
    before the collective so no participant can overflow)."""
    if wire == "fp32":
        return 4
    return jnp.dtype(wire_sum_dtype(wire, n_participants)).itemsize


def _dither(key, shape) -> jnp.ndarray:
    """U[0,1) dither field from a cheap counter hash (murmur3 finalizer over
    element index x key-derived seed). Stochastic rounding needs uniform
    MARGINALS per element per step, not cryptographic randomness — and the
    counter-based threefry behind ``jax.random.uniform`` costs ~10x the
    collective it dithers on both CPU and TPU (measured 114 ms vs 14 ms for
    the DCN hop's chunk on the CPU tier). Six vector int-ops per element
    keeps the quantizer off the combine's critical path."""
    kd = jnp.asarray(jax.random.key_data(key), dtype=jnp.uint32).reshape(-1)
    seed = kd[0] ^ (kd[-1] * jnp.uint32(0x9E3779B9))
    n = 1
    for s in shape:
        n *= int(s)
    x = jax.lax.iota(jnp.uint32, n) * jnp.uint32(2654435761) + seed
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    u = (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    return u.reshape(shape)


def quantize_stochastic(v: jnp.ndarray, key, scale, levels: int) -> jnp.ndarray:
    """Unbiased stochastic rounding to ``[-levels, levels]`` integer steps of
    ``scale``: ``E[q] * scale == v`` for every in-range v (floor(x + U[0,1))
    is x's unbiased integer rounding; the dither field is uniform per
    element and fresh per key — see :func:`_dither`)."""
    u = _dither(key, v.shape)
    return jnp.clip(
        jnp.floor(v.astype(jnp.float32) / scale + u), -levels, levels
    )


def quantize_nearest(v: jnp.ndarray, scale, levels: int) -> jnp.ndarray:
    """Round-to-nearest quantization: biased per step (bias bounded by
    scale/2 per element) — the error-feedback residual carries the bias
    forward so it cancels over steps."""
    return jnp.clip(
        jnp.round(v.astype(jnp.float32) / scale), -levels, levels
    )


def tree_hop_widths(
    n_elems: int, sizes: Tuple[int, ...], pad_multiple: int = 0
) -> Tuple[int, ...]:
    """Per-hop payload widths (f32 elements per participant) of the tree
    spine, outermost-first: ``widths[i]`` is the length of the vector that
    crosses hop ``i``, ``widths[-1]`` the full padded tree and ``widths[0]``
    the top chunk each device carries across the slowest link. Shared by the
    residual allocator (one row-block per hop 0..k-1), the engine's
    bytes-on-wire accounting and the ZeRO-1 combine — one formula, no drift.

    ``pad_multiple`` raises the padding granularity (the ZeRO-1 composition
    pads the raveled tree to a multiple of the WHOLE device count so the
    final per-device slice is rectangular); it must itself be a multiple of
    the inner group product."""
    inner = 1
    for s in sizes[1:]:
        inner *= s
    m = max(int(pad_multiple), inner)
    if m % inner:
        raise ValueError(f"pad_multiple {pad_multiple} not a multiple of {inner}")
    padded = -(-n_elems // m) * m
    widths = []
    div = 1
    for s in reversed(sizes[1:]):
        widths.append(padded // div)  # innermost..: width entering hop i
        div *= s
    widths.append(padded // div)  # hop 0 (the top chunk)
    return tuple(reversed(widths))


# Modeled quantize/dequant memory passes per wire, priced at the fastest
# link's rate (a memory-bandwidth proxy). int4 carries an extra ACCURACY tax
# on top of its real two passes: round-to-nearest is biased, so it should
# only win when the link is so slow that halving int8's payload dominates
# (~20x asymmetry at the default weights; int8 needs ~6x to beat fp32).
_WIRE_COST_PASSES = {"fp32": 0.0, "int8": 3.0, "int4": 8.0}


def choose_wires(
    sizes: Tuple[int, ...], level_bytes_per_s
) -> Tuple[str, ...]:
    """Per-hop codec choice from MEASURED link rates (the bandwidth probe's
    ``level_bytes_per_s``, outermost-first) against a bytes-vs-quantization
    cost model: hop ``i``'s modeled per-element cost is

        payload_bytes(wire, sizes[i]) / rate_i  +  passes(wire) * 4 / rate_ref

    and the cheapest wire wins (ties resolve toward less compression). The
    innermost hop is ALWAYS fp32 — it is the fastest link by construction
    and keeping it exact is what bounds the residual set to hops 0..k-1.
    Unmeasured/non-positive rates degrade to fp32 for that hop (never guess
    a codec from missing data). Deterministic: same rates, same tree, same
    codecs on every process."""
    rates = [float(r) if r and float(r) > 0 else 0.0 for r in level_bytes_per_s]
    if len(rates) != len(sizes):
        raise ValueError("one measured rate per level")
    r_ref = max(rates) if rates else 0.0
    out = []
    for i, (s, r) in enumerate(zip(sizes, rates)):
        if i == len(sizes) - 1 or r <= 0.0 or r_ref <= 0.0:
            out.append("fp32")
            continue
        out.append(
            min(
                WIRE_FORMATS,
                key=lambda w: wire_payload_bytes(w, s) / r
                + _WIRE_COST_PASSES[w] * 4.0 / r_ref,
            )
        )
    return tuple(out)


def tree_allreduce(
    grads,
    key,
    names: Tuple[str, ...],
    sizes: Tuple[int, ...],
    wires: Tuple[str, ...],
    residuals=None,
):
    """The N-level combine spine (inside a shard_map body; ISSUE 17, after
    DynamiQ's compressed multi-hop all-reduce). ``names``/``sizes``/``wires``
    are the topology tree's levels OUTERMOST-first (``wires[i]`` is hop i's
    codec; the innermost hop must be fp32 — enforce, don't trust).

    Ravel the gradient tree ONCE, then:

    * **up** — reduce-scatter over the innermost axis at full precision,
      then one error-fed compressed reduce-scatter per middle level
      (each halves-or-better the bytes ON that level's link and divides the
      payload by the level size), and finally one compressed all-reduce
      across the outermost (slowest) axis;
    * **down** — all-gather back through levels 1..k in order, inverting the
      scatters (each gather re-concatenates the chunks the matching scatter
      dealt, so the flat layout reconstructs exactly).

    ``residuals`` is None or a tuple with one per-hop row for hops 0..k-1
    (``tree_hop_widths`` gives the lengths); the return's second element is
    the matching tuple of new residuals (identically zero on fp32 hops, so
    the state layout is codec-independent). Per-hop dither keys fold the hop
    index so no two compressed hops share a rounding field.

    With two levels and ``wires=(w, "fp32")`` this IS the PR-12 spine,
    bit-for-bit at the fp32 wire. Called by StepLibrary._hier_combine and,
    directly, by tests/test_grad_comm.py."""
    import jax.flatten_util

    k = len(names) - 1
    if k < 1 or len(sizes) != k + 1 or len(wires) != k + 1:
        raise ValueError("tree_allreduce needs >= 2 aligned levels")
    if wires[-1] != "fp32":
        raise ValueError(
            f"innermost hop must ride the fp32 wire, got {wires[-1]!r} "
            "(residuals exist only for hops 0..k-1)"
        )
    flat, unravel = jax.flatten_util.ravel_pytree(grads)
    t_real = flat.size
    inner = 1
    for s in sizes[1:]:
        inner *= s
    padded = -(-t_real // inner) * inner
    v = jnp.pad(flat, (0, padded - t_real))
    # up: innermost hop, exact
    v = jax.lax.psum_scatter(v, names[k], scatter_dimension=0, tiled=True)
    new_res = [None] * k
    for i in range(k - 1, 0, -1):  # middle hops, error-fed reduce-scatter
        vi = v + (residuals[i] if residuals is not None else 0.0)
        v, sent = compressed_reduce_scatter_ef(
            vi, jax.random.fold_in(key, i), names[i], sizes[i], wires[i]
        )
        new_res[i] = vi - sent
    v0 = v + (residuals[0] if residuals is not None else 0.0)
    total, sent = compressed_reduce(
        v0, jax.random.fold_in(key, 0), names[0], sizes[0], wires[0]
    )
    new_res[0] = v0 - sent
    # down: gathers invert the scatters last-to-first
    out = total
    for i in range(1, k + 1):
        out = jax.lax.all_gather(out, names[i], tiled=True)
    return unravel(out[:t_real]), tuple(new_res)


def compressed_reduce_scatter_ef(
    v: jnp.ndarray,
    key,
    axis: AxisName,
    n_participants: int,
    wire: str,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One compressed reduce-scatter hop over ``axis`` (inside shard_map),
    with the error-feedback contract of :func:`compressed_reduce`: returns
    ``(scattered_sum, sent)`` where ``sent`` is THIS participant's
    dequantized contribution (full pre-scatter width — the caller's residual
    is ``v - sent``, zero for fp32). ``v``'s leading dim must divide by the
    axis size (the callers' tree/ZeRO-1 padding guarantees it)."""
    if wire == "fp32":
        return (
            jax.lax.psum_scatter(v, axis, scatter_dimension=0, tiled=True),
            v,
        )
    levels = _LEVELS[wire]
    amax = jax.lax.pmax(jnp.max(jnp.abs(v)), axis)
    scale = jnp.maximum(amax / levels, jnp.finfo(jnp.float32).tiny)
    if wire == "int8":
        q = quantize_stochastic(v, key, scale, levels)
    else:
        q = quantize_nearest(v, scale, levels)
    s = jax.lax.psum_scatter(
        q.astype(wire_sum_dtype(wire, n_participants)),
        axis,
        scatter_dimension=0,
        tiled=True,
    )
    return s.astype(jnp.float32) * scale, q.astype(jnp.float32) * scale


def compressed_reduce_scatter(
    v: jnp.ndarray,
    key,
    axis: AxisName,
    n_participants: int,
    wire: str,
) -> jnp.ndarray:
    """The residual-free reduce-scatter hop (the flat ZeRO-1 path's gradient
    collective riding the quantized wire): :func:`compressed_reduce_scatter_ef`
    without the error-feedback return — the int8 wire's stochastic rounding
    keeps the scattered sum unbiased with no residual needed."""
    return compressed_reduce_scatter_ef(v, key, axis, n_participants, wire)[0]


def compressed_reduce(
    v: jnp.ndarray,
    key,
    axis: AxisName,
    n_participants: int,
    wire: str,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One compressed all-reduce hop over ``axis`` (inside shard_map).

    Returns ``(total, sent)``: the dequantized cross-``axis`` sum, and THIS
    participant's dequantized contribution — the value the wire actually
    carried for us, so the caller's error-feedback residual is
    ``v - sent`` (zero for the fp32 wire). The quantization scale is shared
    across the hop via ``pmax`` (one scalar per hop, negligible next to the
    tensor payload)."""
    if wire == "fp32":
        return jax.lax.psum(v, axis), v
    levels = _LEVELS[wire]
    amax = jax.lax.pmax(jnp.max(jnp.abs(v)), axis)
    scale = jnp.maximum(amax / levels, jnp.finfo(jnp.float32).tiny)
    if wire == "int8":
        q = quantize_stochastic(v, key, scale, levels)
    else:
        q = quantize_nearest(v, scale, levels)
    s = jax.lax.psum(q.astype(wire_sum_dtype(wire, n_participants)), axis)
    return s.astype(jnp.float32) * scale, q.astype(jnp.float32) * scale
