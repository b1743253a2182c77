"""The gated delta rule of ``ops/linear_attention.py`` as two kernels, one a
direction: a chunk's system and the heads' states never leave VMEM.

The recurrence, the chunked form and its notation are the module's
(``ops/linear_attention.py``); the chunk is 64 tokens. ``q`` and ``k`` are
``[B, T, Hk, Dk]``, ``v`` ``[B, T, H, Dv]`` as the model holds them (value
head ``h`` reads key head ``h // (H / Hk)``: nothing is repeated), ``Dk = Dv =
128``; seen as ``[B, T, H*D]`` a head is 128 neighbouring lanes, so no operand
is transposed. The running sums of ``g`` inside each chunk and ``beta`` come
as ``[B, H, T/128, 128]`` float32 rows (tokens in lanes), a head's whole.

The grid is ``(column, key head, step)``, the last axis sequential: a step
takes ``block_t`` tokens of a key head and of all its value heads, two chunks
(a *pair*, 128 tokens) at a time in a loop. ``k k^T`` and ``q k^T`` are made
once for the key head. A value head's pair is one block-diagonal ``[128, 128]``
system, so every tile is whole vregs and whole MXU tiles: the decays
``exp(G_i - G_j)`` (``exp`` of a difference that is at most 0 wherever it is
kept), ``A``, ``(I + A)^-1``, ``W``, ``U0``, ``mix``, ``q_in`` and ``k_out``
are made in VMEM from the pair's q, k, v, running sums and beta and used
there. Column forms of the per-token factors come from one transpose of a
tile of their rows.

*The inverse* is forward substitution in float32. The 16 x 16 diagonal blocks,
eight a system, are packed side by side in a ``[16, 128]`` tile (the value
heads' tiles side by side again) and eliminated together, 15 steps of ``X -=
column_j(D) row_j(X)`` (right-looking: row ``j`` is final when it is used); a
column is spread over its block's lanes by rolls, off the steps' chain. The
blocks are then merged twice, ``[[T1, 0], [-T2 A21 T1, T2]]``, by float32
products (``Precision.HIGHEST``) of the rows that are not 0. No power series:
a Neumann product is not exact on strongly correlated keys.

*The states* ``[128, 128]``, one a value head, stay in a float32 VMEM scratch
over the steps of a (column, key head), and the heads' chains of products run
side by side; products take the operands' dtype with float32 accumulation,
and the roundings are the chunked form's (``T``, ``W``, ``U0``, ``mix``,
``q_in``, ``k_out``, ``U`` and the state as read are cast where it casts
them). The forward pass under ``jax.grad`` also writes the state each chunk
starts from (float32, ``[B, H, T/64, 128, 128]``: what the chunked form's scan
holds); nothing of size 64 x 64 goes to HBM.

*The backward pass* walks the steps in reverse with ``dS`` in a float32 VMEM
scratch, makes the pair's systems again from q, k, v, the sums and beta, the
chunk's ``U`` from the saved state, and writes dq and dk (a key head's, summed
over its value heads in float32), dv and, as rows, the gradients of the
running sums and of beta. Through the inverse: ``dA = -T^T dT T^T``, strictly
lower part, in float32 products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
PAIR = 2 * CHUNK  # tokens of one block-diagonal system: whole tiles
BASE = 16         # diagonal blocks eliminated by rows before the merges
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_F32 = jnp.float32


def _dot(a, b, dims=_NN):
    """A product in the operands' dtype, accumulated in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot_exact(a, b):
    """``a @ b`` in float32 products of float32 operands: the solve's."""
    return jax.lax.dot_general(a, b, _NN, preferred_element_type=_F32,
                               precision=jax.lax.Precision.HIGHEST)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _invert(systems):
    """``(I + a)^-1`` for every ``a`` of ``systems``, each ``[128, 128]``
    float32, strictly lower triangular inside each 64 x 64 diagonal block and
    0 outside them. The systems' diagonal blocks are eliminated side by side
    in one tile: 15 steps for several systems as for one (on the chip two
    heads' tiles eliminated one after the other read 4 % faster forward all
    the same: PERF.md section 6, PR 33, finding 3)."""
    n, count = systems[0].shape[0], len(systems)
    width = n * count
    lane, sub = _iota((BASE, width), 1), _iota((BASE, width), 0)
    # the 16 x 16 diagonal blocks side by side: packed[r, 16 b + c] = a[16 b + r, 16 b + c]
    wide = jnp.concatenate(systems, axis=1) if count > 1 else systems[0]
    packed = jnp.zeros((BASE, width), _F32)
    for b in range(n // BASE):
        packed = packed + jnp.where((lane % n) // BASE == b, wide[BASE * b:BASE * (b + 1), :], 0.0)
    x = jnp.where(lane % BASE == sub, 1.0, 0.0).astype(_F32)
    for j in range(BASE - 1):
        # column j of every block, spread over the block's 16 lanes
        col = jnp.where(lane % BASE == j, packed, 0.0)
        if j:
            col = pltpu.roll(col, width - j, 1)
        shift = 1
        while shift < BASE:
            col = col + pltpu.roll(col, shift, 1)
            shift *= 2
        x = x - col * x[j:j + 1, :]
    return [_merged(a, x[:, i * n:(i + 1) * n]) for i, a in enumerate(systems)]


def _merged(a, x):
    """The inverse of one system from its diagonal blocks' inverses ``x``,
    packed ``[16, 128]``."""
    n = a.shape[0]
    lane = _iota((BASE, n), 1)
    inv = jnp.concatenate(
        [jnp.where(lane // BASE == b, x, 0.0) for b in range(n // BASE)], axis=0)
    size = BASE
    while size < CHUNK:
        # [[T1, 0], [-T2 A21 T1, T2]] for every pair of neighbouring blocks. Only
        # the second block of each pair has rows in either product, so they are
        # taken out and pushed through alone: half the rows.
        def second(m):
            return jnp.concatenate([m[i:i + size] for i in range(size, n, 2 * size)], axis=0)

        def back(m, zero=jnp.zeros((size, n), _F32)):  # the reverse: the first blocks 0
            return jnp.concatenate(
                [part for i in range(0, n // 2, size) for part in (zero, m[i:i + size])], axis=0)

        half = (n // 2, n)
        first_block = 2 * (_iota(half, 0) // size)  # of the row's pair, in blocks of `size`
        a21 = jnp.where(_iota(half, 1) // size == first_block, second(a), 0.0)
        inv = inv - back(_dot_exact(second(inv), back(_dot_exact(a21, inv))))
        size *= 2
    return inv


def _columns(*rows):
    """``[1, 128]`` rows as ``[128, 1]`` columns: one transpose of a tile
    that holds them."""
    n = rows[0].shape[1]
    sub = _iota((n, n), 0)
    tile = jnp.zeros((n, n), _F32)
    for i, r in enumerate(rows):
        tile = jnp.where(sub == i, r, tile)
    tile = tile.T
    return tuple(tile[:, i:i + 1] for i in range(len(rows)))


def _rows(*columns):
    """The reverse of :func:`_columns`."""
    n = columns[0].shape[0]
    lane = _iota((n, n), 1)
    tile = jnp.zeros((n, n), _F32)
    for i, c in enumerate(columns):
        tile = jnp.where(lane == i, c, tile)
    tile = tile.T
    return tuple(tile[i:i + 1, :] for i in range(len(columns)))


def _shared(q, k, transposed=False):
    """What the value heads of one key head share in a pair: ``q`` and ``k``
    ``[128, 128]`` in the operands' dtype, in float32, and their products."""
    out = {"q": q, "k": k, "q32": q.astype(_F32), "k32": k.astype(_F32),
           "kk": _dot(k, k, _NT), "qk": _dot(q, k, _NT)}
    if transposed:
        out["kq"] = _dot(k, q, _NT)
    return out


def _systems(shared, heads, transposed=False):
    """What a pair's 128 tokens give each value head of a key head before any
    state: ``shared`` the key head's part (:func:`_shared`), ``heads`` a list
    of ``(v, run, beta)``: ``v`` ``[128, 128]`` in the operands' dtype, ``run``
    (the running sum of g inside each chunk) and ``beta`` ``[1, 128]`` float32
    rows."""
    n = shared["k"].shape[0]
    k32, q32 = shared["k32"], shared["q32"]
    row, column = _iota((n, n), 0), _iota((n, n), 1)
    same = row // CHUNK == column // CHUNK
    lower = jnp.logical_and(same, row >= column)
    strict = jnp.logical_and(same, row > column)
    lane = _iota((1, n), 1)
    outs = []
    for v, run, beta in heads:
        last = jnp.where(lane < CHUNK, run[:, CHUNK - 1:CHUNK], run[:, n - 1:n])  # G_C of its chunk
        e_in, e_out = jnp.exp(run), jnp.exp(last - run)
        run_c, beta_c, e_in_c, e_out_c, w_in_c, keep_c = _columns(
            run, beta, e_in, e_out, beta * e_in, jnp.exp(last))
        # G_i - G_j; 0 above the diagonal and across chunks
        decay = jnp.exp(jnp.where(lower, run_c - run, -jnp.inf))
        out = {
            "decay": decay, "dkk": jnp.where(strict, decay * shared["kk"], 0.0),
            "dqk": decay * shared["qk"], "v32": v.astype(_F32),
            # each chunk's whole decay, a column as long as the state
            "keep": tuple(jnp.concatenate([keep_c[c * CHUNK:(c + 1) * CHUNK]] * 2, axis=0)
                          for c in range(2)),
            "beta_c": beta_c, "e_in_c": e_in_c, "e_out_c": e_out_c, "w_in_c": w_in_c,
            "lower": lower, "strict": strict,
        }
        if transposed:
            # mix^T made as it stands, keys in sublanes: exp(G_i - G_j) with i in lanes
            upper = jnp.logical_and(same, row <= column)
            out["mix_t"] = (jnp.exp(jnp.where(upper, run - run_c, -jnp.inf))
                            * shared["kq"]).astype(v.dtype)
        outs.append(out)
    inverses = _invert([out["beta_c"] * out["dkk"] for out in outs])
    for out, inv, (v, _, _) in zip(outs, inverses, heads):
        dt = v.dtype
        inv_b = inv.astype(dt)
        k_in32, q_in32, k_out32 = k32 * out["w_in_c"], q32 * out["e_in_c"], k32 * out["e_out_c"]
        k_in, v_in = k_in32.astype(dt), (out["v32"] * out["beta_c"]).astype(dt)
        out.update({
            "inv": inv, "k_in": k_in, "v_in": v_in, "w": _dot(inv_b, k_in).astype(dt),
            "u0": _dot(inv_b, v_in).astype(dt).astype(_F32), "mix": out["dqk"].astype(dt),
            "q_in": q_in32.astype(dt), "k_out": k_out32.astype(dt),
            "k_in32": k_in32, "q_in32": q_in32, "k_out32": k_out32,
        })
    return outs


def _chunk_u(sys, c, state_b):
    """``U`` of chunk ``c`` of the pair from the state it starts from."""
    rows = slice(c * CHUNK, (c + 1) * CHUNK)
    return (sys["u0"][rows] - _dot(sys["w"][rows], state_b)).astype(state_b.dtype)


def _pair_rows(u, c):
    """One chunk's rows as the pair's, the other chunk's 0."""
    zero = jnp.zeros_like(u)
    return jnp.concatenate([u, zero] if c == 0 else [zero, u], axis=0)


def _for_pairs(pairs, body, reverse=False):
    """``body(p)`` over a step's pairs in order, or from the last to the
    first. A loop, not unrolled code: the kernels are traced and lowered for
    every program that holds them, and a step of four pairs unrolled took a
    quarter of a minute a program longer (PERF.md section 6, PR 33)."""
    def trip(i, carry):
        body(pairs - 1 - i if reverse else i)
        return carry

    jax.lax.fori_loop(0, pairs, trip, 0)


def _delta_rule_fwd(q_ref, k_ref, v_ref, run_ref, beta_ref, o_ref, *rest, pairs, heads, save):
    states_ref, state = (rest[0], rest[1]) if save else (None, rest[0])
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    dt, d = v_ref.dtype, q_ref.shape[-1]

    def pair(p):
        tokens = pl.ds(pl.multiple_of(p * PAIR, PAIR), PAIR)
        at = pl.ds(step * pairs + p, 1)
        shared = _shared(q_ref[0, tokens, :], k_ref[0, tokens, :])
        systems = _systems(shared, [(v_ref[0, tokens, h * d:(h + 1) * d], run_ref[0, h, at, :],
                                     beta_ref[0, h, at, :]) for h in range(heads)])
        outs = [[] for _ in range(heads)]
        # the heads' chains side by side: one's products fill the other's waits
        for c in range(2):
            rows = slice(c * CHUNK, (c + 1) * CHUNK)
            for h, sys in enumerate(systems):
                s = state[h]
                if save:
                    states_ref[0, h, 2 * p + c] = s
                s_b = s.astype(dt)
                u = _chunk_u(sys, c, s_b)
                outs[h].append(_dot(sys["q_in"][rows], s_b)
                               + _dot(sys["mix"][rows], _pair_rows(u, c)))
                state[h] = sys["keep"][c] * s + _dot(sys["k_out"][rows], u, _TN)
        for h in range(heads):
            o_ref[0, tokens, h * d:(h + 1) * d] = jnp.concatenate(outs[h], axis=0).astype(
                o_ref.dtype)

    _for_pairs(pairs, pair)


def _delta_rule_bwd(q_ref, k_ref, v_ref, run_ref, beta_ref, do_ref, states_ref,
                    dq_ref, dk_ref, dv_ref, drun_ref, dbeta_ref, dstate, *, pairs, heads):
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    dt, d = v_ref.dtype, q_ref.shape[-1]
    lane = _iota((1, PAIR), 1)

    def pair(p):
        tokens = pl.ds(pl.multiple_of(p * PAIR, PAIR), PAIR)
        at = pl.ds((steps - 1 - step) * pairs + p, 1)
        shared = _shared(q_ref[0, tokens, :], k_ref[0, tokens, :], transposed=True)
        q, k = shared["q"], shared["k"]
        systems = _systems(shared, [(v_ref[0, tokens, h * d:(h + 1) * d], run_ref[0, h, at, :],
                                     beta_ref[0, h, at, :]) for h in range(heads)],
                           transposed=True)
        dos = [do_ref[0, tokens, h * d:(h + 1) * d] for h in range(heads)]
        parts = [{n: [None, None] for n in ("dq_in", "dk_out", "dmix", "du", "dw", "dkeep")}
                 for _ in range(heads)]
        for c in (1, 0):
            rows = slice(c * CHUNK, (c + 1) * CHUNK)
            for h, sys in enumerate(systems):
                part, do = parts[h], dos[h]
                s = states_ref[0, h, 2 * p + c]
                s_b = s.astype(dt)
                ds = dstate[h]
                ds_b = ds.astype(dt)
                u = _chunk_u(sys, c, s_b)
                do_c = do[rows]
                part["dq_in"][c] = _dot(do_c, s_b, _NT)
                part["dmix"][c] = _dot(do_c, _pair_rows(u, c), _NT)
                du_b = (_dot(sys["mix_t"][rows], do) + _dot(sys["k_out"][rows], ds_b)).astype(dt)
                part["du"][c] = du_b
                part["dk_out"][c] = _dot(u, ds_b, _NT)
                part["dkeep"][c] = jnp.sum(jnp.sum(ds * s, axis=1, keepdims=True), axis=0,
                                           keepdims=True)
                part["dw"][c] = -_dot(du_b, s_b, _NT)
                dstate[h] = (sys["keep"][c] * ds + _dot(sys["q_in"][rows], do_c, _TN)
                             - _dot(sys["w"][rows], du_b, _TN))
        dq_sum, dk_sum = jnp.zeros((PAIR, d), _F32), jnp.zeros((PAIR, d), _F32)
        for h, sys in enumerate(systems):
            dq_in, dk_out, dmix, du, dw = (jnp.concatenate(parts[h][n], axis=0) for n in
                                           ("dq_in", "dk_out", "dmix", "du", "dw"))
            dkeep = parts[h]["dkeep"]
            dw_b = dw.astype(dt)
            # through W = T k_in and U0 = T v_in, then through the inverse
            d_inv = _dot(dw_b, sys["k_in"], _NT) + _dot(du, sys["v_in"], _NT)
            inv_t = sys["inv"].T
            inv_tb = inv_t.astype(dt)
            dk_in, dv_in = _dot(inv_tb, dw_b), _dot(inv_tb, du)
            da = -_dot_exact(inv_t, _dot_exact(d_inv, inv_t))
            m = da * sys["dkk"]  # dkk is 0 on and above the diagonal and across chunks
            dkk_b = jnp.where(sys["strict"], da * sys["beta_c"] * sys["decay"], 0.0).astype(dt)
            dmix = jnp.where(sys["lower"], dmix, 0.0)
            dqk_b = (dmix * sys["decay"]).astype(dt)
            n = sys["beta_c"] * m + dmix * sys["dqk"]  # dD * D
            # a token's factors: exp(G) in q_in and k_in, exp(G_C - G) in k_out, beta in k_in
            # and v_in; the sums over a head's width are taken of what shares a factor
            to_out = dk_out * sys["k_out32"]
            dbeta_c = (jnp.sum(m + dv_in * sys["v32"], axis=1, keepdims=True)
                       + jnp.sum(dk_in * shared["k32"], axis=1, keepdims=True) * sys["e_in_c"])
            drun_c = jnp.sum(n + dq_in * sys["q_in32"] - to_out + dk_in * sys["k_in32"],
                             axis=1, keepdims=True)
            drun, dbeta = _rows(drun_c, dbeta_c)
            drun = drun - jnp.sum(n, axis=0, keepdims=True)
            # what reaches the chunk's last running sum: k_out's factors and the state's decay
            to_last = tuple(
                jnp.sum(jnp.sum(to_out[c * CHUNK:(c + 1) * CHUNK], axis=0, keepdims=True),
                        axis=1, keepdims=True) + dkeep[c] * sys["keep"][c][:1]
                for c in range(2))
            drun = drun + jnp.where(lane == CHUNK - 1, to_last[0], 0.0) \
                + jnp.where(lane == PAIR - 1, to_last[1], 0.0)
            drun_ref[0, h, at, :] = drun
            dbeta_ref[0, h, at, :] = dbeta
            dq_sum = dq_sum + dq_in * sys["e_in_c"] + _dot(dqk_b, k)
            dk_sum = (dk_sum + dk_out * sys["e_out_c"] + dk_in * sys["w_in_c"] + _dot(dkk_b, k)
                      + _dot(dkk_b, k, _TN) + _dot(dqk_b, q, _TN))
            dv_ref[0, tokens, h * d:(h + 1) * d] = (dv_in * sys["beta_c"]).astype(dv_ref.dtype)
        dq_ref[0, tokens, :] = dq_sum.astype(dq_ref.dtype)
        dk_ref[0, tokens, :] = dk_sum.astype(dk_ref.dtype)

    _for_pairs(pairs, pair, reverse=True)


def _specs(q, v, block_t, reverse):
    """Block specs and shapes by kind, a key head's group of value heads a
    step: "keys" (q, k and their gradients: the key head), "values" (v, o and
    their gradients: its value heads, neighbours in the lanes), "rows" (the
    running sums and beta and their gradients, the heads' whole ``[T/128,
    128]``) and "states"."""
    b, t, hk, d = q.shape
    h = v.shape[2]
    group, steps, chunks = h // hk, t // block_t, block_t // CHUNK

    def at(i):
        return steps - 1 - i if reverse else i

    return {
        "keys": (pl.BlockSpec((1, block_t, d), lambda b, h, i: (b, at(i), h)),
                 jax.ShapeDtypeStruct((b, t, hk * d), q.dtype)),
        "values": (pl.BlockSpec((1, block_t, group * d), lambda b, h, i: (b, at(i), h)),
                   jax.ShapeDtypeStruct((b, t, h * d), v.dtype)),
        "rows": (pl.BlockSpec((1, group, t // PAIR, PAIR), lambda b, h, i: (b, h, 0, 0)),
                 jax.ShapeDtypeStruct((b, h, t // PAIR, PAIR), _F32)),
        "states": (pl.BlockSpec((1, group, chunks, d, d), lambda b, h, i: (b, h, at(i), 0, 0)),
                   jax.ShapeDtypeStruct((b, h, t // CHUNK, d, d), _F32)),
    }


def _call(kernel, q, v, ins, outs, block_t, reverse, interpret, **static):
    b, t, hk, d = q.shape
    h = v.shape[2]
    if t % block_t or block_t % PAIR or h % hk or d != PAIR or v.shape[3] != d:
        raise ValueError(f"delta rule kernel: T={t} must divide by {block_t}, heads {h} by "
                         f"{hk}, and both head sizes be {PAIR} (got {d}, {v.shape[3]})")
    specs = _specs(q, v, block_t, reverse)
    return pl.pallas_call(
        functools.partial(kernel, pairs=block_t // PAIR, heads=h // hk, **static),
        grid=(b, hk, t // block_t),
        in_specs=[specs[n][0] for n in ins],
        out_specs=[specs[n][0] for n in outs],
        out_shape=[specs[n][1] for n in outs],
        scratch_shapes=[pltpu.VMEM((h // hk, d, d), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=kernel.__name__.lstrip("_"),
    )


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


_INS = ("keys", "keys", "values", "rows", "rows")


# The kernels are long straight-line programs: each is traced and lowered once
# a program, under `jit`, and every further call of the same shapes is a call
# of that function (36 calls stand in one superstep of the Qwen3-Next cell).
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _forward(q, k, v, run, beta, block_t, interpret, save):
    outs = ("values", "states") if save else ("values",)
    got = _call(_delta_rule_fwd, q, v, _INS, outs, block_t, False, interpret, save=save)(
        _flat(q), _flat(k), _flat(v), run, beta)
    return got[0].reshape(v.shape), (got[1] if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, run, beta, block_t, interpret):
    return _forward(q, k, v, run, beta, block_t, interpret, False)[0]


def _rule_fwd(q, k, v, run, beta, block_t, interpret):
    o, states = _forward(q, k, v, run, beta, block_t, interpret, True)
    return o, (q, k, v, run, beta, states)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(q, k, v, run, beta, do, states, block_t, interpret):
    dq, dk, dv, drun, dbeta = _call(
        _delta_rule_bwd, q, v, _INS + ("values", "states"),
        ("keys", "keys", "values", "rows", "rows"), block_t, True, interpret,
    )(_flat(q), _flat(k), _flat(v), run, beta, _flat(do), states)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), drun, dbeta


def _rule_bwd(block_t, interpret, saved, do):
    return _backward(*saved[:5], do, saved[5], block_t, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def fused_delta_rule(q, k, v, g, beta, block_t: int = 256, interpret: bool = False):
    """The gated delta rule from a zero state in chunks of 64: ``q`` and ``k``
    ``[B, T, Hk, 128]``, ``v`` ``[B, T, H, 128]`` (``H`` a multiple of
    ``Hk``), ``g`` and ``beta`` ``[B, T, H]``; ``T`` must divide by
    ``block_t``, a multiple of 128. Returns ``[B, T, H, 128]`` in ``v``'s
    dtype. The running sums of ``g`` inside each chunk are taken here, in
    float32, and their gradient goes back through them."""
    b, t, h = g.shape

    def rows(x):  # [B, T, H] -> [B, H, T/128, 128]
        return jnp.moveaxis(x, 1, 2).reshape(b, h, t // PAIR, PAIR)

    run = jnp.cumsum(g.astype(_F32).reshape(b, t // CHUNK, CHUNK, h), axis=2).reshape(b, t, h)
    return _rule(q, k, v, rows(run), rows(beta.astype(_F32)), block_t, interpret)
