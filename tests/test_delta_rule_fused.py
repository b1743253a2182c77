"""The fused form of the gated delta rule (``ops/pallas/delta_rule.py``) on the
CPU, its kernels under ``interpret=True`` at heads of 128 and two to four
chunks: against the token-by-token recurrence and the chunked XLA form, output
and all five gradients; its in-kernel inverse against ``solve_triangular``;
and which calls ``gated_delta_rule`` gives it (none on the CPU), with what
each lowered call says. What Mosaic makes of it is ``tests/test_chip_compile.py``'s,
what the chip does ``chip_smoke.kernels_phase``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from dynamic_load_balance_distributeddnn_tpu.ops import linear_attention
from dynamic_load_balance_distributeddnn_tpu.ops.pallas import delta_rule
from tests.conftest import traced_instants
from tests.test_qwen3_next_lm import recurrence, rule_operands

NAMES = ("q", "k", "v", "g", "beta")


def operands(decay, dtype=jnp.float32, t=256, hk=1, h=2, seed=0, b=1):
    """q and k at ``hk`` key heads, v at ``h`` value heads of 128."""
    q, k, v, g, beta = rule_operands(decay, b=b, t=t, h=h, dk=128, dv=128, seed=seed)
    return q[:, :, :hk].astype(dtype), k[:, :, :hk].astype(dtype), v.astype(dtype), g, beta


def repeated(q, k, v, g, beta):
    group = v.shape[2] // q.shape[2]
    return jnp.repeat(q, group, 2), jnp.repeat(k, group, 2), v, g, beta


def scalar(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))


def outputs_and_gradients(fn, args):
    return (fn(*args),) + jax.grad(scalar(fn), argnums=(0, 1, 2, 3, 4))(*args)


def distance(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def fused(block_t):
    return lambda *a: delta_rule.fused_delta_rule(*a, block_t=block_t, interpret=True)


# a step of one pair (the state crosses grid steps) and of two (it crosses pairs inside one)
@pytest.mark.parametrize("block_t", [128, 256])
@pytest.mark.parametrize("decay", [0.1, 2.0], ids=["slow_decay", "fast_decay"])
def test_fused_is_the_recurrence_and_the_chunked_form_in_float32(decay, block_t):
    args = operands(decay)
    with jax.default_matmul_precision("highest"):
        got = outputs_and_gradients(fused(block_t), args)
        want = outputs_and_gradients(lambda *a: recurrence(*repeated(*a)), args)
        chunked = outputs_and_gradients(
            lambda *a: linear_attention._chunked(*a, linear_attention.CHUNK), args)
    for name, g_, w_, c_ in zip(("o",) + NAMES, got, want, chunked):
        assert g_.shape == w_.shape and g_.dtype == w_.dtype, name
        assert distance(g_, w_) < 1e-5, name
        assert distance(g_, c_) < 1e-5, name


def test_fused_is_as_near_the_recurrence_in_bfloat16_as_the_chunked_form_is():
    """Same roundings in the same places: each of the six results lies inside
    the chunked form's own distance from the float32 recurrence (with a
    quarter of room: a rounding falls one way here and the other there)."""
    args = operands(0.5, jnp.bfloat16, seed=3)
    exact = tuple(x.astype(jnp.float32) for x in args)
    with jax.default_matmul_precision("highest"):
        want = outputs_and_gradients(lambda *a: recurrence(*repeated(*a)), exact)
    got = outputs_and_gradients(fused(256), args)
    chunked = outputs_and_gradients(
        lambda *a: linear_attention._chunked(*a, linear_attention.CHUNK), args)
    for name, g_, w_, c_ in zip(("o",) + NAMES, got, want, chunked):
        assert g_.dtype == c_.dtype, name
        ours, theirs = distance(g_, w_), distance(c_, w_)
        assert ours < 1.25 * theirs, (name, ours, theirs)


def system(keys, beta, rng, decay):
    """A pair's ``A``: two 64 x 64 strictly lower blocks of beta_i (k_i . k_j)
    under a mild decay."""
    run = np.cumsum(-decay * rng.uniform(size=(2, 64)), axis=1).reshape(128)
    a = beta[:, None] * np.exp(run[:, None] - run[None, :]) * (keys @ keys.T)
    i, j = np.arange(128)[:, None], np.arange(128)[None, :]
    return np.where((i > j) & (i // 64 == j // 64), a, 0.0)


@pytest.mark.parametrize("keys", ["random", "near_parallel"])
def test_the_kernels_inverse_is_solve_triangulars_to_float32_rounding(keys):
    """By rows and merged with float32 products, so no less exact than the
    XLA form's solve where a power series would lose everything: keys nearly
    parallel (k_i . k_j over 0.98) and beta near 1, every entry of A over 0.9."""
    rng = np.random.default_rng(7)
    if keys == "random":
        k = rng.normal(size=(128, 128))
        beta = rng.uniform(size=128)
    else:
        k = np.ones((1, 128)) + 0.01 * rng.normal(size=(128, 128))
        beta = 1.0 - 0.01 * rng.uniform(size=128)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    a = system(k, beta, rng, 0.05 if keys == "random" else 0.002)
    if keys == "near_parallel":
        assert a[63, :63].min() > 0.9
    exact = np.linalg.inv(np.eye(128) + a)
    a32 = jnp.asarray(a, jnp.float32)
    got = np.asarray(jax.jit(lambda x: delta_rule._invert([x, x])[1])(a32))
    eye = jnp.eye(128, dtype=jnp.float32)
    solved = np.asarray(solve_triangular(a32, eye, lower=True, unit_diagonal=True))
    scale = np.abs(exact).max()
    assert np.abs(solved - exact).max() < 1e-5 * scale
    assert np.abs(got - exact).max() < max(2 * np.abs(solved - exact).max(), 2e-6 * scale)
    i, j = np.arange(128)[:, None], np.arange(128)[None, :]
    assert not got[(i < j) | (i // 64 != j // 64)].any()  # lower triangular, block by block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_decay_past_underflow_gives_no_inf_or_nan_forward_or_backward(dtype):
    """g = -20 a token: the running sum passes -1,000 inside a chunk, where
    ``exp`` is 0 in float32 and a ratio of two exponentials would be 0/0."""
    q, k, v, g, beta = operands(1.0, dtype, t=128, h=1)
    g = jnp.full_like(g, -20.0)
    results = outputs_and_gradients(fused(128), (q, k, v, g, beta))
    for name, x in zip(("o",) + NAMES, results):
        assert bool(jnp.isfinite(x.astype(jnp.float32)).all()), name
    # every token forgets all before it: o_t = beta_t (k_t . q_t) v_t
    want = (beta[..., None] * jnp.sum(k.astype(jnp.float32) * q.astype(jnp.float32), -1,
                                      keepdims=True) * v.astype(jnp.float32))
    assert distance(results[0], want) < (1e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("case,why", [
    ("float32_many_columns", "float32"), ("ragged_window", "t"), ("head_of_64", "head_dim"),
    ("chunk_of_32", "chunk"), ("bfloat16_on_the_cpu", "cpu")])
def test_calls_the_kernel_does_not_serve_take_the_chunked_form_and_say_why(case, why):
    bf16 = jnp.bfloat16
    args, chunk = {
        "float32_many_columns": (operands(0.5, t=64, b=10), 64),
        "ragged_window": (operands(0.5, bf16, t=192), 64),
        "head_of_64": (tuple(x.astype(bf16) if x.ndim == 4 else x
                             for x in rule_operands(0.5, t=256, h=2, dk=64, dv=128)), 64),
        "chunk_of_32": (operands(0.5, bf16), 32),
        "bfloat16_on_the_cpu": (operands(0.5, bf16), 64),
    }[case]
    with traced_instants("linear_attention_path") as said:
        got = jax.jit(lambda *a: linear_attention.gated_delta_rule(*a, chunk))(*args)
    assert said == [{"path": "chunked", "why": why, "chunk": chunk, "t": args[0].shape[1],
                     "dtype": str(args[0].dtype), "heads": args[2].shape[2]}]
    exact = tuple(x.astype(jnp.float32) for x in args)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*repeated(*exact))
    assert got.dtype == args[2].dtype
    assert distance(got, want) < (2e-5 if args[0].dtype == jnp.float32 else 3e-2)


def test_the_fused_form_refuses_what_it_cannot_tile():
    with pytest.raises(ValueError, match="must divide by 256"):
        delta_rule.fused_delta_rule(*operands(0.5, jnp.bfloat16, t=128), interpret=True)
    q, k, v, g, beta = rule_operands(0.5, t=256, h=1, dk=64, dv=128)
    with pytest.raises(ValueError, match="head sizes"):
        delta_rule.fused_delta_rule(q, k, v, g, beta, interpret=True)
