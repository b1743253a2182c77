"""The fused attention kernel (``ops/pallas/fused_attention.py``) against dense
attention under the same mask, in interpret mode on the CPU, and the choice
``ops/attention.py`` makes between it and the blocked form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.ops.attention import (
    FUSED_BLOCK,
    _blocked,
    blocked_causal_attention,
)
from dynamic_load_balance_distributeddnn_tpu.ops.pallas.fused_attention import (
    fused_causal_attention,
)
from tests.conftest import traced_instants

B, T, D = 2, 512, 128


def dense_attention(q, k, v, window):
    """All ``[T, T]`` scores at once, float32 at ``highest``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(b, t, hkv, h // hkv, d), k,
                   precision="highest") / np.sqrt(d)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    mask = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision="highest").reshape(b, t, h, d)


def operands(h, hkv, dtype=jnp.bfloat16, d=D):
    keys = jax.random.split(jax.random.PRNGKey(h * 8 + hkv), 4)
    q = jax.random.normal(keys[0], (B, T, h, d), dtype)
    k, v = (jax.random.normal(key, (B, T, hkv, d), dtype) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], (B, T, h, d), jnp.float32)


# blocks of 128 against T 512 and a window of 256: whole tiles, tiles the
# window's edge cuts, the diagonal's, and tiles never touched; 192 and the
# unequal blocks put the edges inside tiles
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)], ids=str)
@pytest.mark.parametrize("window", [256, 192, None], ids=["window256", "window192", "full"])
@pytest.mark.parametrize("heads", [(8, 1), (32, 4)], ids=["8q1kv", "32q4kv"])
def test_fused_attention_agrees_with_dense(heads, window, blocks):
    q, k, v, w = operands(*heads)

    def loss(attention):
        return lambda q, k, v: jnp.sum(attention(q, k, v).astype(jnp.float32) * w)

    def fused(q, k, v):
        return fused_causal_attention(q, k, v, window, *blocks, interpret=True)

    want = dense_attention(q, k, v, window)
    got = fused(q, k, v)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    # bfloat16 holds 8 bits: outputs of size one to within 2^-7
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=0.02)
    grads = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, window)), argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", grads, wants):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.linalg.norm(g - r) <= 0.01 * np.linalg.norm(r), name


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)], ids=str)
def test_fused_attention_at_heads_of_256_in_groups_of_8(blocks):
    """Qwen3-Next's full layers: 16 query heads of 256 over 2 key-value heads,
    no window. The kernel's tiles are the head's 256 lanes wide."""
    q, k, v, w = operands(16, 2, d=256)
    want = dense_attention(q, k, v, None)
    got = fused_causal_attention(q, k, v, None, *blocks, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=0.02)

    def loss(attention):
        return lambda q, k, v: jnp.sum(attention(q, k, v).astype(jnp.float32) * w)

    grads = jax.grad(loss(lambda q, k, v: fused_causal_attention(
        q, k, v, None, *blocks, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, None)),
                     argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", grads, wants):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.linalg.norm(g - r) <= 0.01 * np.linalg.norm(r), name


def test_fused_attention_takes_the_blocked_forms_precision():
    """bfloat16 products, float32 accumulation and softmax: as far from dense
    attention as the blocked form is, not farther."""
    q, k, v, _ = operands(8, 1)
    want = np.asarray(dense_attention(q, k, v, 256))
    fused = np.asarray(fused_causal_attention(q, k, v, 256, 128, interpret=True), np.float32)
    blocked = np.asarray(blocked_causal_attention(q, k, v, 256), np.float32)
    assert np.abs(fused - want).max() <= 1.5 * np.abs(blocked - want).max()


# float32 and small, ragged shapes (a T the block does not divide, a head size
# off every tile, a last block under the window): what every call the kernel
# does not serve runs
@pytest.mark.parametrize("t,d,block_q,window,grad", [
    (35, 25, 16, None, False), (48, 32, 16, None, False), (96, 16, 32, None, False),
    (80, 16, 32, 24, False), (32, 16, 16, None, True), (32, 16, 16, 24, True),
    (35, 25, 16, None, True), (80, 16, 32, 24, True),
], ids=str)
def test_blocked_attention_agrees_with_dense_in_float32(t, d, block_q, window, grad):
    rng = np.random.RandomState(t + d)
    q = jnp.asarray(rng.randn(2, t, 4, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(2, t, 2, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(2, t, 2, d), jnp.float32)
    forms = (lambda q, k, v: _blocked(q, k, v, window, block_q),
             lambda q, k, v: dense_attention(q, k, v, window))
    if not grad:
        got, want = (form(q, k, v) for form in forms)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        return
    target = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    got, want = (
        jax.grad(lambda q, k, v: jnp.sum((form(q, k, v) - target) ** 2), argnums=(0, 1, 2))(q, k, v)
        for form in forms)
    for name, g, r in zip("qkv", got, want):
        np.testing.assert_allclose(g, r, atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


def test_fused_attention_refuses_a_ragged_t():
    q, k, v, _ = operands(8, 1)
    with pytest.raises(ValueError, match="must divide by the blocks"):
        fused_causal_attention(q, k, v, None, 384, interpret=True)


@pytest.mark.parametrize("case,why", [("cpu", "cpu"), ("float32", "float32"),
                                      ("head_dim", "head_dim"), ("t", "t")])
def test_the_blocked_form_is_taken_and_says_why(case, why):
    """On the CPU every call is blocked: by the platform where the kernel
    would have served the call, else by what about the call it does not
    serve. The result is the blocked form's, to the bit."""
    q, k, v, _ = operands(8, 1, jnp.float32 if case == "float32" else jnp.bfloat16,
                          64 if case == "head_dim" else D)
    if case == "t":
        q, k, v = (x[:, : FUSED_BLOCK // 2] for x in (q, k, v))
    with traced_instants("attention_path") as said:
        got = jax.jit(lambda q, k, v: blocked_causal_attention(q, k, v, 256))(q, k, v)
    assert said == [{"path": "blocked", "why": why, "window": 256, "t": q.shape[1],
                     "dtype": str(q.dtype)}]
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(_blocked(q, k, v, 256, 256), np.float32))


def test_no_option_selects_an_attention_kernel(capsys):
    """The attention is chosen by the code (the trainer's mesh for the
    paper's LM, the call and the lowering's platform for the routed decoder):
    the switch that once chose a kernel by hand is refused, not ignored."""
    import dataclasses

    from dynamic_load_balance_distributeddnn_tpu.config import Config, get_parser

    with pytest.raises(SystemExit):
        get_parser().parse_args(["-m", "transformer", "--use_flash_attention", "true"])
    assert "unrecognized arguments: --use_flash_attention" in capsys.readouterr().err
    assert not [f.name for f in dataclasses.fields(Config) if "flash" in f.name]
