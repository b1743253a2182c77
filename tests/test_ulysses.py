"""Ulysses (head all-to-all) sequence parallelism vs full attention, and the
LM wired with sp_mode='ulysses' vs the single-device model — same params,
same loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import data_mesh
from dynamic_load_balance_distributeddnn_tpu.parallel.ring import reference_attention
from dynamic_load_balance_distributeddnn_tpu.parallel.ulysses import (
    make_ulysses_attention_fn,
)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(causal):
    devices = jax.devices()
    mesh = data_mesh(devices)
    n = len(devices)
    b, h, t_local, d = 2, n, 16, 8  # H == n devices: one head per device
    t = n * t_local
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    uly = make_ulysses_attention_fn(mesh, causal=causal)
    out = np.asarray(uly(q, k, v))
    ref = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_grad_matches(causal):
    devices = jax.devices()
    mesh = data_mesh(devices)
    n = len(devices)
    b, h, t_local, d = 1, n, 8, 4
    t = n * t_local
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    uly = make_ulysses_attention_fn(mesh, causal=causal)

    g_uly = np.asarray(jax.grad(lambda q: jnp.sum(uly(q, k, v) ** 2))(q))
    g_ref = np.asarray(
        jax.grad(
            lambda q: jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)
        )(q)
    )
    np.testing.assert_allclose(g_uly, g_ref, atol=5e-5, rtol=5e-5)


def test_lm_ulysses_mode_matches_single_device():
    """TransformerLM(sp_mode='ulysses') under seq-parallel shard_map produces
    the same loss as the plain single-device model with the SAME weights
    (interchangeable param layout)."""
    from dynamic_load_balance_distributeddnn_tpu.models import build_model
    from dynamic_load_balance_distributeddnn_tpu.parallel.seq_parallel import (
        make_seq_parallel_value_and_grad,
        shard_tokens,
    )

    devices = jax.devices()
    mesh = data_mesh(devices)
    n = len(devices)
    kw = dict(ntoken=64, ninp=32, nhead=n, nhid=32, nlayers=1, dropout=0.0)
    single = build_model("transformer", **kw).module
    sp = build_model("transformer", **kw, seq_axis="data", sp_mode="ulysses").module

    t = n * 8
    rng = np.random.RandomState(2)
    toks = jnp.asarray(rng.randint(0, 64, (2, t)), jnp.int32)
    tgts = jnp.asarray(rng.randint(0, 64, (2, t)), jnp.int32)
    params = single.init({"params": jax.random.PRNGKey(0)}, toks, train=False)

    sp_fn = make_seq_parallel_value_and_grad(mesh, sp)
    sp_loss, sp_grads = sp_fn(params, shard_tokens(mesh, toks), shard_tokens(mesh, tgts))

    from dynamic_load_balance_distributeddnn_tpu.ops.losses import (
        per_example_cross_entropy,
    )

    def single_loss(p):
        logits = single.apply(p, toks, train=False)
        return per_example_cross_entropy(logits, tgts).mean()

    ref_loss, ref_grads = jax.value_and_grad(single_loss)(params)
    np.testing.assert_allclose(float(sp_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(sp_grads), jax.tree_util.tree_leaves(ref_grads)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)
