"""Pallas kernels vs their pure-XLA references (interpret mode on CPU).

The kernels must be drop-in numerically: same forward values and same
gradients as nn.GroupNorm / ops.losses.per_example_cross_entropy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
import pytest

from dynamic_load_balance_distributeddnn_tpu.ops.losses import per_example_cross_entropy
from dynamic_load_balance_distributeddnn_tpu.ops.pallas import (
    fused_group_norm,
    fused_softmax_xent,
    set_use_pallas,
    use_pallas,
)


@pytest.mark.parametrize("shape,groups", [((3, 8, 8, 64), 32), ((2, 16, 16, 24), 8), ((4, 10, 48), 16)])
def test_groupnorm_forward_matches_flax(shape, groups):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    c = shape[-1]
    scale = jnp.asarray(rng.randn(c).astype(np.float32))
    bias = jnp.asarray(rng.randn(c).astype(np.float32))
    ref = nn.GroupNorm(num_groups=groups).apply(
        {"params": {"scale": scale, "bias": bias}}, x
    )
    got = fused_group_norm(x, scale, bias, groups)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-4)


def test_groupnorm_grads_match_flax():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 6, 6, 32).astype(np.float32))
    scale = jnp.asarray(rng.randn(32).astype(np.float32))
    bias = jnp.asarray(rng.randn(32).astype(np.float32))
    gn = nn.GroupNorm(num_groups=32)

    def f_ref(x, s, b):
        return jnp.sum(jnp.tanh(gn.apply({"params": {"scale": s, "bias": b}}, x)))

    def f_got(x, s, b):
        return jnp.sum(jnp.tanh(fused_group_norm(x, s, b, 32)))

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, scale, bias)
    gg = jax.grad(f_got, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_ in zip(gr, gg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_groupnorm_relu_epilogue_matches_gn_then_relu():
    """relu=True fuses the GN→relu pair (the zoo-wide block pattern) into
    the kernel; forward and grads must match the unfused composition —
    including the idempotence contract models rely on (an OUTER nn.relu on
    the fused output is a no-op, models/common.py group_norm docstring)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 5, 7, 32).astype(np.float32))
    scale = jnp.asarray(rng.randn(32).astype(np.float32))
    bias = jnp.asarray(rng.randn(32).astype(np.float32))
    gn = nn.GroupNorm(num_groups=16)
    ref = nn.relu(gn.apply({"params": {"scale": scale, "bias": bias}}, x))
    got = fused_group_norm(x, scale, bias, 16, relu=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nn.relu(got)), np.asarray(got))

    def f_ref(x, s, b):
        return jnp.sum(
            jnp.tanh(
                nn.relu(gn.apply({"params": {"scale": s, "bias": b}}, x))
            )
        )

    def f_got(x, s, b):
        return jnp.sum(jnp.tanh(fused_group_norm(x, s, b, 16, relu=True)))

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, scale, bias)
    gg = jax.grad(f_got, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_ in zip(gr, gg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_groupnorm_bf16_output_dtype():
    x = jnp.ones((2, 4, 4, 16), jnp.bfloat16)
    y = fused_group_norm(x, jnp.ones(16), jnp.zeros(16), 8)
    assert y.dtype == jnp.bfloat16 and y.shape == x.shape


def test_xent_matches_reference_fwd_bwd():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(13, 101).astype(np.float32)) * 3
    labels = jnp.asarray(rng.randint(0, 101, (13,)).astype(np.int32))
    ref = per_example_cross_entropy(logits, labels)
    got = fused_softmax_xent(logits, labels)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-5)

    w = jnp.asarray(rng.rand(13).astype(np.float32))
    g1 = jax.grad(lambda l: jnp.sum(per_example_cross_entropy(l, labels) * w))(logits)
    g2 = jax.grad(lambda l: jnp.sum(fused_softmax_xent(l, labels) * w))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_xent_batched_shape():
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(4, 7, 11).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 11, (4, 7)).astype(np.int32))
    got = fused_softmax_xent(logits, labels)
    ref = per_example_cross_entropy(logits, labels)
    assert got.shape == (4, 7)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-5)


def test_pallas_groupnorm_module_swaps_in():
    from dynamic_load_balance_distributeddnn_tpu.models.common import group_norm

    set_use_pallas(True)
    try:
        assert use_pallas()
        mod = group_norm(32)
        x = jnp.asarray(np.random.RandomState(4).randn(2, 5, 5, 32).astype(np.float32))
        params = mod.init(jax.random.PRNGKey(0), x)
        y = mod.apply(params, x)
        ref = nn.GroupNorm(num_groups=32).apply(
            {"params": {"scale": jnp.ones(32), "bias": jnp.zeros(32)}}, x
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    finally:
        set_use_pallas(False)
    assert isinstance(group_norm(32), nn.GroupNorm)


def test_groupnorm_module_relu_toggle_equivalent():
    """group_norm(relu=True) applies relu INSIDE the module in both branches
    (kernel epilogue when Pallas is on, nn.relu in the flax fallback) with
    the same params — the compute-only-toggle contract extended to the
    fused GN→relu pair."""
    from dynamic_load_balance_distributeddnn_tpu.models.common import group_norm

    x = jnp.asarray(np.random.RandomState(6).randn(2, 5, 5, 32).astype(np.float32))
    mod_off = group_norm(32, relu=True)
    params = mod_off.init(jax.random.PRNGKey(0), x)
    y_off = mod_off.apply(params, x)
    # relu is genuinely applied (about half the normalized activations clip)
    assert float(jnp.min(y_off)) == 0.0

    set_use_pallas(True)
    try:
        y_on = group_norm(32, relu=True).apply(params, x)
    finally:
        set_use_pallas(False)
    np.testing.assert_allclose(np.asarray(y_off), np.asarray(y_on), atol=1e-4)


@pytest.mark.slow  # ~56s: two DenseNet inits
def test_pallas_toggle_param_trees_identical():
    """The toggle must be compute-only: same module names, same param pytree,
    so checkpoints are portable across --use_pallas."""
    from dynamic_load_balance_distributeddnn_tpu.models import build_model

    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    set_use_pallas(False)
    p_off = build_model("resnet").module.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=False,
    )
    set_use_pallas(True)
    try:
        p_on = build_model("resnet").module.init(
            {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
            x, train=False,
        )
    finally:
        set_use_pallas(False)
    assert jax.tree_util.tree_structure(p_off) == jax.tree_util.tree_structure(p_on)
    for a, b in zip(jax.tree_util.tree_leaves(p_off), jax.tree_util.tree_leaves(p_on)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_groupnorm_large_mean_no_nan():
    """Cancellation guard: huge mean, tiny spread must not produce NaN."""
    rng = np.random.RandomState(5)
    x = jnp.asarray((1000.0 + 0.01 * rng.randn(2, 4, 4, 32)).astype(np.float32))
    y = fused_group_norm(x, jnp.ones(32), jnp.zeros(32), 32)
    assert np.isfinite(np.asarray(y)).all()
