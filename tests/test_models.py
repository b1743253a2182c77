"""Model zoo shape/param sanity (reference architectures: Net/*.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.models import build_model


def _init_and_apply(spec, x):
    params = spec.module.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x,
        train=False,
    )
    out = spec.module.apply(params, x, train=False)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    return out, n_params


def test_mnistnet_shapes():
    spec = build_model("mnistnet", num_classes=10)
    out, n = _init_and_apply(spec, jnp.zeros((2, 28, 28, 1)))
    assert out.shape == (2, 10)
    assert n == 21_840  # exact torch parity (Net/MnistNet.py)


# Exact parameter-count parity with the reference torch modules (verified by
# instantiating the reference models directly). GoogLeNet has no reference
# count — the original crashes at forward (Net/GoogleNet.py:29-30 defect) —
# so its fixed version is range-checked.
@pytest.mark.slow  # full-size model init + forward, ~20-40s each
@pytest.mark.parametrize(
    "name,nc,expect",
    [
        ("resnet", 10, 42_512_970),   # ResNet-101 (dbs.py:350)
        ("densenet", 10, 6_956_298),  # DenseNet-121 (dbs.py:353)
        ("regnet", 10, 5_714_362),    # RegNetY-400MF (dbs.py:359)
    ],
)
def test_cnn_families_exact_param_parity(name, nc, expect):
    spec = build_model(name, num_classes=nc)
    out, n = _init_and_apply(spec, jnp.zeros((2, 32, 32, 3)))
    assert out.shape == (2, nc)
    assert n == expect, f"{name}: {n:,} params != reference {expect:,}"


@pytest.mark.slow
def test_googlenet_fixed_runs():
    spec = build_model("googlenet", num_classes=10)
    out, n = _init_and_apply(spec, jnp.zeros((2, 32, 32, 3)))
    assert out.shape == (2, 10)
    assert 5.5e6 < n < 7.0e6


@pytest.mark.slow
def test_resnet18_small_variant():
    from dynamic_load_balance_distributeddnn_tpu.models.resnet import ResNet18

    m = ResNet18(10)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False)
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert n == 11_173_962  # exact torch parity

@pytest.mark.slow
def test_outputs_finite_on_random_input():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
    for name in ("densenet", "googlenet", "regnet"):
        spec = build_model(name, num_classes=10)
        out, _ = _init_and_apply(spec, x)
        assert np.isfinite(np.asarray(out)).all(), name


def test_grouped_conv_decompose_matches_grouped():
    """GroupedConv's per-group decomposition (the XLA:CPU compile-pathology
    workaround, models/regnet.py) is numerically the fused grouped conv:
    same single kernel param, same output to fp tolerance, fwd and grad."""
    from dynamic_load_balance_distributeddnn_tpu.models.regnet import GroupedConv

    m_fused = GroupedConv(features=32, strides=2, groups=4, decompose=False)
    m_dec = GroupedConv(features=32, strides=2, groups=4, decompose=True)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 16, 16), jnp.float32)
    p = m_fused.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(
        m_dec.init(jax.random.PRNGKey(0), x)
    )
    y1 = m_fused.apply(p, x)
    y2 = m_dec.apply(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)

    def loss(params, mod):
        return jnp.sum(mod.apply(params, x) ** 2)

    g1 = jax.grad(loss)(p, m_fused)
    g2 = jax.grad(loss)(p, m_dec)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
