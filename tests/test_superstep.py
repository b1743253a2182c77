"""Elastic supersteps (ISSUE 2): bitwise parity + compile-once contract.

The superstep path exists to remove per-step host dispatch, NOT to change
math: running the same plan through the legacy per-step elastic loop
(superstep="off") and the superstep loop must produce the exact same loss
trajectory, parameters, and balancer ratios — on both the single-device
scan mode (combine cadence inside the compiled window) and the multi-device
windowed mode (per-step combine, on-device step slicing).
"""

import jax
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.analysis.guards import compile_budget
from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
from dynamic_load_balance_distributeddnn_tpu.faults import StaticStragglerInjector
from dynamic_load_balance_distributeddnn_tpu.train import Trainer


@pytest.fixture(scope="module")
def bundle():
    return synthetic_dataset("mnist", n_train=1024, n_test=256)


def linear_time(plan):
    return np.array([3.0, 1.0, 1.0, 1.0]) * np.array(
        [w.batch_size * w.steps for w in plan.workers]
    )


def _run(bundle, superstep, device=None, epochs=3, **kw):
    cfg = Config(
        debug=True,
        world_size=4,
        batch_size=128,
        learning_rate=0.05,
        epoch_size=epochs,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        fault_tolerance=True,
        seed=1234,
        bucket=8,
        device=device,
        superstep=superstep,
        packed="off",  # force the elastic path on single-device topologies
        **kw,
    )
    tr = Trainer(
        cfg,
        bundle=bundle,
        injector=StaticStragglerInjector([3.0, 1.0, 1.0, 1.0], mode="virtual"),
        timing_model=linear_time,
        log_to_file=False,
    )
    rec = tr.run()
    return tr, rec


def _assert_bitwise_equal(tr_a, rec_a, tr_b, rec_b):
    np.testing.assert_array_equal(
        rec_a.data["train_loss"], rec_b.data["train_loss"]
    )
    np.testing.assert_array_equal(
        np.asarray(rec_a.data["partition"]), np.asarray(rec_b.data["partition"])
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(tr_a.state.params),
        jax.tree_util.tree_leaves(tr_b.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_superstep_scan_bitwise_parity(bundle):
    """Single device group (-gpu 0,0,0,0): the whole window runs as ONE
    compiled lax.scan carrying the TrainState — and must match the per-step
    loop bit for bit (loss trajectory, params, balancer ratios)."""
    tr_off, rec_off = _run(bundle, superstep="off", device=0)
    tr_on, rec_on = _run(bundle, superstep="auto", device=0)
    assert tr_on._elastic_mode() == "scan"
    assert tr_off._elastic_mode() == "step"
    _assert_bitwise_equal(tr_off, rec_off, tr_on, rec_on)
    # the scan actually ran (and the legacy per-step loop did not)
    assert tr_on.steps.superstep_cache_size() >= 1
    assert tr_on.steps.worker_step_acc._cache_size() == 0
    assert tr_on.steps.worker_step_acc_idx._cache_size() == 0


def test_superstep_scan_zero1_bitwise_parity(bundle):
    """shard_update x scan mode (the PR-13 fallback, closed in PR 18): the
    superstep body routes into the axis-free ZeRO-1 twin
    (``_zero1_update(with_comm=False, local_index=0)``) — on the 1-device
    mesh that scan mode requires, the windowed combine twin's collectives
    are identities, so the compiled window must match the per-step zero-1
    cadence bit for bit."""
    tr_off, rec_off = _run(
        bundle, superstep="off", device=0, shard_update=True
    )
    tr_on, rec_on = _run(
        bundle, superstep="auto", device=0, shard_update=True
    )
    assert tr_on._elastic_mode() == "scan"
    assert tr_off._elastic_mode() == "step"
    _assert_bitwise_equal(tr_off, rec_off, tr_on, rec_on)
    # the scan actually carried the sharded state (and donation stayed off
    # — the XLA:CPU donated-carry sanction, steps.py _state_donate)
    assert tr_on.steps.superstep_cache_size() >= 1
    assert tr_on.steps._state_donate == ()


def test_superstep_scan_zero1_compress_stays_windowed(bundle):
    """The one remaining exclusion: shard_update x compress_grads keeps
    the windowed cadence (stochastic rounding is not an identity even
    over a size-1 axis, so the scan's comm-free twin would diverge)."""
    cfg = Config(
        debug=True, world_size=4, batch_size=128, epoch_size=1,
        dataset="mnist", model="mnistnet", dynamic_batch_size=False,
        device=0, superstep="auto", packed="off",
        shard_update=True, compress_grads="int8",
    )
    tr = Trainer(cfg, bundle=bundle, log_to_file=False)
    assert tr._elastic_mode() == "window"


@pytest.mark.slow
def test_superstep_windowed_bitwise_parity(bundle):
    """Multi-device topology (round-robin over the mesh): the per-step
    combine cadence stays, worker-steps go through the window-sliced
    executables — bitwise-identical to host-side slicing."""
    tr_off, rec_off = _run(bundle, superstep="off")
    tr_on, rec_on = _run(bundle, superstep="auto")
    assert tr_on._elastic_mode() == "window"
    _assert_bitwise_equal(tr_off, rec_off, tr_on, rec_on)


def test_superstep_compiles_once_per_shape_window(bundle):
    """Compile-once contract: a second epoch on an identical plan layout
    (same shapes, same window) must not compile ANY new superstep
    executable — each (shape, window) variant compiles exactly once."""
    cfg = Config(
        debug=True,
        world_size=4,
        batch_size=128,
        learning_rate=0.05,
        epoch_size=2,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        seed=7,
        bucket=8,
        device=0,
        superstep="auto",
        packed="off",
    )
    tr = Trainer(
        cfg,
        bundle=bundle,
        timing_model=lambda plan: np.ones(4),  # equal times -> stable plan
        log_to_file=False,
    )
    tr.run_epoch(0)
    n_variants = tr.steps.superstep_cache_size()
    assert n_variants >= 1
    keys_seen = set(tr._superstep_keys)
    with compile_budget(max_compiles=0, label="superstep_repeat_epoch"):
        tr.run_epoch(1)
    # identical plan layout -> no new (shape, window) key, no new variant
    assert tr._superstep_keys == keys_seen
    assert tr.steps.superstep_cache_size() == n_variants


def test_superstep_host_overhead_metered(bundle):
    """The elastic epoch reports its host dispatch/put walls (what the
    superstep path exists to shrink)."""
    tr, rec = _run(bundle, superstep="auto", epochs=1)
    assert rec.data["host_overhead_per_step_s"], "meter series missing"
    v = rec.data["host_overhead_per_step_s"][-1]
    assert np.isfinite(v) and v >= 0.0
    # scan mode: one dispatch per WINDOW (num_steps=8 fits one window at the
    # default superstep_window=16), not one per step
    tr2, rec2 = _run(bundle, superstep="auto", device=0, epochs=1)
    assert tr2._elastic_mode() == "scan"
    assert tr2._host_meter.dispatches == 1


@pytest.mark.slow
def test_superstep_device_cache_bitwise_equal(bundle):
    """Index-fed superstep (device cache) must equal the materialized feed
    on the scan mode — same rows, same rng stream, different transport."""
    tr_m, rec_m = _run(bundle, superstep="auto", device=0, device_cache="off")
    tr_c, rec_c = _run(bundle, superstep="auto", device=0, device_cache="on")
    assert tr_c._use_device_cache and not tr_m._use_device_cache
    _assert_bitwise_equal(tr_m, rec_m, tr_c, rec_c)
