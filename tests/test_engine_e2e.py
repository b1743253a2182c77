"""End-to-end engine tests on the 8-virtual-device CPU mesh.

Mirrors the reference's de-facto verification style (SURVEY §4): debug-mode
multi-worker runs plus straggler injection, but with actual assertions —
loss decreases, the equal-step collectives stay aligned, and the partition
vector shifts toward fast workers within a few epochs.
"""

import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
from dynamic_load_balance_distributeddnn_tpu.faults import StaticStragglerInjector
from dynamic_load_balance_distributeddnn_tpu.train import Trainer


def small_cfg(**kw):
    base = dict(
        debug=True,
        world_size=4,
        batch_size=128,
        learning_rate=0.05,
        epoch_size=3,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        seed=1234,
        bucket=8,
    )
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module")
def bundle():
    return synthetic_dataset("mnist", n_train=1024, n_test=256)


def make_trainer(bundle, **kw):
    injector = kw.pop("injector", None)
    timing_model = kw.pop("timing_model", None)
    cfg = small_cfg(**kw)
    return Trainer(
        cfg,
        bundle=bundle,
        injector=injector,
        log_to_file=False,
        timing_model=timing_model,
    )


def linear_time(plan):
    """Deterministic compute model: time ∝ examples processed (the regime the
    reference assumes; wall-clock on tiny CPU batches is overhead-dominated)."""
    return np.array([w.padded_batch * w.steps * 1e-3 for w in plan.workers])


def test_e2e_uniform_runs_and_learns(bundle, tmp_path):
    tr = make_trainer(bundle, stat_dir=str(tmp_path), epoch_size=2)
    rec = tr.run()
    losses = rec.data["train_loss"]
    assert len(losses) == 2
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 1.2  # moving, not exploding
    # with no straggler, shares stay near uniform
    assert np.allclose(rec.data["partition"][-1], 0.25, atol=0.12)
    # the reference's nine mandatory series all recorded (dbs.py:316-326)
    for k in (
        "epoch",
        "train_loss",
        "train_time",
        "sync_time",
        "val_loss",
        "accuracy",
        "partition",
        "node_time",
        "wallclock_time",
    ):
        assert len(rec.data[k]) == 2, k


@pytest.mark.slow
def test_e2e_partition_shifts_under_straggler(bundle, tmp_path):
    """The DBS capability itself: a 3:1 virtual straggler on worker 0 must
    pull worker 0's share below uniform and push the others above."""
    tr = make_trainer(
        bundle,
        stat_dir=str(tmp_path),
        epoch_size=4,
        injector=StaticStragglerInjector([3.0, 1.0, 1.0, 1.0], mode="virtual"),
        fault_tolerance=True,
        timing_model=linear_time,
    )
    rec = tr.run()
    final = np.array(rec.data["partition"][-1])
    # equilibrium for 3:1 among 4 workers: [0.1, 0.3, 0.3, 0.3]
    assert abs(final[0] - 0.1) < 0.05
    assert np.allclose(final[1:], 0.3, atol=0.05)
    assert final.sum() == pytest.approx(1.0)
    # node_time converges toward equal (balanced) once shares shift
    nt = np.array(rec.data["node_time"][-1])
    # bucket snapping (snap_to_bucket) quantizes shares to bucket multiples,
    # so residual imbalance up to ~one bucket's worth of work remains
    assert nt.max() / nt.min() < 2.0


@pytest.mark.slow
def test_e2e_fused_path_dbs_off(bundle, tmp_path):
    """dbs-off with one worker per device takes the fused whole-epoch SPMD
    scan path; results must be sane."""
    tr = make_trainer(
        bundle, stat_dir=str(tmp_path), dynamic_batch_size=False, epoch_size=2
    )
    from dynamic_load_balance_distributeddnn_tpu.balance import integer_batch_split

    plan = tr._build_plan(0, integer_batch_split(tr.shares, tr.cfg.batch_size))
    assert tr._can_use_fused(plan)
    rec = tr.run()
    assert np.isfinite(rec.data["train_loss"]).all()
    assert rec.data["train_loss"][-1] < rec.data["train_loss"][0] * 1.2


@pytest.mark.slow
def test_e2e_dbs_off_stays_uniform(bundle, tmp_path):
    tr = make_trainer(
        bundle,
        stat_dir=str(tmp_path),
        dynamic_batch_size=False,
        epoch_size=2,
        injector=StaticStragglerInjector([3.0, 1.0, 1.0, 1.0], mode="virtual"),
    )
    rec = tr.run()
    assert np.allclose(rec.data["partition"][-1], 0.25)


@pytest.mark.slow
def test_e2e_contention_map(bundle, tmp_path):
    """The README recipe shape: several workers share one device
    (analogue of -gpu 0,0,0,1)."""
    tr = make_trainer(
        bundle,
        stat_dir=str(tmp_path),
        device=[0, 0, 0, 1],
        epoch_size=1,
    )
    rec = tr.run()
    assert len(rec.data["train_loss"]) == 1
    assert tr.topology.contention_factor(0) == 3
    assert tr.topology.contention_factor(3) == 1


@pytest.mark.slow
def test_e2e_disable_enhancements(bundle, tmp_path):
    """-de: uniform 1/ws gradient weights (dbs.py:293) still trains."""
    tr = make_trainer(
        bundle, stat_dir=str(tmp_path), disable_enhancements=True, epoch_size=1
    )
    rec = tr.run()
    assert np.isfinite(rec.data["train_loss"]).all()


@pytest.mark.slow
def test_compute_injection_applies_without_dbs(bundle, tmp_path):
    """The dbs-off A/B arm must still receive compute-mode straggler load
    (probes run for calibration even with the balancer off)."""
    from dynamic_load_balance_distributeddnn_tpu.faults import (
        EpochFaults,
        StaticStragglerInjector,
    )

    seen = []

    class Spy(StaticStragglerInjector):
        def epoch_faults(self, epoch, num_batches, ctx):
            out = super().epoch_faults(epoch, num_batches, ctx)
            seen.append(out.slow_iters_per_step.copy())
            return out

    tr = make_trainer(
        bundle,
        stat_dir=str(tmp_path),
        dynamic_batch_size=False,
        epoch_size=2,
        fault_mode="compute",
        injector=Spy([3.0, 1.0, 1.0, 1.0], mode="compute"),
    )
    tr.run()
    assert np.isfinite(tr.per_example_cost).all()  # probes ran despite dbs off
    assert seen[0].sum() == 0          # epoch 0: calibration, no injection
    assert seen[1][0] > 0              # epoch 1: worker 0 carries real load
    assert (seen[1][1:] == 0).all()


@pytest.mark.slow
def test_e2e_eight_workers_heterogeneous_map(bundle, tmp_path):
    """BASELINE.md acceptance config 4: 8 workers on a heterogeneous device
    map (two workers contend on device 0, the rest own a chip each). The
    balancer must pull work away from the modeled-slow contended workers and
    every worker must keep a non-zero bucket-snapped batch."""
    factors = np.array([2.0, 2.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])

    def hetero_time(plan):
        return factors * np.array(
            [w.batch_size * w.steps * 1e-3 for w in plan.workers]
        )

    tr = make_trainer(
        bundle,
        stat_dir=str(tmp_path),
        world_size=8,
        batch_size=256,
        bucket=8,
        epoch_size=3,
        device=[0, 0, 1, 2, 3, 4, 5, 6],
        timing_model=hetero_time,
    )
    rec = tr.run()
    final = np.array(rec.data["partition"][-1])
    assert final.sum() == pytest.approx(1.0)
    assert (final > 0).all()
    # contended workers 0,1 end below uniform share; others at or above
    # (bucket snapping can pin some fast workers exactly at uniform)
    assert final[0] < 1 / 8 and final[1] < 1 / 8
    assert final[2:].min() >= 1 / 8
    assert final[2:].mean() > 1 / 8


@pytest.mark.slow
def test_e2e_bfloat16_mixed_precision(bundle, tmp_path):
    """bf16 compute + f32 master weights (the TPU MXU's native dtype, what
    both benchmark cells run): training must run and reduce loss like the f32 path, and the
    master params must stay f32."""
    import jax
    import jax.numpy as jnp

    tr = make_trainer(
        bundle, stat_dir=str(tmp_path), epoch_size=2, precision="bfloat16"
    )
    rec = tr.run()
    losses = rec.data["train_loss"]
    assert len(losses) == 2 and np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 1.2
    for leaf in jax.tree_util.tree_leaves(tr.state.params):
        assert leaf.dtype == jnp.float32
