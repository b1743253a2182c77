"""Adaptive probe scheduling (config.probe_mode).

The reference re-times every epoch for free — it times the epoch it already
ran (dbs.py:226-250). Our probe-based signal costs real step executions, which
round 2 showed is pure overhead when the plan is balanced (c2 insurance: dbs-on
21% slower). These tests pin the scheduler that fixes it: probes anchor a cost
model on epochs 0-1, later epochs run on modeled times, and re-probes happen
only on schedule, on injection-episode changes, or on wall deviation.
"""

import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data import load_dataset
from dynamic_load_balance_distributeddnn_tpu.faults import (
    FaultInjector,
    EpochFaults,
    StaticStragglerInjector,
)
from dynamic_load_balance_distributeddnn_tpu.train import Trainer


def _cfg(**kw):
    # bucket=16 keeps the elastic shape ladder short (4 rungs, not 8) — the
    # tier's wall here is XLA compiles, not the epochs themselves
    base = dict(
        debug=True,
        world_size=4,
        batch_size=128,
        learning_rate=0.01,
        epoch_size=6,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        bucket=16,
        n_train=256,
        probe_every=3,
    )
    base.update(kw)
    return Config(**base)


def _count_probes(tr):
    """Wrap _probe_workers with a counter."""
    calls = []
    orig = tr._probe_workers

    def counting(plan, data, faults, epoch, **kw):
        calls.append(epoch)
        return orig(plan, data, faults, epoch, **kw)

    tr._probe_workers = counting
    return calls


@pytest.fixture(scope="module")
def bundle():
    return load_dataset("mnist", n_train=256, n_test=256)


def test_adaptive_skips_probes_when_stable(bundle):
    tr = Trainer(
        _cfg(),
        bundle=bundle,
        injector=StaticStragglerInjector([3, 1, 1, 1], mode="virtual"),
        log_to_file=False,
    )
    calls = _count_probes(tr)
    for e in range(6):
        tr.run_epoch(e)
    # anchors on 0-1, then the static episode + stable plan skip until the
    # probe_every=3 schedule fires (epoch 4 = 1 + 3)
    assert 0 in calls and 1 in calls
    assert len(calls) <= 4, f"adaptive mode probed too often: {calls}"
    assert not {2, 3} & set(calls), f"skipped window was probed: {calls}"
    # the balancer still converged on MODELED times: worker 0 (3x slower,
    # virtual) ends with roughly a third of a fair share
    assert tr.shares[0] < 0.18, tr.shares
    assert abs(tr.shares.sum() - 1.0) < 1e-9


def test_probe_cost_excluded_from_epoch_wall(bundle):
    """VERDICT r3 weak #7: re-probe epochs were 2x wall outliers in the
    dbs-on arm because the elastic path's standalone probes ran inside the
    timed wall (the fused path already excluded its own). The wall must
    exclude probe cost on every path, with the cost visible as the
    recorder's probe_time series and the engine's total_probe_s."""
    tr = Trainer(
        _cfg(probe_mode="always", epoch_size=3),
        bundle=bundle,
        injector=StaticStragglerInjector([3, 1, 1, 1], mode="virtual"),
        log_to_file=False,
    )
    probe_walls = []
    orig = tr._probe_workers

    def timed(plan, data, faults, epoch, **kw):
        import time

        t0 = time.perf_counter()
        out = orig(plan, data, faults, epoch, **kw)
        probe_walls.append(time.perf_counter() - t0)
        return out

    tr._probe_workers = timed
    walls = [tr.run_epoch(e)["epoch_wall"] for e in range(3)]
    assert len(probe_walls) == 3
    recorded = tr.recorder.data.get("probe_time", [])
    assert len(recorded) == 3
    # the recorded probe series covers at least the _probe_workers wall
    # (it may also include one-time flops-AOT overhead on epoch 0)
    for rec, pw in zip(recorded, probe_walls):
        assert rec >= pw * 0.95
    assert tr.total_probe_s == pytest.approx(sum(recorded), rel=1e-6)
    # wallclock series tracks the probe-free walls
    assert tr.total_wallclock == pytest.approx(sum(walls), rel=1e-6)


def test_always_mode_probes_every_epoch(bundle):
    tr = Trainer(
        _cfg(probe_mode="always", epoch_size=4),
        bundle=bundle,
        injector=StaticStragglerInjector([3, 1, 1, 1], mode="virtual"),
        log_to_file=False,
    )
    calls = _count_probes(tr)
    for e in range(4):
        tr.run_epoch(e)
    assert calls == [0, 1, 2, 3]


def test_balanced_plan_skips_probes_and_stays_uniform(bundle):
    """The c2 regression case: balanced workers, nothing to balance — epochs
    2+ must not pay for probes, and the partition must stay put."""
    tr = Trainer(_cfg(epoch_size=4), bundle=bundle, log_to_file=False)
    calls = _count_probes(tr)
    shares = []
    for e in range(4):
        tr.run_epoch(e)
        shares.append(tr.shares.copy())
    assert not {2, 3} & set(calls), calls
    for s in shares[1:]:
        # modeled times are noise-free, so the plan must be frozen solid
        np.testing.assert_allclose(s, shares[0], atol=1e-9)


class _EpisodeInjector(FaultInjector):
    """Virtual straggler that switches on at a given epoch — the episode
    change the scheduler must react to."""

    def __init__(self, ws, start_epoch):
        self.ws = ws
        self.start = start_epoch

    def epoch_faults(self, epoch, num_steps, ctx):
        out = EpochFaults.none(self.ws)
        if epoch >= self.start:
            out.time_multipliers = np.array([3.0] + [1.0] * (self.ws - 1))
        return out


def test_episode_change_forces_reprobe(bundle):
    tr = Trainer(
        _cfg(epoch_size=6),
        bundle=bundle,
        injector=_EpisodeInjector(4, start_epoch=3),
        log_to_file=False,
    )
    calls = _count_probes(tr)
    for e in range(6):
        tr.run_epoch(e)
    assert 3 in calls, f"episode start not re-probed: {calls}"
    assert 2 not in calls, f"pre-episode epoch should have been skipped: {calls}"
    # after the episode starts, the balancer shifts load off worker 0
    assert tr.shares[0] < 0.22, tr.shares


def test_skipped_epochs_report_cached_sync_time(bundle):
    tr = Trainer(
        _cfg(epoch_size=4),
        bundle=bundle,
        injector=StaticStragglerInjector([2, 1, 1, 1], mode="virtual"),
        log_to_file=False,
    )
    for e in range(4):
        tr.run_epoch(e)
    sync = tr.recorder.data["sync_time"]
    # epoch 2-3 skip probes but must report the last probed per-step sync
    # scaled by their own step counts, not zero
    assert all(s > 0 for s in sync[2:]), sync


def test_adaptive_skips_with_compute_injection(bundle):
    """Regression (seen once under a 3:1 compute straggler): compute-mode
    slow_iters scale with each worker's batch, so a naive episode signature
    read every rebalance as a new episode and probed every epoch. The
    plan-normalized iters-per-example ratio must keep skipping."""
    tr = Trainer(
        _cfg(epoch_size=5, fault_mode="compute", fault_tolerance=True),
        bundle=bundle,
        injector=StaticStragglerInjector([3, 1, 1, 1], mode="compute"),
        log_to_file=False,
    )
    calls = _count_probes(tr)
    for e in range(5):
        tr.run_epoch(e)
    assert not {2, 3} & set(calls), f"rebalance misread as episode change: {calls}"


def test_straggler_profile_stamped_in_meta(bundle):
    # the induced profile is recorded so offline tooling can compute the
    # ideal equilibrium partition (BASELINE.md balancer-quality metric)
    tr = Trainer(
        _cfg(straggler="3,1,1,1", fault_mode="virtual"),
        bundle=bundle,
        log_to_file=False,
    )
    assert tr.recorder.meta["straggler_factors"] == [3.0, 1.0, 1.0, 1.0]
    assert tr.recorder.meta["fault_mode"] == "virtual"


def test_probe_overhead_correction_recorded(bundle):
    """config.probe_overhead_correction subtracts the measured per-device
    dispatch overhead from standalone probe walls before they anchor the
    per-example cost model. On a remotely attached device that overhead can
    dwarf the step and an uncorrected anchor oversizes compute-mode
    injection; on CPU it is O(100us) and the correction must be a no-op in
    magnitude but still instrumented."""
    tr = Trainer(
        _cfg(),
        bundle=bundle,
        injector=StaticStragglerInjector([3, 1, 1, 1], mode="virtual"),
        log_to_file=False,
    )
    tr.run_epoch(0)
    ovh = tr.recorder.meta.get("probe_dispatch_overhead_s")
    assert ovh is not None and 0.0 <= ovh < 0.05
    # the clean anchor must survive the subtraction (floored at 20% raw wall)
    assert np.isfinite(tr.per_example_cost).all()
    assert (tr.per_example_cost > 0).all()

    off = Trainer(
        _cfg(probe_overhead_correction=False),
        bundle=bundle,
        injector=StaticStragglerInjector([3, 1, 1, 1], mode="virtual"),
        log_to_file=False,
    )
    off.run_epoch(0)
    assert "probe_dispatch_overhead_s" not in off.recorder.meta
