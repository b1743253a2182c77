"""Async AOT compile service (runtime/compiler.py, ISSUE 3).

Contracts:

* **Parity** — AOT-compiled executables dispatched by the engine are
  bitwise-identical to the lazy-jit path (same HLO, same donation): loss
  trajectory and params match exactly on the CPU tier.
* **One compile per key** — concurrent submission of one key from many
  threads (N workers / a warm pass racing speculation) backend-compiles
  exactly once.
* **Warm budget (tier-1 CI guard)** — the ws=4 warm-start compile count is
  bounded by the ladder size via ``compile_budget``; a regression back to
  per-device/per-dispatch recompiles trips it.
* **Silent sentinel** — with speculation enabled, a rebalancing run's
  steady-state epochs report zero foreground XLA compiles (the
  ``xla_compiles`` series): no timed epoch ever blocks on the compiler.
"""

import concurrent.futures
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.analysis.guards import compile_budget
from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
from dynamic_load_balance_distributeddnn_tpu.obs.flops import compiled_flops
from dynamic_load_balance_distributeddnn_tpu.runtime.compiler import AOTCompileService
from dynamic_load_balance_distributeddnn_tpu.train import Trainer


@pytest.fixture(scope="module")
def bundle():
    return synthetic_dataset("mnist", n_train=512, n_test=64)


def linear_time(plan):
    return np.array([3.0, 1.0, 1.0, 1.0]) * np.array(
        [w.batch_size * w.steps for w in plan.workers]
    )


def _cfg(**kw):
    base = dict(
        debug=True,
        world_size=4,
        batch_size=64,
        learning_rate=0.05,
        epoch_size=3,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        seed=11,
        bucket=8,
        packed="off",
    )
    base.update(kw)
    return Config(**base)


# ------------------------------------------------------------- service unit


def test_one_compile_per_key_under_concurrent_submission():
    """N threads (one per 'device') racing the same key must produce ONE
    backend compile — the dedup contract that keeps a shared-device worker
    group from compiling its program once per worker."""
    import os

    salt = int.from_bytes(os.urandom(4), "little") / 2**32
    fn = jax.jit(lambda x: x * 2.0 + salt)
    spec = jax.ShapeDtypeStruct((16,), jnp.float32)
    svc = AOTCompileService(workers=4)
    try:
        with compile_budget(label="one-key", include_background=True) as budget:
            with concurrent.futures.ThreadPoolExecutor(8) as callers:
                futs = [
                    callers.submit(svc.submit, ("k", 16), fn, (spec,))
                    for _ in range(8)
                ]
                inner = {f.result() for f in futs}
            assert svc.wait() == []
        assert len(inner) == 1  # every submit joined the same job
        st = svc.stats()
        assert st["compiled"] == 1
        assert st["submitted"] == 1
        assert st["deduped"] == 7
        assert budget.count >= 1  # the one compile was observed
        assert svc.get(("k", 16)) is not None
    finally:
        svc.close()


def test_failed_job_reports_and_does_not_retry():
    bad = jax.jit(lambda x: x + 1)
    svc = AOTCompileService(workers=1)
    try:
        svc.submit("bad", bad, ("not-a-spec",))
        failures = svc.wait()
        assert len(failures) == 1 and failures[0][0] == "bad"
        assert svc.get("bad") is None  # dispatch falls back to lazy jit
        # resubmission joins the failed future instead of recompiling
        svc.submit("bad", bad, ("not-a-spec",))
        assert svc.stats()["submitted"] == 1
    finally:
        svc.close()


def test_compiled_flops_reuses_executable():
    fn = jax.jit(lambda x: (x @ x).sum())
    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    svc = AOTCompileService()
    c = svc.compile_now("flops", fn, (spec,))
    lazy = compiled_flops(fn, spec)
    with compile_budget(max_compiles=0, label="flops-reuse", include_background=True):
        reused = compiled_flops(None, compiled=c)  # no fn needed, no compile
    assert reused == lazy


# ------------------------------------------------------- engine integration


def test_aot_warm_bitwise_parity_with_lazy(bundle):
    """The whole point of dispatching AOT executables: same HLO, same
    donation, bitwise-identical training — loss trajectory, params, and
    balancer partitions must match the lazy-jit run exactly."""

    def run(**kw):
        tr = Trainer(
            _cfg(**kw), bundle=bundle, timing_model=linear_time, log_to_file=False
        )
        rec = tr.run()
        return tr, rec

    tr_lazy, rec_lazy = run(aot_warm=False, warm_start=False)
    tr_aot, rec_aot = run(aot_warm=True, warm_start=True)
    assert tr_aot._aot is not None and tr_aot._aot.stats()["compiled"] >= 1
    np.testing.assert_array_equal(
        rec_lazy.data["train_loss"], rec_aot.data["train_loss"]
    )
    np.testing.assert_array_equal(
        np.asarray(rec_lazy.data["partition"]), np.asarray(rec_aot.data["partition"])
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(tr_lazy.state.params),
        jax.tree_util.tree_leaves(tr_aot.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_warm_compile_count_bounded_by_ladder(bundle):
    """Tier-1 CI guard: the AOT warm submits exactly (used devices) x
    (ladder rungs) x (plain + windowed) jobs — one compile each — and the
    total backend-compile event count stays under the ladder bound. A
    regression to per-worker or per-dispatch recompiles trips this."""
    cfg = _cfg(warm_start=True, aot_warm=True)
    tr = Trainer(cfg, bundle=bundle, timing_model=linear_time, log_to_file=False)
    max_share = min(1.0, cfg.capacity_factor / cfg.world_size)
    max_b = -(-int(np.ceil(max_share * cfg.batch_size)) // cfg.bucket) * cfg.bucket
    ladder_len = len(range(cfg.bucket, max_b + 1, cfg.bucket))
    n_used = len(tr.topology.used_device_indices)
    assert tr._elastic_mode() == "window"
    # plain probe executable + one windowed twin per rung per device, plus
    # the two mesh-wide combine twins (warm-submitted since the multi-device
    # AOT lowering landed — they dispatch every elastic step/probe)
    expected_jobs = n_used * ladder_len * 2 + 2
    per_job_events = 8  # constants/layout twins ride along with each compile
    with compile_budget(
        max_compiles=per_job_events * expected_jobs,
        label="aot warm ladder",
        include_background=True,
    ):
        tr._maybe_warm()
        assert tr._aot.wait() == []
    st = tr._aot.stats()
    assert st["submitted"] == expected_jobs
    assert st["compiled"] == expected_jobs  # exactly one compile per key
    assert st["failed"] == 0


def test_rebalance_sentinel_silent_with_speculation(bundle):
    """Acceptance: with speculation on, the recompile sentinel reports ZERO
    steady-state foreground compiles on a rebalancing run — every fresh
    layout a rebalance dispatches was compiled in the background (adjacent
    rungs speculated while the previous epoch executed), so no timed epoch
    blocks on XLA."""
    cfg = _cfg(epoch_size=4, warm_start=False, aot_warm=True, aot_speculate=True)
    tr = Trainer(cfg, bundle=bundle, timing_model=linear_time, log_to_file=False)
    warnings_seen = []
    orig_warning = tr.logger.warning
    tr.logger.warning = lambda msg, *a, **k: warnings_seen.append(str(msg))
    try:
        rec = tr.run()
    finally:
        tr.logger.warning = orig_warning
    # the plan actually rebalanced away from uniform (3:1 modeled straggler)
    parts = np.asarray(rec.data["partition"])
    assert not np.allclose(parts[-1], parts[0])
    compiles = rec.data["xla_compiles"]
    # epoch 0 pays the one-time foreground work (eval, combine, tiny probes);
    # steady-state epochs must be compile-free on the execution path
    assert sum(compiles[2:]) == 0, compiles
    assert tr._aot.stats()["speculative"] > 0
    assert not any("XLA backend compile" in w for w in warnings_seen), warnings_seen


def test_fused_path_sentinel_silent_and_registry_dispatched(bundle):
    """ISSUE-5 acceptance: the fused multi-device path compiles zero
    steady-state foreground programs. The mesh-sharded whole-epoch scan
    (`fused_epoch`/`fused_epoch_idx`) AOT-lowers from ShapeDtypeStructs with
    explicit shardings at warm-start and dispatches from the service
    registry — the lazy jit cache stays EMPTY, so the executable provably
    came from the AOT path, not a lazy fallback."""
    cfg = _cfg(
        epoch_size=4,
        warm_start=True,
        aot_warm=True,
        fused_dbs=True,
        fault_tolerance=True,
    )
    from dynamic_load_balance_distributeddnn_tpu.faults import (
        StaticStragglerInjector,
    )

    tr = Trainer(
        cfg,
        bundle=bundle,
        injector=StaticStragglerInjector([3.0, 1.0, 1.0, 1.0], mode="virtual"),
        timing_model=linear_time,
        log_to_file=False,
    )
    rec = tr.run()
    fused_keys = [
        k for k in tr._aot.keys() if k[0] in ("fused_epoch", "fused_epoch_idx")
    ]
    assert fused_keys, tr._aot.keys()
    assert all(tr._aot.get(k) is not None for k in fused_keys)
    # registry dispatch: the lazy twins never compiled
    scan = (
        tr.steps.fused_epoch_idx if tr._use_device_cache else tr.steps.fused_epoch
    )
    assert scan._cache_size() == 0
    compiles = rec.data["xla_compiles"]
    # epoch 0 pays the one-time foreground work; the fused steady state must
    # be compile-free INCLUDING the mesh program (the PR-3 exclusion, lifted)
    assert sum(compiles[2:]) == 0, compiles
    assert np.isfinite(rec.data["train_loss"]).all()


def test_scan_speculation_precompiles_predicted_tuple(bundle):
    """Scan-mode tuple speculation: with `speculate_scan`, the predictor's
    superstep (shapes, window) keys are background-compiled in the untimed
    tail, and a rebalancing scan run's steady-state epochs stay
    foreground-compile-free."""
    cfg = _cfg(
        epoch_size=4,
        warm_start=True,
        aot_warm=True,
        aot_speculate=True,
        speculate_scan=True,
        superstep="auto",
        device=0,  # all workers on one device group -> scan mode
    )
    tr = Trainer(
        cfg, bundle=bundle, timing_model=linear_time, log_to_file=False
    )
    assert tr._elastic_mode() == "scan"
    rec = tr.run()
    parts = np.asarray(rec.data["partition"])
    assert not np.allclose(parts[-1], parts[0])  # it rebalanced
    compiles = rec.data["xla_compiles"]
    assert sum(compiles[2:]) == 0, compiles
    # The converged run above predicts the tuple it already dispatches —
    # every speculation dedups to a lookup (the cheap steady state). Drive
    # the predictor onto a MOVING trajectory and check the wiring: the
    # predicted (unseen) tuple is queued speculatively.
    calls = []
    tr._aot_submit_superstep = (
        lambda padded, win, speculative=False: calls.append(
            (tuple(padded), int(win), speculative)
        )
        or []
    )
    tr._share_predictor.observe(np.array([0.25, 0.25, 0.25, 0.25]))
    tr._share_predictor.observe(np.array([0.375, 0.2083, 0.2084, 0.2083]))
    tr._speculate_scan_tuple()
    assert calls, "moving trajectory must queue the predicted tuple"
    assert all(spec for _, _, spec in calls)
    # velocity extrapolation: worker 0's padded batch keeps growing past
    # its last realized rung
    assert calls[0][0][0] > 0.375 * 64


def test_aot_off_keeps_legacy_warm(bundle):
    """--aot_warm off: no service, the legacy execute-to-compile warm runs
    (the serial reference leg)."""
    cfg = _cfg(warm_start=True, aot_warm=False, epoch_size=1)
    tr = Trainer(cfg, bundle=bundle, timing_model=linear_time, log_to_file=False)
    assert tr._aot is None
    tr._maybe_warm()  # executes the dummy ladder without error
    assert tr._warmed


@pytest.mark.parametrize("release", [False, True])
def test_close_lets_the_executables_go_and_clears_caches_only_where_asked(monkeypatch, release):
    """A closed service holds no executable. JAX's in-memory caches go with
    them only for a service built with ``release_caches`` (--release_on_close:
    on the TPU a program's temporaries stay reserved while any cache holds
    its executable)."""
    cleared = []
    monkeypatch.setattr(jax, "clear_caches", lambda: cleared.append(True))
    svc = AOTCompileService(workers=1, release_caches=release)
    svc.submit("k", jax.jit(lambda x: x * 2), (jax.ShapeDtypeStruct((4,), jnp.float32),))
    assert svc.wait() == [] and svc.get("k") is not None
    svc.close()
    svc.close()  # a second close (the trainer's finalizer) clears nothing more
    assert svc.get("k") is None and svc.keys() == [] and cleared == [True] * release
