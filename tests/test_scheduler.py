"""Many-stream training engine (ISSUE 18): job-as-value scheduling over one
device pool.

The contract stack, bottom-up: the :class:`DevicePool` allocator moves
ordinals minimally and only between windows (G019 quiesce discipline); the
outer inverse-time solve partitions devices ∝ demand (more devices → shorter
tenant epoch, the inverse of the inner examples→time coupling); a sole
tenant through :class:`MultiStreamEngine` is BITWISE identical to the legacy
direct ``Trainer.run()`` loop; a job admission costs zero foreground
compiles in the steady-state windows around it; and the analysis surfaces
(G012 thread inventory, ``reshard_surface``) discover the scheduler's
worker threads and the pool's topology writes without being told.
"""

import pathlib
import threading

import numpy as np
import pytest

import jax

from dynamic_load_balance_distributeddnn_tpu.analysis.flow import (
    CallGraph,
    Project,
)
from dynamic_load_balance_distributeddnn_tpu.analysis.flow.mesh import (
    reshard_surface,
)
from dynamic_load_balance_distributeddnn_tpu.analysis.guards import (
    compile_budget,
)
from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.datasets import (
    synthetic_dataset,
)
from dynamic_load_balance_distributeddnn_tpu.faults import (
    StaticStragglerInjector,
)
from dynamic_load_balance_distributeddnn_tpu.runtime.scheduler import (
    DevicePool,
    JobSpec,
    JobState,
    MultiStreamEngine,
)
from dynamic_load_balance_distributeddnn_tpu.obs.trace import (
    attribution_by_job,
    get_tracer,
)
from dynamic_load_balance_distributeddnn_tpu.train import Trainer

REPO = pathlib.Path(__file__).resolve().parents[1]
SCHEDULER_SRC = (
    REPO / "dynamic_load_balance_distributeddnn_tpu" / "runtime" / "scheduler.py"
)


def linear_time(plan):
    return np.array([w.padded_batch * w.steps * 1e-3 for w in plan.workers])


@pytest.fixture(scope="module")
def bundle():
    return synthetic_dataset("mnist", n_train=1024, n_test=256)


def _cfg(**kw):
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


# ------------------------------------------------------------- device pool


def test_pool_reallocate_sums_and_minimal_movement():
    pool = DevicePool(8)
    first = pool.reallocate({"a": 4, "b": 4})
    assert first["a"] == (0, 1, 2, 3)
    assert first["b"] == (4, 5, 6, 7)
    # shrinking a and growing b must not move b's surviving ordinals
    second = pool.reallocate({"a": 2, "b": 6})
    assert second["a"] == (0, 1)
    assert set(second["b"]) >= {4, 5, 6, 7}  # kept its whole footprint
    assert len(second["b"]) == 6
    assert set(second["a"]) | set(second["b"]) == set(range(8))
    assert pool.allocation() == second


def test_pool_release_and_free_devices():
    pool = DevicePool(4)
    pool.reallocate({"a": 2, "b": 2})
    pool.release("a")
    assert pool.devices_of("a") == ()
    assert pool.free_devices() == (0, 1)
    assert pool.devices_of("b") == (2, 3)


def test_pool_rejects_overcommit_and_negative_counts():
    pool = DevicePool(4)
    with pytest.raises(ValueError, match="pool has"):
        pool.reallocate({"a": 3, "b": 2})
    with pytest.raises(ValueError, match="non-negative"):
        pool.reallocate({"a": -1})


def test_pool_topology_write_is_gated_on_the_window_quiesce():
    """G019 in vivo: a re-allocation (or release) while tenants are inside
    a window is a hard error, not a silently-racing mesh write."""
    pool = DevicePool(4)
    pool.reallocate({"a": 4})
    pool.begin_window()
    with pytest.raises(RuntimeError, match="window is open"):
        pool.reallocate({"a": 2})
    with pytest.raises(RuntimeError, match="window is open"):
        pool.release("a")
    pool.end_window()
    assert pool.reallocate({"a": 2})["a"] == (0, 1)


# ------------------------------------------------------------- outer solve


def _fake_job(job_id, wall=None, devices=(), **spec_kw):
    js = JobState(JobSpec(job_id, _cfg(), **spec_kw))
    js.wall_ema = wall
    js.devices = tuple(devices)
    return js


def test_outer_counts_inverse_time_direction():
    """The outer coupling is INVERTED relative to the inner DBS problem:
    the slower tenant (longer epoch wall on the same footprint) must be
    handed MORE devices — shares follow r_j ∝ p_j·t_j, equalizing walls."""
    eng = MultiStreamEngine(n_devices=8)
    slow = _fake_job("slow", wall=6.0, devices=(0, 1, 2, 3))
    fast = _fake_job("fast", wall=2.0, devices=(4, 5, 6, 7))
    counts = eng._outer_counts([slow, fast])
    assert counts["slow"] + counts["fast"] == 8
    assert counts["slow"] == 6 and counts["fast"] == 2
    # modeled walls equalize at the fixed point: 24/6 == 8/2
    assert slow.demand_s() / counts["slow"] == pytest.approx(
        fast.demand_s() / counts["fast"]
    )


def test_outer_counts_every_tenant_keeps_a_device():
    eng = MultiStreamEngine(n_devices=4)
    whale = _fake_job("whale", wall=1000.0, devices=(0, 1, 2))
    minnow = _fake_job("minnow", wall=0.001, devices=(3,))
    counts = eng._outer_counts([whale, minnow])
    assert counts["minnow"] >= 1
    assert counts["whale"] + counts["minnow"] == 4


def test_outer_counts_unmeasured_tenants_seed_at_median_demand():
    eng = MultiStreamEngine(n_devices=8)
    known = _fake_job("known", wall=2.0, devices=(0, 1, 2, 3))
    fresh = _fake_job("fresh")  # no wall yet: probe-seeded admission
    counts = eng._outer_counts([known, fresh])
    # the fresh tenant seeds at the known tenant's demand → even split
    assert counts == {"known": 4, "fresh": 4}


def test_outer_counts_max_devices_cap_redistributes():
    eng = MultiStreamEngine(n_devices=8)
    capped = _fake_job("capped", wall=6.0, devices=(0, 1, 2, 3), max_devices=3)
    other = _fake_job("other", wall=2.0, devices=(4, 5, 6, 7))
    counts = eng._outer_counts([capped, other])
    assert counts["capped"] == 3  # clipped from the solve's 6
    assert counts["other"] == 5  # takes the freed devices
    solo = _fake_job("solo", wall=1.0, devices=(0,), max_devices=2)
    assert eng._outer_counts([solo]) == {"solo": 2}  # excess idles


def test_outer_counts_rejects_more_jobs_than_devices():
    eng = MultiStreamEngine(n_devices=2)
    live = [_fake_job(f"j{i}") for i in range(3)]
    with pytest.raises(RuntimeError, match="exceed"):
        eng._outer_counts(live)


def test_submit_rejects_elastic_tenants_and_duplicates(bundle):
    eng = MultiStreamEngine(n_devices=2)
    with pytest.raises(ValueError, match="elastic"):
        eng.submit(JobSpec("e", _cfg(elastic="on", fault_tolerance=True)))
    eng.submit(JobSpec("a", _cfg(), bundle=bundle))
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(JobSpec("a", _cfg(), bundle=bundle))


# ------------------------------------------------- single-tenant parity


def test_single_job_bitwise_matches_legacy_engine(bundle, tmp_path):
    """THE tentpole contract: one job through the MultiStreamEngine is the
    legacy plan→dispatch→record loop verbatim — same losses, same partition
    trajectory, same final parameters, bit for bit."""
    kw = dict(
        device=0,  # whole fleet on ordinal 0: a 1-device pool covers it
        epoch_size=3,
        stat_dir=str(tmp_path / "legacy"),
    )
    mk_inj = lambda: StaticStragglerInjector(  # noqa: E731
        [3.0, 1.0, 1.0, 1.0], mode="virtual"
    )
    legacy = Trainer(
        _cfg(**kw),
        bundle=bundle,
        injector=mk_inj(),
        timing_model=linear_time,
        log_to_file=False,
    )
    rec_legacy = legacy.run()

    eng = MultiStreamEngine(n_devices=1)
    kw["stat_dir"] = str(tmp_path / "ms")
    js = eng.submit(
        JobSpec(
            "solo",
            _cfg(**kw),
            bundle=bundle,
            injector=mk_inj(),
            timing_model=linear_time,
        )
    )
    eng.run()

    assert js.status == "done"
    assert js.migrations == 0
    assert js.epochs_done == 3
    rec_ms = js.recorder
    np.testing.assert_array_equal(
        rec_legacy.data["train_loss"], rec_ms.data["train_loss"]
    )
    np.testing.assert_array_equal(
        rec_legacy.data["partition"], rec_ms.data["partition"]
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(legacy.state.params),
        jax.tree_util.tree_leaves(js.trainer.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- admission compile discipline


def test_job_admission_is_compile_free_in_steady_windows(bundle, tmp_path):
    """Admitting tenant B must not put a single foreground compile into the
    surrounding windows: construction + warm happen at the boundary, and
    tenant A's executables (comm-sig keyed per job) are untouched. Also
    pins that a TENANT trainer never reconfigures the process tracer —
    B's admission must not drop A's buffered spans or untag its worker
    thread (both jobs attribute in the shared trace at the end)."""
    get_tracer().configure("on")
    cfg_a = _cfg(
        world_size=2,
        device=[0, 1],
        dynamic_batch_size=False,
        batch_size=64,
        epoch_size=4,
        stat_dir=str(tmp_path / "a"),
    )
    cfg_b = _cfg(
        world_size=2,
        device=[2, 3],
        dynamic_batch_size=False,
        batch_size=64,
        epoch_size=4,
        seed=77,
        stat_dir=str(tmp_path / "b"),
    )
    eng = MultiStreamEngine(n_devices=8)
    js_a = eng.submit(
        JobSpec("a", cfg_a, bundle=bundle, epochs=3, max_devices=2)
    )
    js_b = eng.submit(
        JobSpec("b", cfg_b, bundle=bundle, epochs=2, max_devices=2)
    )
    # window 0: A alone (its epoch-0 compiles land here, off any budget)
    eng._admit(js_a)
    eng._solve_and_actuate([js_a], membership_changed=True)
    eng._run_window([js_a])
    eng._window += 1
    # boundary: admit B — trainer construction + warm OFF the timed path
    eng._admit(js_b)
    eng._solve_and_actuate([js_a, js_b], membership_changed=True)
    dev_a = js_a.devices
    # window 1: B's first epoch (epoch-0 eval executes its warmed ladder)
    eng._run_window([js_a, js_b])
    eng._window += 1
    # window 2: steady state across the admission — ZERO foreground compiles
    with compile_budget(max_compiles=0, label="steady multistream window"):
        eng._run_window([js_a, js_b])
    eng._window += 1
    assert js_a.devices == dev_a  # A's footprint never moved
    assert js_a.migrations == 0 and js_b.migrations == 0
    assert js_a.status == "finishing" and js_b.status == "finishing"
    eng._retire([js_a, js_b])
    assert js_a.status == "done" and js_b.status == "done"
    assert js_a.epochs_done == 3 and js_b.epochs_done == 2
    # per-tenant attribution survived B's admission: A's pre-admission
    # spans are still in the buffer and both workers kept their job tags
    att = attribution_by_job(get_tracer().chrome_events())
    get_tracer().configure("off")
    assert att["jobs"]["a"]["epochs"] == 3, att["jobs"]
    assert att["jobs"]["b"]["epochs"] == 2, att["jobs"]


# --------------------------------------------- multi-tenant outer re-solve


def test_outer_solve_migrates_devices_toward_the_heavy_tenant(
    bundle, tmp_path
):
    """Two live tenants with 3:1 modeled demand: the engine must migrate
    devices from the light tenant to the heavy one mid-flight (planned
    re-shard through ``_reshard_world``) and both must still finish.

    The pool is 4 of the suite's 8 virtual devices: XLA:CPU's in-process
    collectives hold one thread of the client's pool (max(cores, virtual
    devices) threads) for every participant until all have arrived, and two
    tenants that together span every device, each with more than one program
    in flight, leave an all-reduce short of threads: at 8 of 8 on an 8-core
    machine the heavy tenant's 6-way all-reduce saw 2 arrive and the runtime
    aborted the process after 40 s (with 16 virtual devices the same test
    passed)."""
    demand = {"heavy": 24.0, "light": 8.0}

    def wall_model(js):
        return demand[js.spec.job_id] / max(len(js.devices), 1)

    def job(job_id, seed):
        return JobSpec(
            job_id,
            _cfg(
                world_size=4,
                device=None,  # round-robin: rank r on ordinal r
                dynamic_batch_size=False,
                batch_size=64,
                epoch_size=3,
                seed=seed,
                stat_dir=str(tmp_path / job_id),
            ),
            bundle=bundle,
            epochs=3,
        )

    eng = MultiStreamEngine(n_devices=4, wall_model=wall_model)
    js_heavy = eng.submit(job("heavy", 11))
    js_light = eng.submit(job("light", 22))
    jobs = eng.run()
    assert {j.status for j in jobs.values()} == {"done"}
    # the 3:1 demand ratio splits the 4-device pool 3:1 at the fixed point
    assert js_heavy.migrations >= 1 and js_light.migrations >= 1
    final = eng.windows[-1]["jobs"]
    assert final["heavy"]["devices"] == 3
    assert final["light"]["devices"] == 1
    # modeled walls equalized by the migration
    assert demand["heavy"] / 3 == pytest.approx(demand["light"] / 1)
    st = eng.stats()
    assert st["windows"] >= 2
    assert st["jobs"]["heavy"]["epochs"] == 3
    assert st["jobs"]["light"]["epochs"] == 3
    assert st["migrations"] >= 2


def test_zero_epoch_job_retires_without_a_worker_thread(bundle, tmp_path):
    js_spec = JobSpec(
        "noop",
        _cfg(device=0, stat_dir=str(tmp_path)),
        bundle=bundle,
        epochs=0,
    )
    eng = MultiStreamEngine(n_devices=1)
    js = eng.submit(js_spec)
    eng.run()
    assert js.status == "done"
    assert js.worker_thread is None
    assert js.epochs_done == 0
    assert eng.pool.free_devices() == (0,)


def test_failing_tenant_reports_and_releases_its_devices(bundle, tmp_path):
    class Boom(RuntimeError):
        pass

    def exploding_injector():
        raise Boom("injected")

    js_spec = JobSpec(
        "bad",
        _cfg(device=0, stat_dir=str(tmp_path)),
        bundle=bundle,
        # timing_model runs inside run_epoch: first plan dispatch raises
        timing_model=lambda plan: exploding_injector(),
        epochs=2,
    )
    eng = MultiStreamEngine(n_devices=1)
    js = eng.submit(js_spec)
    with pytest.raises(RuntimeError, match="bad"):
        eng.run()
    assert js.status == "failed"
    assert isinstance(js.error, Boom)
    assert eng.pool.free_devices() == (0,)  # devices freed on retirement
    assert eng.run(raise_on_failure=False)["bad"].status == "failed"


# -------------------------------------------------------- analysis surface


@pytest.fixture(scope="module")
def scheduler_project():
    return Project.load([str(SCHEDULER_SRC)])


def test_thread_inventory_discovers_the_job_worker(scheduler_project):
    """ISSUE 18: G012's thread inventory must see the per-tenant driver
    thread — everything it touches is lock-checked interprocedurally."""
    thread_fns = CallGraph(scheduler_project).thread_sides()[0]
    tails = {fn.rsplit("::", 1)[-1] for fn in thread_fns}
    assert "MultiStreamEngine._job_worker" in tails, sorted(tails)


def test_reshard_surface_discovers_pool_topology_writes(scheduler_project):
    """The pool allocator's ordinal→tenant map lives under ``_mesh`` so
    G019's quiesce discipline covers pool re-allocations like any other
    topology write — discovery, not annotation."""
    mutators, can_reshard = reshard_surface(
        scheduler_project, CallGraph(scheduler_project)
    )
    tails = {fn.rsplit("::", 1)[-1] for fn in mutators}
    assert "DevicePool.reallocate" in tails, sorted(tails)
    assert "DevicePool.release" in tails, sorted(tails)
