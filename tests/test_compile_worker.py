"""Process-parallel compile workers (runtime/compile_worker.py) + the
solver-trajectory speculation predictor (ISSUE 5).

The worker-pool contract under test:

* ``backend="process"`` ships the serialized lowering to a subprocess
  worker, which compiles it into the run's pinned persistent cache; the
  in-process replay is then a cache hit (deserialization, not compilation).
* A dead/failed worker costs nothing: the replay compiles in-process,
  which is exactly the ``backend="thread"`` behavior.
* Thread- and process-backend executables are interchangeable: same
  optimized program, bitwise-identical outputs.

Workers are real spawned processes importing jax (~5-10 s each on the CPU
tier), so the pool-backed tests share one module-scoped service with a
single worker.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.balance.solver import (
    ShareTrajectoryPredictor,
    integer_batch_split,
    quantize_batches,
    rebalance,
)
from dynamic_load_balance_distributeddnn_tpu.runtime.compile_worker import (
    CompileWorkerPool,
    default_worker_count,
    ensure_persistent_cache,
    extract_lowering_payload,
)
from dynamic_load_balance_distributeddnn_tpu.runtime.compiler import (
    AOTCompileService,
)


def _make_program(tag: float, width: int = 17):
    """A distinct-by-construction jitted program + its abstract spec.
    ``tag`` lands in a constant so every test compiles a fresh key even
    against the shared persistent cache; odd widths keep the shapes off
    anything the engine tests compile."""

    @jax.jit
    def f(x, y):
        return jnp.tanh(x @ y) * tag + (x * y).sum()

    spec = (
        jax.ShapeDtypeStruct((width, width), jnp.float32),
        jax.ShapeDtypeStruct((width, width), jnp.float32),
    )
    return f, spec


@pytest.fixture(scope="module")
def proc_service():
    """One process-backend service (single subprocess worker) shared by the
    pool tests — the worker's jax import is paid once for the module."""
    svc = AOTCompileService(workers=2, backend="process", process_workers=1)
    pool = svc._ensure_worker_pool()
    if pool is None:
        pytest.skip("compile worker pool unavailable in this environment")
    assert pool.wait_ready(timeout=180), "worker never finished its jax import"
    yield svc
    svc.close()


def test_worker_compiles_one_per_key_and_replay_hits_cache(proc_service):
    """One submit -> one worker compile; the in-process replay is a
    persistent-cache HIT (no second backend compile in the parent)."""
    from jax._src import monitoring

    hits = []
    monitoring.register_event_listener(
        lambda name, **kw: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits"
        else None
    )
    f, spec = _make_program(3.25)
    fut = proc_service.submit(("wk", "hit"), f, spec)
    fut.result(timeout=300)
    assert proc_service.wait() == []
    st = proc_service.stats()
    assert st["worker_compiled"] >= 1, st
    assert st["worker_fallback"] == 0, st
    # the replay deserialized the worker's cache entry instead of
    # recompiling: the cache-hit event fired in THIS process
    assert hits, "parent replay missed the persistent cache"
    # dedup across submitters: a second submit on the same key is a lookup
    again = proc_service.submit(("wk", "hit"), f, spec)
    assert again.result(timeout=10) is fut.result()
    assert proc_service.stats()["deduped"] >= 1


def test_thread_and_process_backends_bitwise_identical(proc_service):
    """The worker only pre-pays the cache; the replayed executable is the
    same program a thread-backend compile produces — same optimized HLO,
    bitwise-identical outputs."""
    f, spec = _make_program(7.5, width=19)
    compiled_p = proc_service.compile_now(("wk", "parity"), f, spec)
    svc_t = AOTCompileService(workers=1, backend="thread")
    try:
        g, _ = _make_program(7.5, width=19)
        compiled_t = svc_t.compile_now(("wk", "parity"), g, spec)
    finally:
        svc_t.close()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(19, 19), jnp.float32)
    y = jnp.asarray(rng.randn(19, 19), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(compiled_p(x, y)), np.asarray(compiled_t(x, y))
    )
    assert compiled_p.as_text() == compiled_t.as_text()


def test_worker_death_falls_back_in_process(proc_service):
    """Killing every worker degrades the job to an in-process compile —
    the service never raises, the executable still lands in the registry."""
    pool = proc_service._worker_pool
    for p in pool._procs:
        p.terminate()
    for p in pool._procs:
        p.join(10)
    f, spec = _make_program(11.0, width=21)
    fut = proc_service.submit(("wk", "death"), f, spec)
    compiled = fut.result(timeout=300)
    assert proc_service.wait() == []
    st = proc_service.stats()
    assert st["worker_fallback"] >= 1, st
    assert proc_service.get(("wk", "death")) is compiled
    x = jnp.ones((21, 21), jnp.float32)
    assert np.isfinite(np.asarray(compiled(x, x))).all()


def test_payload_extraction_is_self_contained():
    """The payload carries everything the worker needs: MLIR bytecode,
    serialized CompileOptions, device ids, platform — and an unoffloadable
    program returns None, while a lowering whose private surface this code
    was not written against raises instead of degrading in silence."""
    f, spec = _make_program(1.5, width=23)
    payload = extract_lowering_payload(f.lower(*spec))
    assert payload is not None
    assert isinstance(payload["module"], bytes) and payload["module"]
    assert isinstance(payload["options"], bytes) and payload["options"]
    assert payload["platform"] == "cpu"
    assert payload["device_ids"] == [0]
    # host callback: deliberately not offloadable
    cb = jax.jit(lambda x: jax.debug.callback(lambda v: None, x, ordered=True))
    assert extract_lowering_payload(cb.lower(spec[0])) is None
    with pytest.raises(AttributeError):
        extract_lowering_payload(object())


def test_pool_sizing_default_adapts_to_cores(monkeypatch):
    """Auto worker count scales with the host (PR 5 follow-up): small hosts
    keep the old one-per-core cap of 4; many-core hosts get cpus/2 capped
    at 8 — the regime where per-program compiles stop sharing an emitter."""
    import os as _os

    from dynamic_load_balance_distributeddnn_tpu.runtime import (
        compile_worker as cw,
        compiler as rc,
    )

    assert 1 <= default_worker_count() <= 8
    for cpus, want_workers in ((1, 1), (4, 4), (8, 4), (16, 8), (64, 8)):
        monkeypatch.setattr(_os, "cpu_count", lambda n=cpus: n)
        assert cw.default_worker_count() == want_workers, cpus
    # thread-pool width: ~3/4 of cores, floor 2, cap 16
    for cpus, want_pool in ((1, 2), (4, 3), (8, 6), (16, 12), (64, 16)):
        monkeypatch.setattr(_os, "cpu_count", lambda n=cpus: n)
        assert rc.default_pool_size() == want_pool, cpus


def test_process_backend_refuses_to_start_on_a_tpu(monkeypatch):
    """A chip belongs to one process: compile workers open the default
    platform's backend, so on a TPU the process backend fails at start-up
    with that reason instead of degrading to in-process compiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip belongs to this process"):
        AOTCompileService(backend="process")
    AOTCompileService(backend="thread").close()  # the default is unaffected


def test_dead_at_spawn_pool_unblocks_waiters_fast():
    """A pool whose workers die before ever acking ready (e.g. a __main__
    the spawn machinery cannot re-import) must cost ~0: wait_ready returns
    False as soon as the death is detected, not after its full timeout —
    pre-fix every offloaded job paid one whole ready-timeout before falling
    back, stretching a 12 s epoch to 250 s."""
    import time

    pool = CompileWorkerPool(1)
    for p in pool._procs:
        p.terminate()  # well before the ~5 s jax import can ack ready
    t0 = time.perf_counter()
    assert pool.wait_ready(timeout=60) is False
    assert time.perf_counter() - t0 < 30
    ok, err = pool.wait(pool.submit("dead", {"module": b""}))
    assert not ok and err
    pool.shutdown()


def test_ensure_persistent_cache_respects_configured_dir():
    """conftest places the suite's cache through the one helper; the worker
    channel must reuse that directory, not fork a second cache."""
    configured = jax.config.jax_compilation_cache_dir
    assert configured
    assert ensure_persistent_cache() == str(configured)


# ------------------------------------------------- trajectory speculation


def _trajectory(n_epochs=14, bucket=8, batch=256):
    """Synthetic DBS feedback loop: heterogeneous worker speeds (worker 0 a
    3x straggler, the rest spread 1.0-1.4x); each epoch probes, rebalances,
    quantizes — the exact pipeline the engine feeds the predictor. Distinct
    speeds keep the fixed point STABLE: with exactly-equal workers the
    integer split breaks ties by index and probe noise permutes their rungs
    every epoch — a jitter no one-step predictor can (or should) chase."""
    speed = np.array([3.0, 1.0, 1.2, 1.4])
    ws = speed.size
    shares = np.full(ws, 1.0 / ws)
    out = []
    for _ in range(n_epochs):
        batches = quantize_batches(
            integer_batch_split(shares, batch), bucket, batch
        )
        node_times = batches * speed * (1.0 + 0.01 * np.random.RandomState(
            len(out)).randn(ws))
        shares, _ = rebalance(node_times, batches / batches.sum(), batch)
        out.append((shares.copy(), batches.copy()))
    return out


def test_predictor_hit_rate_on_converging_trajectory():
    """Speculation smoke: on a converging solver trajectory the predictor's
    quantized batch vector matches the NEXT epoch's realized vector for
    most steady-state epochs — each hit is a superstep tuple key compiled
    before it is dispatched."""
    traj = _trajectory()
    pred = ShareTrajectoryPredictor()
    hits = total = 0
    for i, (shares, _) in enumerate(traj[:-1]):
        # the engine observes REALIZED (post-quantization) shares
        realized = traj[i][1] / traj[i][1].sum()
        pred.observe(realized)
        guess = pred.predict_batches(256, bucket=8)
        if i < 3:  # transient: the EMA is still locking on
            continue
        total += 1
        if guess is not None and np.array_equal(guess, traj[i + 1][1]):
            hits += 1
    assert total >= 8
    assert hits / total >= 0.7, (hits, total)


def test_predictor_handles_world_size_change_and_cap():
    pred = ShareTrajectoryPredictor()
    pred.observe(np.array([0.5, 0.5]))
    pred.observe(np.array([0.6, 0.4]))
    assert pred.predict() is not None
    # world size changes: the velocity track restarts instead of mixing
    # incompatible shapes
    pred.observe(np.array([0.4, 0.3, 0.3]))
    p = pred.predict()
    assert p is not None and p.shape == (3,)
    np.testing.assert_allclose(p.sum(), 1.0)
    # share cap redistributes the excess onto the free workers
    pred2 = ShareTrajectoryPredictor()
    pred2.observe(np.array([0.7, 0.2, 0.1]))
    pred2.observe(np.array([0.8, 0.15, 0.05]))
    batches = pred2.predict_batches(240, bucket=0, max_share=0.5)
    assert batches is not None
    assert batches.max() <= 0.5 * 240 + 1  # integer split rounding slack
    assert batches.sum() == 240


def test_predictor_before_first_observation():
    pred = ShareTrajectoryPredictor()
    assert pred.predict() is None
    assert pred.predict_batches(256) is None
