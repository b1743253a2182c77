"""Composable ZeRO-1 (ISSUE 13): generic optax weight-update sharding.

Contracts:

* **Bitwise parity, arbitrary transforms** — the sharded update (flat-ravel
  reduce_scatter -> tx.update on the 1/n chunk -> all_gather delta) equals
  the replicated per-leaf optax update for SGD-momentum AND adamw. Proven
  BITWISE at the collective level on integer-valued gradients (every
  summation order is exact, and elementwise transforms are layout-
  invariant), and to accumulation-order tolerance end-to-end.
* **Hier/wire composition** — on the two-level mesh the ZeRO-1 gradient
  reduce-scatter becomes the in-host reduce-scatter plus ONE compressed
  cross-host hop with the error-feedback residual carried per-chunk: fp32
  wire bitwise vs flat, int8/int4 convergent.
* **Elastic composition** — the 1/N optimizer chunks survive a worker
  loss: the reshard re-chunks them onto the survivor mesh and training
  continues (orbax round-trip across the reshard asserted separately).
* **DBS composition** — the sharded update rides the elastic combine
  twins; warm-started composed runs report zero steady-state foreground
  compiles.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
    data_mesh,
    hier_mesh,
    shard_map,
    zero1_chunk_axes,
)
from dynamic_load_balance_distributeddnn_tpu.train import Trainer
from dynamic_load_balance_distributeddnn_tpu.train.state import (
    TrainState,
    shard_optimizer_state,
    zero1_padded_size,
)
from dynamic_load_balance_distributeddnn_tpu.train.steps import StepLibrary


def _params(seed=0):
    """A small multi-leaf tree with a non-divisible total (padding real)."""
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randint(-8, 8, size=(13, 7)).astype(np.float32)),
        "b": jnp.asarray(rng.randint(-8, 8, size=(5,)).astype(np.float32)),
    }


def _int_grads(seed):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randint(-16, 16, size=(13, 7)).astype(np.float32)),
        "b": jnp.asarray(rng.randint(-16, 16, size=(5,)).astype(np.float32)),
    }


def _zero1_lib(mesh, tx, padded, *, hier=False, wire="fp32", compress=""):
    """The production-owned shell exposing ONLY the shipped ZeRO-1 update
    math — the same code object production dispatches, minus the model
    plumbing (StepLibrary.zero1_shell)."""
    return StepLibrary.zero1_shell(
        mesh, tx, padded, hier=hier, wire=wire, compress=compress
    )


def _sharded_step(lib, mesh, state, grads_by_dev):
    """One sharded update through shard_map: each device contributes its own
    local gradient tree (stacked [n, ...] rows, one per device)."""
    bx = lib._batch_entry

    def body(state, stacked):
        local = jax.tree_util.tree_map(lambda g: jnp.sum(g, axis=0), stacked)
        return lib._zero1_update(
            state, local, jax.random.PRNGKey(123), with_comm=True
        )

    fn = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(lib._state_spec(), P(bx)),
            out_specs=lib._state_spec(),
            check_vma=False,
        )
    )
    stacked = jax.device_put(grads_by_dev, NamedSharding(mesh, P(bx)))
    return fn(state, stacked)


def _replicated_step(tx, params, opt_state, grads_sum):
    def step(p, o, g):
        updates, o = tx.update(g, o, p)
        return optax.apply_updates(p, updates), o

    # jit like production (both paths compile; eager op-by-op arithmetic
    # can differ from the fused lowering by an ulp on division chains)
    return jax.jit(step)(params, opt_state, grads_sum)


TXS = {
    "sgd_momentum": lambda: optax.inject_hyperparams(optax.sgd)(
        learning_rate=0.05, momentum=0.9
    ),
    "adamw": lambda: optax.inject_hyperparams(optax.adamw)(
        learning_rate=0.01, weight_decay=0.01
    ),
}


def _assert_parity(sharded, rep_params, rep_opt, padded):
    """The parity contract: the collective+transform chain — reduce-scatter
    sum, chunked ``tx.update``, new opt state — is BITWISE the replicated
    one (integer grads sum exactly under any grouping; elementwise
    transforms are layout-invariant). The final ``p + u`` add is the one
    site where XLA's FMA contraction may fire differently between the two
    lowerings, so params compare to an ulp-scale tolerance."""
    chunked_s = [
        l
        for l in jax.tree_util.tree_leaves(sharded.opt_state)
        if l.ndim >= 1 and l.shape[0] == padded
    ]
    chunked_r = [
        l
        for l in jax.tree_util.tree_leaves(rep_opt)
        if l.ndim >= 1 and l.shape[0] == padded
    ]
    assert chunked_s and len(chunked_s) == len(chunked_r)
    for a, b in zip(chunked_s, chunked_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(rep_params),
        jax.tree_util.tree_leaves(sharded.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-6, atol=5e-6
        )


@pytest.mark.parametrize("kind", sorted(TXS))
def test_sharded_update_parity_flat_mesh(kind):
    """Bitwise parity on the flat mesh, for SGD-momentum and adamw alike:
    the replicated reference runs the SAME transform on the full flat
    vector (proven tree==flat bitwise by elementwise layout-invariance),
    the sharded run through the shipped shard_map spine."""
    mesh = data_mesh()
    n = len(mesh.devices.flat)
    tx = TXS[kind]()
    params = _params()
    padded = zero1_padded_size(params, n)
    state = TrainState(
        params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32)
    )
    sharded = shard_optimizer_state(state, mesh, tx)
    lib = _zero1_lib(mesh, tx, padded)

    rep_params, rep_opt = params, tx.init(params)
    for step in range(3):
        grads = [_int_grads(100 * step + d) for d in range(n)]
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *grads
        )
        sharded = _sharded_step(lib, mesh, sharded, stacked)
        gsum = jax.tree_util.tree_map(
            lambda *ls: sum(ls[1:], ls[0]), *grads
        )
        rep_params, rep_opt = _replicated_step(tx, rep_params, rep_opt, gsum)
        # the reference opt state must mirror the flat-init layout for the
        # bitwise chunk comparison: re-run it flat
    flat_ref = _flat_reference(tx, params, n, padded, steps=3)
    _assert_parity(sharded, rep_params, flat_ref, padded)
    # the chunked state leaves really live 1/n sharded over the mesh
    chunked = [
        l
        for l in jax.tree_util.tree_leaves(sharded.opt_state)
        if l.ndim >= 1 and l.shape[0] == padded
    ]
    assert chunked  # sgd: trace; adamw: mu + nu
    for l in chunked:
        shards = l.addressable_shards
        assert len(shards) == n
        assert all(s.data.shape[0] == padded // n for s in shards)


def _flat_reference(tx, params, n, padded, steps, seed_base=0):
    """Replicated update on the FLAT padded vector — the layout the sharded
    chunks concatenate into, so opt-state leaves compare bitwise."""
    import jax.flatten_util

    fp, _ = jax.flatten_util.ravel_pytree(params)
    fp = jnp.pad(fp, (0, padded - fp.size))

    def stepf(fp, o, fg):
        u, o = tx.update(fg, o, fp)
        return fp + u, o

    fn = jax.jit(stepf)
    o = tx.init(fp)
    for step in range(steps):
        grads = [_int_grads(seed_base + 100 * step + d) for d in range(n)]
        gsum = jax.tree_util.tree_map(lambda *ls: sum(ls[1:], ls[0]), *grads)
        fg, _ = jax.flatten_util.ravel_pytree(gsum)
        fg = jnp.pad(fg, (0, padded - fg.size))
        fp, o = fn(fp, o, fg)
    return o


@pytest.mark.parametrize("kind", sorted(TXS))
def test_sharded_update_parity_hier_fp32(kind):
    """Hier/wire composition at the fp32 wire: in-host reduce-scatter + one
    cross-host hop + host re-split computes the SAME chunk sum as the flat
    reduce-scatter (integer grads), so the composed update keeps the same
    parity contract."""
    mesh = hier_mesh(jax.devices(), 2)
    n = len(jax.devices())
    tx = TXS[kind]()
    params = _params()
    padded = zero1_padded_size(params, n)
    state = TrainState(
        params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32)
    )
    sharded = shard_optimizer_state(state, mesh, tx)
    # per-device residual rows for the DCN hop: [n, chunk_d]
    chunk_d = padded // int(mesh.shape["device"])
    residual = jax.device_put(
        jnp.zeros((n, chunk_d), jnp.float32),
        NamedSharding(mesh, P(("host", "device"))),
    )
    sharded = sharded.replace(comm_residual=residual)
    lib = _zero1_lib(mesh, tx, padded, hier=True, wire="fp32")

    rep_params, rep_opt = params, tx.init(params)
    for step in range(3):
        grads = [_int_grads(500 + 100 * step + d) for d in range(n)]
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *grads)
        sharded = _sharded_step(lib, mesh, sharded, stacked)
        gsum = jax.tree_util.tree_map(lambda *ls: sum(ls[1:], ls[0]), *grads)
        rep_params, rep_opt = _replicated_step(tx, rep_params, rep_opt, gsum)
    flat_ref = _flat_reference(tx, params, n, padded, steps=3, seed_base=500)
    _assert_parity(sharded, rep_params, flat_ref, padded)
    # fp32 wire: the residual exists but stays exactly zero
    assert float(np.abs(np.asarray(sharded.comm_residual)).max()) == 0.0
    # chunk layout is device-major on the two-level mesh
    assert zero1_chunk_axes(mesh) == ("device", "host")


# ------------------------------------------------------ engine composition


@pytest.fixture(scope="module")
def bundle():
    return synthetic_dataset("mnist", n_train=256, n_test=64)


def _cfg(**kw):
    base = dict(
        debug=True,
        world_size=8,
        batch_size=64,
        learning_rate=0.05,
        epoch_size=2,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=False,
        one_cycle_policy=True,  # exercises with_learning_rate on the state
        seed=11,
        bucket=8,
        packed="off",
        device_cache="off",
        shard_update=True,
    )
    base.update(kw)
    return Config(**base)


def _chunk_leaves(state):
    from dynamic_load_balance_distributeddnn_tpu.train.state import (
        zero1_param_count,
    )

    total = zero1_param_count(state.params)
    return [
        l
        for l in jax.tree_util.tree_leaves(state.opt_state)
        if hasattr(l, "ndim") and l.ndim >= 1 and l.shape[0] >= total
    ]


def test_zero1_hier_fp32_matches_flat_end_to_end(bundle):
    """Full fused training, flat+sharded vs 2x4-hier+sharded at the fp32
    wire: the composed reduce-scatter (in-host RS + DCN hop + host
    re-split) is the same sum, so losses/params agree to accumulation-order
    tolerance — the hier/wire composition's end-to-end leg."""
    runs = {}
    for name, kw in (
        ("flat", dict()),
        ("hier", dict(grad_comm="hier", hier_hosts=2, grad_comm_wire="fp32")),
    ):
        tr = Trainer(_cfg(**kw), bundle=bundle, log_to_file=False)
        rec = tr.run()
        runs[name] = (tr, rec)
    assert runs["hier"][0].grad_comm == "hier"
    np.testing.assert_allclose(
        np.asarray(runs["flat"][1].data["train_loss"], dtype=np.float64),
        np.asarray(runs["hier"][1].data["train_loss"], dtype=np.float64),
        rtol=1e-5, atol=1e-6,
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(runs["flat"][0].state.params),
        jax.tree_util.tree_leaves(runs["hier"][0].state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )
    # chunked opt leaves live device-major over the two-level mesh, and the
    # residual exists (sized by the zero-1 padding) but stays exactly zero
    # at the fp32 wire
    tr_h = runs["hier"][0]
    (trace,) = _chunk_leaves(tr_h.state)
    assert trace.sharding.spec == P(("device", "host"))
    res = tr_h.state.comm_residual
    assert res is not None and float(np.abs(np.asarray(res)).max()) == 0.0
    assert res[0].shape[1] * 4 == trace.shape[0]  # chunk_d = padded / D


def test_zero1_hier_int8_trains(bundle):
    """The composed quantized DCN hop converges and leaves a realized
    residual (stochastic rounding error is re-injected next step)."""
    tr = Trainer(
        _cfg(grad_comm="hier", hier_hosts=2, grad_comm_wire="int8"),
        bundle=bundle,
        log_to_file=False,
    )
    rec = tr.run()
    assert np.isfinite(rec.data["train_loss"]).all()
    assert float(np.abs(np.asarray(tr.state.comm_residual)).max()) > 0.0


def test_zero1_rides_elastic_dbs_combine_twins(bundle):
    """DBS composition: with the balancer on (non-fused), the elastic
    dispatch rides the zero-1 combine twins — the sharded update runs per
    step over the mesh and the chunks stay 1/n-sharded while plans
    rebalance."""
    cfg = _cfg(dynamic_batch_size=True, one_cycle_policy=False, epoch_size=2)
    tr = Trainer(cfg, bundle=bundle, log_to_file=False)
    assert tr._combine_names() == ("combine_update_zero1", "combine_probe_zero1")
    rec = tr.run()
    assert np.isfinite(rec.data["train_loss"]).all()
    (trace,) = _chunk_leaves(tr.state)
    assert len(trace.addressable_shards) == 8
    assert float(np.abs(np.asarray(trace)).max()) > 0


def test_zero1_compress_int8_fused_dbs(bundle):
    """compress x shard_update x DBS: the quantized reduce-scatter inside
    the sharded update on the fused-DBS capacity path."""
    cfg = _cfg(
        dynamic_batch_size=True,
        fused_dbs=True,
        compress_grads="int8",
        one_cycle_policy=False,
    )
    tr = Trainer(cfg, bundle=bundle, log_to_file=False)
    rec = tr.run()
    assert np.isfinite(rec.data["train_loss"]).all()
    assert rec.data["train_loss"][-1] < rec.data["train_loss"][0]


# -------------------------------------------------- elastic composition


def _elastic_cfg(**kw):
    base = dict(
        debug=True,
        world_size=4,
        batch_size=64,
        learning_rate=0.05,
        epoch_size=5,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        seed=7,
        bucket=8,
        stream_chunk_steps=1,  # several windows/epoch -> mid-epoch detection
        elastic="on",
        shard_update=True,
        packed="off",
        device_cache="off",
    )
    base.update(kw)
    return Config(**base)


def _factored_timing(holder, base_factors):
    def tm(plan):
        tr = holder["tr"]
        f = np.asarray(base_factors)[np.asarray(tr.active_ranks)]
        return f * np.array(
            [w.batch_size * w.steps * 1e-3 for w in plan.workers]
        )

    return tm


def test_zero1_survives_elastic_reshard(bundle):
    """Elastic composition: kill 1 of 4 mid-epoch — the 1/N optimizer
    chunks re-chunk onto the 3-survivor mesh (new padding multiple), the
    run completes, and the readmitted fleet re-chunks back to 4."""
    from dynamic_load_balance_distributeddnn_tpu.faults import (
        PreemptionEvent,
        PreemptionInjector,
    )
    from dynamic_load_balance_distributeddnn_tpu.train.state import (
        zero1_padded_size,
    )

    holder = {}
    inj = PreemptionInjector(
        4, [PreemptionEvent(worker=3, down_at=1.4, rejoin_epoch=3)]
    )
    tr = Trainer(
        _elastic_cfg(),
        bundle=bundle,
        injector=inj,
        timing_model=_factored_timing(holder, [1.0, 1.0, 1.0, 1.0]),
        log_to_file=False,
    )
    holder["tr"] = tr
    rec = tr.run()
    assert rec.data["epoch"] == list(range(5))
    alive = rec.data["workers_alive"]
    assert 3.0 in alive and alive[-1] == 4.0
    assert rec.data["recoveries"][-1] == 1.0
    assert np.isfinite(rec.data["train_loss"]).all()
    # back at world 4: chunks re-chunked to the 4-device padding, 1/4 per
    # device, with real momentum in them
    (trace,) = _chunk_leaves(tr.state)
    padded4 = zero1_padded_size(tr.state.params, 4)
    assert trace.shape[0] == padded4
    assert len(trace.addressable_shards) == 4
    assert float(np.abs(np.asarray(trace)).max()) > 0


def test_zero1_orbax_roundtrip_across_reshard(bundle, tmp_path):
    """ISSUE 13 satellite: save the 1/N-sharded optimizer state at world 4,
    kill one worker permanently (checkpoints now carry the 3-survivor
    chunks), and restore into a FRESH world-4 trainer: the restore template
    adapts to the saved fleet (checkpoint.py template_fn), the engine
    adopts the survivor set, and the chunks come back 1/3-sharded over the
    3-device mesh with momentum intact."""
    from dynamic_load_balance_distributeddnn_tpu.faults import (
        PreemptionEvent,
        PreemptionInjector,
    )
    from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
        flush_checkpoints,
    )
    from dynamic_load_balance_distributeddnn_tpu.train.state import (
        zero1_padded_size,
    )

    ck = str(tmp_path / "ck")
    holder = {}
    inj = PreemptionInjector(
        4, [PreemptionEvent(worker=3, down_at=1.4, rejoin_epoch=None)]
    )
    cfg = _elastic_cfg(epoch_size=3, ckpt_dir=ck)
    tr = Trainer(
        cfg,
        bundle=bundle,
        injector=inj,
        timing_model=_factored_timing(holder, [1.0, 1.0, 1.0, 1.0]),
        log_to_file=False,
    )
    holder["tr"] = tr
    tr.run()
    flush_checkpoints(ck)
    assert tr.world_size == 3
    (trace3,) = _chunk_leaves(tr.state)
    padded3 = zero1_padded_size(tr.state.params, 3)
    assert trace3.shape[0] == padded3
    saved = np.asarray(trace3)

    holder2 = {}
    tr2 = Trainer(
        cfg,
        bundle=bundle,
        timing_model=_factored_timing(holder2, [1.0, 1.0, 1.0, 1.0]),
        log_to_file=False,
    )
    holder2["tr"] = tr2
    start = tr2._maybe_restore()
    assert start == 3  # resumes past the final saved epoch
    assert tr2.world_size == 3 and tr2.active_ranks == [0, 1, 2]
    (trace_r,) = _chunk_leaves(tr2.state)
    # sharding re-placement: 1/3 per surviving device, values intact
    assert trace_r.shape[0] == padded3
    shards = trace_r.addressable_shards
    assert len(shards) == 3
    assert all(s.data.shape[0] == padded3 // 3 for s in shards)
    np.testing.assert_allclose(np.asarray(trace_r), saved, rtol=1e-6)
    flush_checkpoints(close=True)


@pytest.mark.slow
def test_zero1_lm_engine(tmp_path):
    """The LM engine rides the same conversion and combine twins (the DBS
    composition on the sequence workload)."""
    from tests.conftest import make_tiny_corpus

    from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

    corpus = make_tiny_corpus(tmp_path / "corpus")
    cfg = Config(
        debug=True, world_size=8, batch_size=32, learning_rate=0.5,
        epoch_size=2, dataset="wikitext2", model="transformer",
        dynamic_batch_size=True, seed=3, bucket=4, shard_update=True,
        packed="off", device_cache="off",
    )
    tr = LMTrainer(cfg, bundle=corpus, log_to_file=False)
    assert tr._combine_names() == ("combine_update_zero1", "combine_probe_zero1")
    rec = tr.run()
    assert np.isfinite(rec.data["train_loss"]).all()
    assert rec.data["train_loss"][-1] < rec.data["train_loss"][0]
    assert _chunk_leaves(tr.state)  # transformer opt state really chunked


# ----------------------------------------------------------------- sentinel


def test_zero_foreground_compiles_zero1_fused(bundle):
    """Composed-path sentinel: a warm-started fused zero-1 run compiles
    zero steady-state foreground programs, and the update spec is part of
    every registry key."""
    cfg = _cfg(epoch_size=4, warm_start=True, aot_warm=True,
               one_cycle_policy=False)
    tr = Trainer(cfg, bundle=bundle, log_to_file=False)
    rec = tr.run()
    fused_keys = [
        k
        for k in tr._aot.keys()
        if k[0] in ("fused_epoch", "fused_epoch_idx")
    ]
    assert fused_keys and all("zero1" in k for k in fused_keys), fused_keys
    compiles = rec.data["xla_compiles"]
    assert sum(compiles[2:]) == 0, compiles


def test_zero_foreground_compiles_zero1_across_reshard(bundle):
    """The sentinel holds ACROSS an elastic reshard: after the recovery
    re-warm, steady-state epochs report zero foreground compiles and the
    new generation's combine keys carry the zero-1 update spec."""
    from dynamic_load_balance_distributeddnn_tpu.faults import (
        PreemptionEvent,
        PreemptionInjector,
    )

    holder = {}
    inj = PreemptionInjector(
        4, [PreemptionEvent(worker=3, down_at=1.4, rejoin_epoch=None)]
    )
    tr = Trainer(
        _elastic_cfg(epoch_size=6, warm_start=True, aot_warm=True),
        bundle=bundle,
        injector=inj,
        timing_model=_factored_timing(holder, [1.0, 1.0, 1.0, 1.0]),
        log_to_file=False,
    )
    holder["tr"] = tr
    rec = tr.run()
    assert 3.0 in rec.data["workers_alive"]
    combine_keys = [
        k for k in tr._aot.keys() if str(k[0]).startswith("combine_")
    ]
    assert combine_keys and all("zero1" in k for k in combine_keys)
    # the recovery epoch re-runs with a fresh generation (compiles expected,
    # drained pre-wall by the AOT re-warm); epochs after the next boundary
    # are steady state again
    rec_ep = tr.recorder.meta["elastic_events"][0]["epoch"]
    compiles = rec.data["xla_compiles"]
    assert sum(compiles[rec_ep + 2:]) == 0, (rec_ep, compiles)


def test_sharded_update_int8_wire_unbiased_close():
    """The quantized reduce-scatter (flat compress_grads composition) stays
    an unbiased estimate: the sharded-update delta tracks the exact one
    within the wire's quantization band."""
    mesh = data_mesh()
    n = len(mesh.devices.flat)
    tx = TXS["sgd_momentum"]()
    params = _params()
    padded = zero1_padded_size(params, n)
    state = TrainState(
        params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32)
    )
    grads = [_int_grads(900 + d) for d in range(n)]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *grads)

    exact = _sharded_step(
        _zero1_lib(mesh, tx, padded),
        mesh,
        shard_optimizer_state(state, mesh, tx),
        stacked,
    )
    quant = _sharded_step(
        _zero1_lib(mesh, tx, padded, compress="int8"),
        mesh,
        shard_optimizer_state(state, mesh, tx),
        stacked,
    )
    ge = np.concatenate(
        [np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(exact.params)]
    )
    gq = np.concatenate(
        [np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(quant.params)]
    )
    # lr * n * scale bounds the per-element quantization error of the summed
    # chunk; the int8 wire's 127 levels keep it small relative to the update
    assert np.abs(ge - gq).max() < 0.05 * max(np.abs(ge).max(), 1e-9) + 1e-3
    assert not np.array_equal(ge, gq)  # the wire really engaged
