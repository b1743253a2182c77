"""Multi-host worker: one process of a multi-process × 2-virtual-device run.

Launched by tests/test_multihost.py as
``python _mh_worker.py <proc_id> <num_procs> <port>``. Three modes:

* default — the PR-2 era integration run: trains MnistNet with ws=4 workers
  split across the processes (elastic DBS path with a deterministic 3:1
  timing model, plus one fused dbs-off epoch over the global mesh) and
  prints one RESULT JSON line for the parent to cross-check.
* ``DBS_MH_RDZV=1`` — the ISSUE-14 elasticity harness: the world comes up
  through ``rendezvous.elastic_initialize`` (survivable coordination
  service), trains an elastic DBS run with per-epoch checkpoints and
  epoch-start marker files, and SURVIVES a peer-process SIGKILL by
  re-rendezvousing over the survivors. ``DBS_MH_WEDGE=<id>`` wedges that
  process's rendezvous (beacon alive, agree() stalls) to drive the
  timeout-degrade path; ``DBS_MH_RESPAWNED=1`` marks a respawned joiner,
  which offers a rendezvous join and enters the grown world.
* ``DBS_MH_PARITY=1`` — the bitwise-parity reference leg: a fresh
  SINGLE-process run at the reduced world size, restored from the same
  checkpoint directory, controller vectors seeded from
  ``DBS_MH_PARITY_VECS`` (the survivor-restricted sidecar), driven over the
  same remaining epochs.
"""

import json
import os
import sys
import traceback

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax

if os.environ.get("DBS_MH_PARITY") != "1":
    # gloo needs a live distributed client; the parity leg is single-process
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _params_hash(state) -> str:
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(state.params)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _factored_timing(holder, base_factors):
    """Deterministic per-ORIGINAL-worker timing model that follows the
    active fleet (same shape as tests/test_elastic.py)."""
    import numpy as np

    def tm(plan):
        tr = holder["tr"]
        f = np.asarray(base_factors, dtype=np.float64)[
            np.asarray(tr.active_ranks)
        ]
        return f * np.array(
            [w.batch_size * w.steps * 1e-3 for w in plan.workers]
        )

    return tm


def _elastic_cfg(ws: int, num_procs: int, epochs: int, ck: str):
    from dynamic_load_balance_distributeddnn_tpu.config import Config

    return Config(
        debug=True,
        world_size=ws,
        batch_size=128,
        learning_rate=0.05,
        epoch_size=epochs,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        bucket=8,
        stream_chunk_steps=2,
        elastic="on",
        ckpt_dir=ck,
        seed=7,
        # one worker per process pins everyone to local ordinal 0 (the
        # symmetric-map requirement); the 2x2 layout round-robins
        device=0 if ws == num_procs else None,
    )


def main_rdzv(proc_id: int, num_procs: int, port: int) -> None:
    """ISSUE-14 mode: elastic multi-host run that survives a peer SIGKILL
    via epoch-boundary re-rendezvous."""
    from dynamic_load_balance_distributeddnn_tpu.runtime import (
        rendezvous as rdzv,
    )

    hb_dir = os.environ["DBS_PEER_HB_DIR"]
    ck = os.environ["DBS_MH_CKPT"]
    epochs = int(os.environ.get("DBS_MH_EPOCHS", "4"))
    ws = int(os.environ.get("DBS_MH_WS", "4"))

    if os.environ.get("DBS_MH_WEDGE") == str(proc_id):
        # test seam for the timeout-degrade path: this peer stays ALIVE
        # (its beacon keeps beating) but never reaches the rendezvous — the
        # "wedged elsewhere" failure the per-phase timeout exists for. The
        # wedge lives in the harness, not the shipped state machine.
        import time as _time

        def _stall(self, *a, **k):
            while True:
                _time.sleep(0.5)

        rdzv.RendezvousStateMachine.agree = _stall

    if os.environ.get("DBS_MH_RESPAWNED") == "1":
        # a respawned process: join the RUNNING fleet at the survivors'
        # next epoch boundary, then build the engine over the grown world
        ident = int(os.environ["DBS_MH_IDENT"])
        from dynamic_load_balance_distributeddnn_tpu.runtime.health import (
            ProcessHeartbeat,
        )

        hb = ProcessHeartbeat(
            period_s=float(os.environ.get("DBS_PEER_HB_PERIOD_S", "0.2"))
        )
        hb.beacon(hb_dir, f"proc{ident}")
        sm, ag, payload = rdzv.join_elastic_world(hb_dir, ident)
        hb.stop()  # the Trainer arms its own beacon on the same file
        print(
            f"JOINED gen={ag.gen} rank={ag.rank} roster={list(ag.roster)} "
            f"payload={json.dumps(payload)}",
            flush=True,
        )
    else:
        rdzv.elastic_initialize(
            f"localhost:{port}", num_procs, proc_id, rdzv_dir=hb_dir
        )

    import numpy as np

    from dynamic_load_balance_distributeddnn_tpu.data.datasets import (
        synthetic_dataset,
    )
    from dynamic_load_balance_distributeddnn_tpu.train import Trainer
    from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
        flush_checkpoints,
    )

    bundle = synthetic_dataset("mnist", n_train=512, n_test=128)
    cfg = _elastic_cfg(ws, num_procs, epochs, ck)
    if os.environ.get("DBS_MH_TRACE_SPOOL"):
        # flight-recorder chaos mode (ISSUE 15): ring-trace + crash-durable
        # spool, fast flush so the SIGKILL window is tight
        cfg = cfg.replace(
            trace="ring",
            trace_spool=os.environ["DBS_MH_TRACE_SPOOL"],
            trace_spool_flush_s=0.05,
            trace_dir=os.path.join(os.environ["DBS_MH_TRACE_SPOOL"], "traces"),
        )
    holder = {}
    factors = ([3.0, 1.0, 1.0, 1.0] * 2)[:ws]
    tr = Trainer(
        cfg,
        bundle=bundle,
        timing_model=_factored_timing(holder, factors),
        log_to_file=False,
    )
    holder["tr"] = tr
    start = tr._maybe_restore()
    # harness knob: stretch each epoch's tail so a respawned joiner (which
    # pays a full interpreter + jax import before it can offer its join)
    # still finds a boundary to be admitted at — CPU-tier epochs are ~1s
    # while real epochs are minutes
    epoch_sleep = float(os.environ.get("DBS_MH_EPOCH_SLEEP_S", "0"))
    for e in range(start, epochs):
        with open(
            os.path.join(hb_dir, f"epoch{e}_p{tr._orig_proc_id}.marker"), "w"
        ) as f:
            f.write("started")
        tr._run_epoch_elastic_world(e)
        tr._save_checkpoint(e)
        if epoch_sleep:
            import time as _time

            _time.sleep(epoch_sleep)
    flush_checkpoints(cfg.ckpt_dir, close=True)
    # survivors drain their spool cleanly (victims are SIGKILLed — the
    # background flusher already persisted all but the last interval)
    tr.close_spool()
    rec = tr.recorder
    out = {
        "proc": proc_id,
        "ident": tr._orig_proc_id,
        "world_size": tr.world_size,
        "n_proc": tr.n_proc,
        "roster": list(tr._proc_roster),
        "losses": [float(v) for v in rec.data["train_loss"]],
        "params_hash": _params_hash(tr.state),
        "elastic_events": rec.meta.get("elastic_events", []),
        "xla_compiles": [int(v) for v in rec.data.get("xla_compiles", [])],
        "shares": [float(s) for s in tr.shares],
        "node_times": [float(t) for t in tr.node_times],
        "grad_comm": tr.grad_comm,
        "retired_runtimes": rdzv.retired_count(),
    }
    print("RESULT " + json.dumps(out), flush=True)
    sys.stdout.flush()
    if tr._rdzv is not None:
        tr._rdzv.finalize(timeout_s=30)
    # skip interpreter teardown: the coordination client's atexit shutdown
    # barrier would wait on peers that may already be gone (see
    # runtime/rendezvous.py — results are flushed above)
    os._exit(0)


def main_parity() -> None:
    """Bitwise-parity reference: a FRESH single-process run at the reduced
    world size from the same checkpoint + survivor-restricted vectors."""
    import numpy as np

    from dynamic_load_balance_distributeddnn_tpu.data.datasets import (
        synthetic_dataset,
    )
    from dynamic_load_balance_distributeddnn_tpu.train import Trainer
    from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
        flush_checkpoints,
    )

    ck = os.environ["DBS_MH_CKPT"]
    epochs = int(os.environ.get("DBS_MH_EPOCHS", "4"))
    vecs = json.loads(os.environ["DBS_MH_PARITY_VECS"])
    ws = len(vecs["shares"])
    bundle = synthetic_dataset("mnist", n_train=512, n_test=128)
    cfg = _elastic_cfg(ws, 1, epochs, ck).replace(elastic="off")
    holder = {}
    tr = Trainer(
        cfg,
        bundle=bundle,
        timing_model=_factored_timing(holder, [3.0, 1.0, 1.0, 1.0][:ws]),
        log_to_file=False,
    )
    holder["tr"] = tr
    start = tr._maybe_restore()
    tr.shares = np.asarray(vecs["shares"], dtype=np.float64)
    tr.node_times = np.asarray(vecs["node_times"], dtype=np.float64)
    for e in range(start, epochs):
        tr.run_epoch(e)
    out = {
        "proc": -1,
        "start_epoch": start,
        "losses": [float(v) for v in tr.recorder.data["train_loss"]],
        "params_hash": _params_hash(tr.state),
    }
    print("RESULT " + json.dumps(out), flush=True)
    flush_checkpoints(close=True)


def main() -> None:
    proc_id, num_procs, port = (
        int(sys.argv[1]),
        int(sys.argv[2]),
        int(sys.argv[3]),
    )
    if os.environ.get("DBS_MH_PARITY") == "1":
        return main_parity()
    if os.environ.get("DBS_MH_RDZV") == "1":
        return main_rdzv(proc_id, num_procs, port)

    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=num_procs,
        process_id=proc_id,
    )
    import numpy as np

    from dynamic_load_balance_distributeddnn_tpu.config import Config
    from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
    from dynamic_load_balance_distributeddnn_tpu.train import Trainer

    bundle = synthetic_dataset("mnist", n_train=512, n_test=128)

    # --- elastic path: dbs on, worker 0 modeled 3x slower ------------------
    cfg = Config(
        debug=True,
        world_size=4,
        batch_size=128,
        learning_rate=0.05,
        epoch_size=3,
        dataset="mnist",
        model="mnistnet",
        dynamic_batch_size=True,
        bucket=8,
        # small window so the streaming host path (prefetch + per-window
        # make_array_from_process_local_data) is exercised ACROSS processes
        stream_chunk_steps=2,
        # elastic harness mode (ISSUE 6): arm the per-process heartbeat
        # beacon + peer watcher under DBS_PEER_HB_DIR so the parent can
        # preempt a REAL worker process and assert the survivor detects it
        elastic="on" if os.environ.get("DBS_MH_ELASTIC") == "1" else "off",
    )

    factors = np.array([3.0, 1.0, 1.0, 1.0])

    def timing_model(plan):
        return factors * np.array([w.batch_size * w.steps for w in plan.workers])

    tr = Trainer(cfg, bundle=bundle, timing_model=timing_model, log_to_file=False)
    rec = tr.run()
    shares = np.asarray(tr.shares)
    losses = [float(e) for e in rec.data["train_loss"]]

    # --- fused path: dbs off, uniform plan, one worker per device ----------
    cfg2 = cfg.replace(dynamic_batch_size=False, epoch_size=1)
    tr2 = Trainer(cfg2, bundle=bundle, log_to_file=False)
    out2 = tr2.run_epoch(0)

    print(
        "RESULT "
        + json.dumps(
            {
                "proc": proc_id,
                "shares": [round(float(s), 6) for s in shares],
                "losses": [round(fl, 6) for fl in losses],
                "fused_loss": round(float(out2["loss"]), 6),
                "node_times": [round(float(t), 6) for t in tr.node_times],
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    try:
        main()
    except BaseException:
        # deterministic nonzero exit WITHOUT interpreter teardown: the
        # coordination client's atexit shutdown barrier would wait on peers
        # that are exactly the reason we are failing (kill/wedge tests)
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(17)
