"""The training engine: the DBS feedback loop.

The reference's per-worker epoch loop (dbs.py:313-446) becomes one controller
driving all logical workers:

    for epoch:
        adjust LR (one-cycle)                        dbs.py:386-387
        shares <- solver(node_times, shares)         dbs.py:388-391
        plan   <- partition dataset + batch sizes    dbs.py:394-395
        train one epoch (elastic or fused path)      dbs.py:408-413
        validate                                     dbs.py:417-421
        node_times <- per-worker compute times       dbs.py:423-426
        record the 9 metric series                   dbs.py:428-438

Per-worker compute time on an async SPMD runtime cannot be a naive
``time.time()`` around a dispatched call (SURVEY §5.1), so the engine times a
*probe*: one standalone execution of each worker's step (blocking, after
warm-up), scaled by the worker's step count. Probes inherently include
compute-mode injected load; virtual-mode injection is added to the vector
afterwards. Communication (combine+update) is probed separately and never
enters the solver's time vector — the reference's compute/comm split contract
(dbs.py:250, 297-299).
"""

from __future__ import annotations

import concurrent.futures
import os
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dynamic_load_balance_distributeddnn_tpu.analysis.guards import (
    CompileTracker,
    compile_count,
    compile_seconds,
)
from dynamic_load_balance_distributeddnn_tpu.balance import (
    HostOverheadMeter,
    TimeKeeper,
    exchange_times,
    initial_partition,
    integer_batch_split,
    rebalance,
)
from dynamic_load_balance_distributeddnn_tpu.balance.controller import (
    OnlineRebalanceController,
    step_time,
)
from dynamic_load_balance_distributeddnn_tpu.balance.solver import (
    ShareTrajectoryPredictor,
    equilibrium_shares,
    quantize_batches,
)
from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data import (
    DatasetBundle,
    build_epoch_plan,
    build_remainder_plan,
    load_dataset,
)
from dynamic_load_balance_distributeddnn_tpu.faults import (
    EpochFaults,
    FaultContext,
    FaultInjector,
    LuckyFaultInjector,
    NullInjector,
    ScheduledStragglerInjector,
    StaticStragglerInjector,
)
from dynamic_load_balance_distributeddnn_tpu.models import build_model
from dynamic_load_balance_distributeddnn_tpu.obs import (
    MetricsRecorder,
    MetricsRegistry,
    init_logger,
)
from dynamic_load_balance_distributeddnn_tpu.obs.trace import EPOCH_CAT, get_tracer
from dynamic_load_balance_distributeddnn_tpu.ops.faultload import calibrate_iter_cost
from dynamic_load_balance_distributeddnn_tpu.ops.losses import example_weights
from dynamic_load_balance_distributeddnn_tpu.parallel import WorkerTopology, data_mesh
from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import replicated_sharding
from dynamic_load_balance_distributeddnn_tpu.runtime.compiler import AOTCompileService
from dynamic_load_balance_distributeddnn_tpu.runtime.health import (
    WorkerHealth,
    WorkerLost,
    retry_transient,
)
from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import heartbeat
from dynamic_load_balance_distributeddnn_tpu.train.schedule import one_cycle_lr
from dynamic_load_balance_distributeddnn_tpu.train.state import create_state, make_optimizer
from dynamic_load_balance_distributeddnn_tpu.train.pipeline import (
    WindowTransferPipeline,
)
from dynamic_load_balance_distributeddnn_tpu.train.steps import (
    StepLibrary,
    shard_views,
    stack_partials,
)

# Dispatch-overhead probe op, constructed ONCE per process: building it inside
# _probe_workers (the pre-fix form, kept as the canonical G001 fixture in
# tests/fixtures/graftlint/g001_violation.py) made every probe epoch pay a
# fresh wrapper + XLA compile for a no-op.
_tiny_sync_probe = jax.jit(lambda a: a + 1.0)


class Trainer:
    """Vision-model trainer (the Transformer-LM path lives in
    train/lm_engine.py and shares this controller's balance machinery)."""

    # Subclasses opt out of bucket snapping (the LM path's "batch" is a small
    # column count where bucket quantization would distort the balance).
    SNAP_BATCHES = True

    def __init__(
        self,
        cfg: Config,
        bundle: Optional[DatasetBundle] = None,
        injector: Optional[FaultInjector] = None,
        logger=None,
        log_to_file: bool = True,
        timing_model=None,
        job_id: Optional[str] = None,
    ):
        """``timing_model``: optional callable(plan) -> per-worker seconds,
        replacing wall-clock probes with a deterministic model — used by tests
        to verify the controller dynamics hermetically (wall-clock on tiny CPU
        batches is dispatch-overhead-dominated and not ∝ batch size).

        ``job_id``: tenant tag when this trainer is one stream of a
        :class:`~..runtime.scheduler.MultiStreamEngine` pool. Folded into
        ``_comm_sig`` so every AOT-registry key carries the tenant — two
        jobs with identical model/topology must never resolve each other's
        executables through any shared compile cache or artifact."""
        self.cfg = cfg
        self.timing_model = timing_model
        self.job_id = job_id
        self.logger = logger or init_logger(cfg, to_file=log_to_file)

        # graftscope tracer, configured FIRST (see the fuller note at the
        # MetricsRegistry construction below): instrumentation that runs
        # during init itself — the hier combine's link-bandwidth probe and
        # its comm_* phase spans — must land in THIS run's trace, not the
        # previous configuration's buffer (or the void). A TENANT trainer
        # (job_id set — one stream of a MultiStreamEngine) must NOT
        # reconfigure the process-wide tracer: configure() rebuilds the
        # event buffer and the thread-local job-tag slots, so a second
        # tenant's admission would drop every earlier tenant's spans and
        # untag their worker threads. In many-stream mode the engine's
        # caller owns the tracer config; per-tenant trace flags are
        # ignored.
        if job_id is None:
            self._trace = get_tracer().configure(
                cfg.trace,
                ring_size=cfg.trace_ring,
                jax_annotations=cfg.trace_annotations,
                # where the scope map of every compiled program goes while
                # the run goes on (obs/scopes.py)
                trace_dir=cfg.trace_dir,
            )
        else:
            self._trace = get_tracer()
        # the jax.monitoring listener that turns JAX's own tracing, lowering,
        # cache reads and compiles into spans: installed before the first
        # program of this trainer is traced
        compile_count()
        with self._trace.span("trainer_init", cat="setup"):
            self._setup(bundle, injector)

    def _setup(self, bundle: Optional[DatasetBundle], injector: Optional[FaultInjector]) -> None:
        """Everything of construction that is timed: graftscope's
        ``trainer_init`` span, with ``setup_model`` (all but a millisecond of it
        on the chip, PERF.md PR 24) under it."""
        cfg = self.cfg
        # Multi-host: each process owns a contiguous slice of the global
        # workers, mapped onto its LOCAL devices; the combine mesh spans every
        # process's used devices (XLA collectives ride ICI within a host, DCN
        # across — the reference's gloo ring analogue, SURVEY §5.8). All
        # processes replicate the plan/solver deterministically, so the only
        # cross-host traffic is gradients (in-step psum) and the per-epoch
        # time vector (process_allgather in balance/timing.py).
        self.n_proc = jax.process_count()
        self.proc_id = jax.process_index()
        if cfg.world_size % self.n_proc != 0:
            raise ValueError(
                f"world_size {cfg.world_size} must divide evenly across "
                f"{self.n_proc} processes"
            )
        self.ws_local = cfg.world_size // self.n_proc
        self.rank_lo = self.proc_id * self.ws_local

        # flight recorder (ISSUE 15): stream the tracer's events into a
        # crash-durable per-process spool so a SIGKILL'd or wedged process
        # leaves its timeline behind (at most the last flush interval is
        # lost). Attached HERE — immediately after the process identity is
        # known and before any instrumented init work (hier bandwidth
        # probe, AOT warm) — so even a process that dies during bring-up
        # spools its evidence. File name carries the logical ident AND the
        # pid: a respawned joiner shares the ident with its dead
        # predecessor but must never interleave frames into its file.
        self._spool_writer = None
        if cfg.trace != "off" and cfg.trace_spool:
            from dynamic_load_balance_distributeddnn_tpu.obs.spool import (
                SpoolWriter,
            )

            ident0 = int(os.environ.get("DBS_MH_IDENT", self.proc_id))
            spool_path = os.path.join(
                cfg.trace_spool, f"proc{ident0}.{os.getpid()}.spool"
            )
            self._spool_writer = SpoolWriter(
                spool_path,
                ident=ident0,
                flush_interval_s=cfg.trace_spool_flush_s,
                fsync=cfg.trace_spool_fsync,
            )
            self._trace.attach_spool(self._spool_writer)
            self.logger.info(
                f"flight recorder: trace spooling to {spool_path} "
                f"(flush every {cfg.trace_spool_flush_s}s"
                + (", fsync" if cfg.trace_spool_fsync else "")
                + ")"
            )
            # drain on GC even when run() never completes — without
            # capturing self (weakref.finalize must not pin the trainer)
            import weakref

            weakref.finalize(self, self._spool_writer.close)

        local_devices = sorted(jax.local_devices(), key=lambda d: d.id)
        ids_global = cfg.worker_device_ids(len(local_devices))
        ids_local = ids_global[self.rank_lo : self.rank_lo + self.ws_local]
        used = sorted(set(ids_local))
        if self.n_proc > 1:
            # Every process must use the same local device ordinals, or the
            # global meshes (built per-process below) would disagree and the
            # collectives would hang. Validate instead of assuming.
            for p in range(self.n_proc):
                slice_p = ids_global[p * self.ws_local : (p + 1) * self.ws_local]
                if sorted(set(slice_p)) != used:
                    raise ValueError(
                        "multi-host topology must be symmetric: every process "
                        f"must map its workers onto the same local device "
                        f"ordinals (process 0 uses {used}, process {p} would "
                        f"use {sorted(set(slice_p))}); adjust the device map"
                    )
        self.topology = WorkerTopology.build(
            self.ws_local,
            [local_devices[i] for i in used],
            [used.index(i) for i in ids_local],
        )
        if self.n_proc == 1:
            mesh_devices = list(self.topology.devices)
        else:
            # Symmetric hosts: every process contributes the same local device
            # ordinals, ordered by process index then device id.
            by_proc: Dict[int, list] = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, []).append(d)
            mesh_devices = []
            for p in sorted(by_proc):
                proc_devs = sorted(by_proc[p], key=lambda d: d.id)
                mesh_devices.extend(proc_devs[i] for i in used)
        # Tree gradient combine (ISSUE 12, N-level since ISSUE 17): resolve
        # --grad_comm hier into an N-level topology mesh when the device
        # list factors into a TopologyTree — declared (--hier_levels),
        # derived from the real process topology / synthetic --hier_hosts
        # split, or probe-learned. self.grad_comm is the RUNTIME choice —
        # "flat" whenever no factorization exists or the bandwidth probe
        # says the fabric gains nothing — and everything downstream
        # (StepLibrary axes, combine dispatch, AOT keys, bytes-on-wire
        # accounting) keys off it, never off cfg.grad_comm.
        self.grad_comm = "flat"
        self._hier_hosts = 0
        self._topo_tree = None
        self._grad_comm_wires: tuple = ()
        self._link_bw: Optional[Dict] = None
        # bandwidth-probe verdict memo: a reshard's tree re-derivation must
        # not re-enable a structure the probe measured as a loss here
        self._probe_gated_flat = False
        if cfg.grad_comm == "hier":
            tree, learn = self._resolve_topology_tree(mesh_devices)
            if tree is None:
                self.logger.warning(
                    "grad_comm=hier: no topology-tree factorization of "
                    f"{len(mesh_devices)} devices "
                    f"(hier_levels={cfg.hier_levels!r}, "
                    f"hier_hosts={cfg.hier_hosts}, processes={self.n_proc})"
                    " — falling back to the flat combine"
                )
            else:
                self.grad_comm = "hier"
                self._topo_tree = tree
                self._hier_hosts = tree.sizes[0]
        if self.grad_comm == "hier":
            from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
                probe_link_bandwidth,
                tree_mesh,
            )

            self.mesh = tree_mesh(
                mesh_devices, self._topo_tree.names, self._topo_tree.sizes
            )
            # The bandwidth probe always runs on a SINGLE-PROCESS tree
            # mesh — its per-phase spans and per-level bytes/s are the
            # run's comm observability, the input of the learned-tree
            # merge and the per-hop codec choice — but it only GATES
            # (falls back to flat) when the operator opted in: forced hier
            # on a deliberately synthetic split (tests) must
            # stay hier. Multi-host runs skip it entirely: the probe
            # device_puts host-local arrays onto the global mesh
            # (non-addressable from any one process), and a per-process
            # wall-clock verdict could DIVERGE across hosts — half the
            # fleet on a tree mesh, half flat, deadlocked at the first
            # collective. Real pods trust --grad_comm until the probe
            # learns a replicated decision channel (ROADMAP).
            if self.n_proc == 1:
                self._link_bw = probe_link_bandwidth(
                    self.mesh, gate_ratio=cfg.dcn_probe_gate
                )
                heartbeat()
                if learn:
                    self._learn_tree_from_probe(mesh_devices)
            elif cfg.dcn_bandwidth_probe or learn:
                self.logger.warning(
                    "the bandwidth probe is single-process-only today — "
                    "keeping grad_comm=hier as configured"
                )
            if (
                cfg.dcn_bandwidth_probe
                and self.grad_comm == "hier"
                and self._link_bw is not None
                and not self._link_bw["hier_wins"]
            ):
                self.logger.warning(
                    "grad_comm=hier: bandwidth probe measured the tree "
                    "structure at "
                    f"{self._link_bw['hier_wall_s']:.4f}s vs "
                    f"{self._link_bw['flat_wall_s']:.4f}s for one flat "
                    f"psum (ratio {self._link_bw['wall_ratio']:.3f}, gate "
                    f"{cfg.dcn_probe_gate}) — falling back to the flat "
                    "combine"
                )
                self.grad_comm = "flat"
                self._hier_hosts = 0
                self._topo_tree = None
                self._probe_gated_flat = True
                self.mesh = data_mesh(mesh_devices)
        if self.grad_comm != "hier" and getattr(self, "mesh", None) is None:
            self.mesh = data_mesh(mesh_devices)
        self.n_dev = len(mesh_devices)
        self._grad_comm_wires = self._resolve_wires()
        # AOT-key / plan-layout signature of the combine structure: a new
        # axis factorization or wire format is a new compiled-program
        # universe, so it participates in every registry key the combine
        # and fused executables are filed under. Since PR 13 the UPDATE
        # SPEC (sharded vs replicated optimizer) is part of the same
        # signature — a zero-1 program and a replicated one lower from
        # different state specs and must never resolve to each other.
        self._comm_sig = self._compute_comm_sig()

        self._setup_data(bundle)
        with self._trace.span("setup_model", cat="setup"):
            self._setup_model()

        # Async AOT compile service (runtime/compiler.py): warm-start and
        # speculative compiles run as jit(...).lower(abstract).compile() jobs
        # on a thread pool — no dummy execution, no device_put traffic — and
        # the elastic hot loop dispatches the compiled executables directly
        # (the lazy jit wrappers stay as fallback). aot_warm=False keeps the
        # legacy execute-to-compile warm loop as the A/B reference.
        self._aot: Optional[AOTCompileService] = None
        self._build_aot_service()
        self._aot_view_specs: Dict[int, object] = {}
        self._aot_dummy_template: list = []
        # world generation: bumped on every elastic re-shard and mixed into
        # every AOT registry key — device indices and mesh programs are only
        # meaningful within one fleet generation, and a stale executable
        # resolving across a re-shard dispatches onto devices that left the
        # fleet (sharding-mismatch crash at best, wrong-device work at worst)
        self._aot_gen = 0
        self._aot_failed_logged: set = set()
        self._aot_warm_t0: Optional[float] = None
        self._aot_compiled_last = 0.0
        self._compile_s_last = compile_seconds()

        if injector is not None:
            self.injector = injector
        elif cfg.straggler and cfg.fault_schedule != "none":
            # time-varying profile (ISSUE 11): the factors follow a sin/ramp
            # schedule within epochs — the scenario window-cadence
            # rebalancing exists for (epoch_faults still exposes the
            # epoch-MEAN view, so epoch-cadence runs stay well-defined)
            self.injector = ScheduledStragglerInjector(
                cfg.straggler_factors(),
                mode=cfg.fault_mode,
                schedule=cfg.fault_schedule,
                period=cfg.fault_period,
                seed=cfg.seed,
            )
        elif cfg.straggler:
            self.injector = StaticStragglerInjector(
                cfg.straggler_factors(), mode=cfg.fault_mode
            )
        elif cfg.fault_tolerance:
            self.injector = LuckyFaultInjector(
                cfg.world_size,
                cfg.fault_tolerance_chance,
                mode=cfg.fault_mode,
                seed=cfg.seed,
                logger=self.logger,
            )
        else:
            self.injector = NullInjector(cfg.world_size)
        self._needs_iter_cost = cfg.fault_mode == "compute" and not isinstance(
            self.injector, NullInjector
        )

        # Elastic world size (ISSUE 6): the ACTIVE fleet. ``world_size`` is
        # the engine's RUNTIME world size — equal to cfg.world_size until a
        # confirmed worker loss shrinks it (readmission grows it back);
        # every runtime surface (solver vectors, plan build, capacity caps,
        # probe loops, rng splits) derives from it. ``active_ranks`` maps
        # compact runtime ranks -> ORIGINAL config ranks: injectors and
        # health verdicts speak original ranks, plans/topology/shares are
        # compact over the survivors.
        self.world_size = cfg.world_size
        self.active_ranks = list(range(cfg.world_size))
        self.health = WorkerHealth(
            cfg.world_size,
            detect_misses=cfg.elastic_detect_misses,
            latency_factor=cfg.elastic_latency_factor,
            logger=self.logger,
        )
        self._recoveries = 0
        self._elastic_events: list = []
        self._epoch_snap: Optional[dict] = None
        self._detect_t0: Optional[float] = None
        # epoch-time each worker's loss was CONFIRMED at: recovery re-runs
        # the epoch, so liveness rounds re-visit schedule times BEFORE the
        # loss — a "not down" verdict there is the past, not a recovery
        self._lost_t: Dict[int, float] = {}
        self._hb_beacon = None
        self._hb_beacon_path: Optional[str] = None
        # Multi-host elasticity (ISSUE 14): the rendezvous state machine
        # (armed with the peer beacon when DBS_PEER_HB_DIR is set) and the
        # fleet's ORIGINAL process identities. ``proc_id``/``n_proc`` are
        # the LIVE world's compact values and change across a re-rendezvous;
        # ``_orig_proc_id``/``_proc_roster``/``_n_proc0`` speak the original
        # ident space the heartbeat files, worker-rank ownership and the
        # rendezvous protocol are keyed by. A respawned joiner carries its
        # original ident in DBS_MH_IDENT (its live process index is whatever
        # rank the grow rendezvous assigned).
        self._rdzv = None
        self._n_proc0 = self.n_proc
        self._orig_proc_id = int(os.environ.get("DBS_MH_IDENT", self.proc_id))
        self._proc_roster = list(range(self.n_proc))
        self._peer_scan_cache = None
        if cfg.elastic == "on" and self.n_proc > 1:
            self._arm_peer_heartbeats()

        # XLA-recompile sentinel (analysis/guards.py): drained every epoch.
        # A compile on a plan layout seen before means a shape fell off the
        # bucket ladder or a jit wrapper was rebuilt inside a timed epoch —
        # invisible in the wall on a fast chip, poison for the DBS signal.
        # (First-visit compiles of a fresh layout are expected lazy work when
        # warm_start is off.)
        self._compile_tracker = CompileTracker()
        self._seen_plan_layouts: set = set()

        self.recorder = MetricsRecorder()
        self.recorder.stamp_data_source(
            self.bundle if self.bundle is not None else getattr(self, "corpus", None)
        )
        # Wall-definition provenance (ADVICE r4): since round 4, epoch walls
        # (and examples_per_s/MFU derived from them) EXCLUDE standalone probe
        # steps on every path; pre-round-4 artifacts include them. Stamped so
        # cross-round comparisons can detect the definition boundary instead
        # of silently mixing the two.
        self.recorder.meta["wall_excludes_probes"] = True
        # combine-structure provenance: which collective this run's walls
        # were measured under (and what the bandwidth probe saw, if it ran)
        self.recorder.meta["grad_comm"] = self.grad_comm
        if self.grad_comm == "hier":
            self.recorder.meta["grad_comm_wire"] = cfg.grad_comm_wire
            self.recorder.meta["grad_comm_hosts"] = self._hier_hosts
            self.recorder.meta["grad_comm_levels"] = [
                [n, int(s)] for n, s in self._topo_tree.levels
            ]
            self.recorder.meta["grad_comm_wires"] = list(self._grad_comm_wires)
        if self._link_bw is not None:
            self.recorder.meta["link_bandwidth"] = {
                k: v for k, v in self._link_bw.items()
            }
        # induced-straggler provenance: lets offline tooling compute the
        # ideal equilibrium partition (share_i ∝ 1/f_i) and report the
        # balancer-quality convergence metric (BASELINE.md §protocol)
        if cfg.straggler:
            self.recorder.meta["straggler_factors"] = [
                float(f) for f in cfg.straggler_factors()
            ]
            self.recorder.meta["fault_mode"] = cfg.fault_mode
        self.shares = initial_partition(cfg.world_size)
        self.node_times = np.ones(cfg.world_size, dtype=np.float64)
        self.per_example_cost = np.full(cfg.world_size, np.nan)
        # In-step cost of one synthetic-load iteration: seeded from the
        # standalone calibration, then closed-loop-corrected from realized
        # probe deltas (per-process — hosts may genuinely differ).
        self._iter_cost_s: Optional[float] = None
        self._iter_cost_calibrated = False
        self.timekeeper = TimeKeeper(cfg.world_size)
        self.total_wallclock = 0.0
        self.total_probe_s = 0.0  # probe/instrumentation wall, kept OUT of
        #                           epoch walls (see run_epoch) but reported
        # Fused-path sync-time meter: seconds of collective cost per step,
        # measured once per run (shapes are constant on the fused path).
        self._fused_sync_per_step: Optional[float] = None
        # FLOP accounting (obs/flops.py): per-padded-example step FLOPs from
        # XLA's cost model, measured once per run; per-epoch totals derive
        # from each epoch's plan. None when the backend exposes no cost model.
        self._flops_per_padded_example: Optional[float] = None
        self._epoch_flops: Optional[float] = None
        self._warmed = False
        self._probes_ran = False  # replicated across processes by construction
        # Adaptive probe scheduler (config.probe_mode): once the per-example
        # cost model is anchored by real probes, epochs skip the probe steps
        # entirely and the solver is fed MODELED times; these fields track the
        # re-probe schedule and the wall-deviation trigger.
        self._probe_this_epoch = True
        self._next_probe_epoch = 0
        self._probe_sig: Optional[tuple] = None
        self._probe_episode: Optional[tuple] = None
        self._probe_wall_ref: Optional[float] = None
        self._slow_streak = 0
        self._sync_per_step = 0.0  # last probed elastic sync cost, reused on skips
        # Device-resident data cache (config.device_cache): train arrays live
        # in HBM and epochs are fed by index (on-device gather), so the
        # per-epoch reshard uploads [steps, batch] int32 instead of the
        # dataset. Lazily materialized per path (replicated for the fused
        # scan; one copy per used device for the elastic executables).
        self._use_device_cache = self._decide_device_cache()
        self._cache_repl = None
        self._cache_dev: Dict[int, tuple] = {}
        # Elastic-superstep bookkeeping: host-overhead meter (dispatch vs put
        # walls, reset per epoch) and the (shape-tuple, window) keys the scan
        # mode has dispatched — the compile-once sentinel the CompileTracker
        # warning is cross-checked against (run_epoch).
        self._host_meter = HostOverheadMeter()
        self._superstep_keys: set = set()
        # a routed model's per-step arrivals, carried out of the scanned
        # superstep with the loss (_aux_rows) until the epoch records them
        self._routing_rows: List = []
        # Solver-trajectory predictor (balance/solver.py): one-step-ahead
        # share-vector prediction feeding scan-mode shape-TUPLE speculation
        # (config.speculate_scan) — tuples have no finite ±bucket adjacency,
        # but the NEXT tuple is a deterministic function of the next share
        # vector, which the solver's smooth trajectory makes predictable.
        self._share_predictor = ShareTrajectoryPredictor()
        # Online window-cadence rebalance controller (ISSUE 11,
        # balance/controller.py): lazily built per fleet generation by
        # _window_controller() when cfg.rebalance == "window"; its EMA rate
        # track and regret ledger persist across epochs, and speculation is
        # re-aimed at ITS candidate plans (the switched-to executables are
        # always AOT-warm — a switch never pays a foreground compile).
        self._rebalance_ctl: Optional[OnlineRebalanceController] = None
        self._rebalance_events: list = []
        self._switches_last = 0
        self._window_rebalance_logged = False
        self._fault_ctx: Optional[FaultContext] = None
        self._clean_compute_s: Optional[np.ndarray] = None
        self._clean_examples: Optional[np.ndarray] = None
        # graftscope (obs/trace.py + obs/registry.py): the process-wide span
        # tracer — configured here from the run config, shared by every
        # instrumented module (pipeline, AOT service, solver, watchdog) —
        # and the unified registry over this engine's observability
        # surfaces. trace="off" keeps every span call a single attribute
        # check (no buffer, no jax — sentinel-silent under the compile
        # guards); the trace saves at end of run (run()).
        # The engine OWNS the process-wide tracer config: configured
        # unconditionally (at the TOP of __init__, before the mesh/probe
        # block), so a trace="off" run can never inherit an earlier traced
        # run's enabled state (and its wall overhead + surprise trace file)
        # from the same process — bench arms, test suites and notebook
        # drivers all build engines back to back.
        self.obs = MetricsRegistry(recorder=self.recorder, tracer=self._trace)
        self.obs.attach(
            host_meter=self._host_meter,
            compile_tracker=self._compile_tracker,
            health=self.health,
        )
        if self._aot is not None:
            self.obs.attach(aot_service=self._aot)
        if cfg.packed == "on":
            # fail fast at init: the epoch dispatch prefers the fused paths,
            # so a forced-but-infeasible packed config would otherwise be
            # silently overridden (or only rejected mid-run)
            self._can_use_packed(None)
        if self._use_device_cache:
            mb = (self.bundle.train_x.nbytes + self.bundle.train_y.nbytes) / 1e6
            self.logger.info(
                f"device cache: train arrays HBM-resident ({mb:.1f} MB), "
                "epochs fed by index"
            )

    def _build_aot_service(self) -> None:
        """(Re)construct the AOT compile service. Re-run after a multi-host
        re-rendezvous: the old pool's registry and any mid-flight lowerings
        reference the RETIRED backend, so the recovery path closes the old
        service and builds a fresh one against the new world."""
        cfg = self.cfg
        self._aot = None
        if not cfg.aot_warm:
            return
        self._aot = AOTCompileService(
            workers=cfg.aot_pool,
            logger=self.logger,
            tick=heartbeat,
            backend=cfg.aot_backend,
            process_workers=cfg.aot_workers,
            # workers write their own graftscope trace files next to the
            # run trace; save_trace stitches them in (pid-tagged tracks)
            trace_dir=cfg.trace_dir if cfg.trace != "off" else None,
            release_caches=cfg.release_on_close,
        )
        if getattr(self, "steps", None) is not None:
            self.steps.aot_service = self._aot
        # tie the pool's lifetime to the trainer: processes that build
        # many engines (the test tier, bench retry/insurance loops) must
        # not accumulate idle non-daemon compile threads
        import weakref

        weakref.finalize(self, self._aot.close, False)

    def _decide_device_cache(self) -> bool:
        cfg = self.cfg
        if cfg.device_cache == "off":
            return False
        tx = getattr(self.bundle, "train_x", None) if self.bundle is not None else None
        ty = getattr(self.bundle, "train_y", None) if self.bundle is not None else None
        if tx is None or ty is None:
            # tokens path (LM folds its stream into windows host-side)
            if cfg.device_cache == "on":
                self.logger.warning("device_cache=on ignored: no cacheable train arrays")
            return False
        if cfg.device_cache == "on":
            return True
        return tx.nbytes + ty.nbytes <= cfg.device_cache_mb * 1_000_000

    def _device_cache_replicated(self):
        if self._cache_repl is None:
            arrays = (
                self.bundle.train_x,
                np.asarray(self.bundle.train_y, dtype=np.int32),
            )
            sh = replicated_sharding(self.mesh)
            if self.n_proc == 1:
                self._cache_repl = tuple(jax.device_put(a, sh) for a in arrays)
            else:
                # every process holds the identical bundle (same files/seed),
                # so its full array IS the addressable portion of the
                # replicated global array
                self._cache_repl = tuple(
                    jax.make_array_from_process_local_data(sh, a) for a in arrays
                )
        return self._cache_repl

    def _device_cache_for(self, d: int):
        if d not in self._cache_dev:
            dev = self.topology.devices[d]
            if self._cache_repl is not None:
                # the replicated copy already has a buffer on this device —
                # reference it instead of uploading a second copy (keeps HBM
                # residency at one dataset per device in fused-DBS mode,
                # where both the scan and the probes need the cache)
                self._cache_dev[d] = tuple(
                    next(
                        s.data
                        for s in arr.addressable_shards
                        if s.device == dev
                    )
                    for arr in self._cache_repl
                )
            else:
                self._cache_dev[d] = (
                    jax.device_put(self.bundle.train_x, dev),
                    jax.device_put(
                        np.asarray(self.bundle.train_y, dtype=np.int32), dev
                    ),
                )
        return self._cache_dev[d]

    # -------------------------------------------------------------- set-up
    # Subclass hooks: the LM trainer (train/lm_engine.py) overrides these.

    def _setup_data(self, bundle: Optional[DatasetBundle]) -> None:
        cfg = self.cfg
        if bundle is None:
            n_cap = cfg.n_train or (2048 if cfg.debug else None)
            n_test = 2048 if cfg.debug else None
            bundle = load_dataset(cfg.dataset, cfg.data_dir, n_train=n_cap, n_test=n_test)
        self.bundle = bundle
        self.n_train = len(bundle.train_x)
        if bundle.synthetic:
            self.logger.info(
                f"dataset {cfg.dataset}: files not found, using the synthetic stand-in"
            )

    def _setup_model(self) -> None:
        cfg = self.cfg
        from dynamic_load_balance_distributeddnn_tpu.ops.pallas import set_use_pallas

        set_use_pallas(cfg.use_pallas)  # routes GroupNorm at module trace time
        self.spec = build_model(cfg.model, num_classes=self.bundle.num_classes)
        self.tx = make_optimizer(cfg.learning_rate, cfg.momentum)
        h, w, c = self.bundle.train_x.shape[1:]
        example = jnp.zeros((1, h, w, c), jnp.float32)
        self.state = create_state(
            self.spec.module,
            example,
            self.tx,
            seed=cfg.seed,
            sharding=replicated_sharding(self.mesh),
        )
        self._zero1_padded = 0
        if cfg.shard_update:
            from dynamic_load_balance_distributeddnn_tpu.train.state import (
                shard_optimizer_state,
                zero1_padded_size,
            )

            self._zero1_padded = zero1_padded_size(self.state.params, self.n_dev)
            self.state = shard_optimizer_state(self.state, self.mesh, self.tx)
        if self.grad_comm == "hier":
            from dynamic_load_balance_distributeddnn_tpu.train.state import (
                attach_comm_residual,
            )

            # zero error-feedback residual, [n_dev, chunk] one row per
            # device over the two-level mesh; checkpoints restore into it.
            # With shard_update the residual chunk follows the ZERO-1
            # padding (a multiple of the TOTAL device count, so the
            # post-hop chunk re-splits evenly across hosts).
            self.state = attach_comm_residual(
                self.state, self.mesh,
                pad_multiple=self.n_dev if cfg.shard_update else 0,
            )
        self._build_steps()

    def _build_steps(self) -> None:
        """(Re)build the StepLibrary against the CURRENT mesh. Split out of
        ``_setup_model`` because the elastic recovery path rebuilds it after
        a fleet change: every compiled executable closes over the mesh, so
        a survivor mesh means a fresh library (old executables are garbage
        the moment their devices leave the fleet)."""
        cfg = self.cfg
        augment = cfg.dataset in ("cifar10", "cifar100")
        self.steps = StepLibrary(
            self.spec,
            self.mesh,
            self.tx,
            mean=self.bundle.mean,
            std=self.bundle.std,
            augment=augment,
            grad_clip=cfg.grad_clip,
            compute_dtype=jnp.bfloat16 if cfg.precision == "bfloat16" else None,
            use_pallas=cfg.use_pallas,
            shard_update=cfg.shard_update,
            grad_accum=cfg.grad_accum,
            compress_grads=cfg.compress_grads,
            remat=cfg.remat,
            grad_comm=self.grad_comm,
            grad_comm_wire=cfg.grad_comm_wire,
            grad_comm_wires=self._grad_comm_wires or None,
            zero1_padded=getattr(self, "_zero1_padded", 0),
        )
        if getattr(self, "_aot", None) is not None:
            self.steps.aot_service = self._aot

    def _build_plan(self, epoch: int, batch_sizes: np.ndarray):
        return build_epoch_plan(
            self.n_train,
            self.shares,
            batch_sizes,
            self.cfg.batch_size,
            epoch,
            seed=self.cfg.seed,
            bucket=self.cfg.bucket,
        )

    # ------------------------------------------------------------------ run

    def _dummy_batch(self, b: int):
        """Zero-filled (x, y, w) for one padded batch of ``b`` — the warm-up
        compile driver. Vision layout; the LM trainer overrides."""
        h, w_, c = self.bundle.train_x.shape[1:]
        return (
            np.zeros((b, h, w_, c), dtype=self.bundle.train_x.dtype),
            np.zeros((b,), dtype=np.int32),
            np.full((b,), 1.0 / max(b * self.world_size, 1), dtype=np.float32),
        )

    # ------------------------------------------------- AOT compile service
    # (runtime/compiler.py). The compile universe — per-step ladder rungs,
    # windowed twins, superstep scan keys — is described as abstract
    # ShapeDtypeStruct args (committed single-device shardings; param/state
    # trees ride in as live arrays so weak types and committed-ness are
    # exact) and compiled concurrently in the background. Dispatch resolves
    # the compiled executables from the service by (kind, batch, window,
    # device) key and falls back to the lazy jit wrappers on a miss.

    def _warm_ladder(self) -> "tuple[list, int]":
        """(ladder rungs, capacity width): every padded batch shape the
        balancer can produce — bucket multiples up to ``_cap_b``. Single
        source of truth for both warm paths (AOT and legacy)."""
        max_b = self._cap_b
        return list(range(self.cfg.bucket, max_b + 1, self.cfg.bucket)), max_b

    def _dummy_arg_shapes(self, b: int) -> list:
        """Per-(x, y, w) ``(shape, dtype)`` at batch ``b`` WITHOUT
        materializing batches: ``_dummy_batch``'s leading dim is the batch
        by contract (vision and LM alike), so one b=1 template — built once
        — scales to every rung. Spec building on the real TPU ladder would
        otherwise allocate and discard tens of MB of zeros per sweep."""
        if not self._aot_dummy_template:
            self._aot_dummy_template = [
                (tuple(t.shape[1:]), t.dtype) for t in self._dummy_batch(1)
            ]
        return [((b,) + s, dt) for s, dt in self._aot_dummy_template]

    def _aot_sds(self, shape, dtype, dev):
        from jax.sharding import SingleDeviceSharding

        return jax.ShapeDtypeStruct(
            tuple(int(s) for s in shape), dtype, sharding=SingleDeviceSharding(dev)
        )

    def _aot_step_key(self, kind: str, b: int, d: int, win: Optional[int]) -> tuple:
        return (kind, int(b), int(win or 0), int(d), self._aot_gen)

    @property
    def _batch_axes(self):
        """PartitionSpec entry splitting a batch dim over the whole mesh —
        the lone axis name (flat) or the (host, device) tuple (hier)."""
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
            mesh_batch_axes,
        )

        return mesh_batch_axes(self.mesh)

    def _combine_names(self) -> "tuple[str, str]":
        """(update, probe) combine executable names for the active combine
        structure — the hier twins ride the two-level mesh (routing into
        the sharded update internally when shard_update is on), the zero-1
        twins the flat mesh with a sharded update, the flat pair the single
        psum plus replicated update."""
        if self.grad_comm == "hier":
            return ("combine_update_hier", "combine_probe_hier")
        if self.cfg.shard_update:
            return ("combine_update_zero1", "combine_probe_zero1")
        return ("combine_update", "combine_probe")

    def _aot_view_spec(self, d: int):
        """Abstract spec of device d's params view: shapes/dtypes/shardings
        never change across steps, so one spec serves the whole run (and
        holds no reference to any live param buffers)."""
        if d not in self._aot_view_specs:
            views = shard_views(self.state.params, self.topology.devices)
            self._aot_view_specs[d] = jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=t.sharding),
                views[d],
            )
        return self._aot_view_specs[d]

    def _comm_bytes_per_step(self) -> "tuple[float, float]":
        """(ICI bytes, DCN bytes) of ONE gradient combine — the logical
        per-device payload each link class carries (the recorder's
        ``comm_bytes_*`` series; tests/test_grad_comm.py).

        flat: the full f32 tree rides every link it spans — ICI always, DCN
        only when the mesh actually crosses hosts (real processes; a
        single-process synthetic split has no DCN and records 0).
        hier: the innermost reduce-scatter + all-gather keep 2x the tree
        on ICI at full precision; each middle hop adds its shrinking
        vector on that hop's wire (up) plus f32 back (down) to the ICI
        class; only the top-hop chunk crosses DCN in the outermost wire's
        sum dtype (parallel/wire.py wire_payload_bytes). On a two-level
        tree this reduces exactly to the PR-12 numbers."""
        from dynamic_load_balance_distributeddnn_tpu.parallel.wire import (
            tree_hop_widths,
            wire_payload_bytes,
        )

        if not hasattr(self, "_param_elems"):
            self._param_elems = int(
                sum(p.size for p in jax.tree_util.tree_leaves(self.state.params))
            )
        elems = self._param_elems
        if self.grad_comm == "hier":
            sizes = self._topo_tree.sizes
            wires = self._grad_comm_wires
            # pad_multiple=0: the LOGICAL payload accounting (identical to
            # the PR-12 numbers); the zero-1 layout pads slightly wider but
            # the padding is zeros, not signal
            widths = tree_hop_widths(elems, sizes, pad_multiple=0)
            dcn = widths[0] * wire_payload_bytes(wires[0], sizes[0])
            # innermost hop: full-tree f32 reduce-scatter + all-gather
            ici = 2.0 * elems * 4
            # middle hops 1..k-1: the hop's vector on its wire up, f32 down
            for i in range(1, len(sizes) - 1):
                ici += widths[i] * (
                    wire_payload_bytes(wires[i], sizes[i]) + 4
                )
            return float(ici), float(dcn)
        # flat: compress_grads rides its own int16 wire (half the f32 bytes)
        per_elem = 2 if self.cfg.compress_grads == "int8" else 4
        return (
            float(elems * per_elem),
            float(elems * per_elem if self.n_proc > 1 else 0),
        )

    def _modeled_comm_step_s(self) -> float:
        """Modeled wall of ONE gradient combine over the probe's measured
        per-level link rates (ISSUE 17): each hop's bytes (the same per-hop
        accounting as :meth:`_comm_bytes_per_step`) divided by that level's
        measured bytes/s, summed — hops serialize along the tree spine.
        Feeds the window controller's ``comm_step_s`` so the rebalance
        hysteresis sees the comm floor a compute rebalance cannot touch.
        0.0 whenever there is no resolved tree or no probe data (the
        compute-only model — never guess a wall from missing rates)."""
        if self.grad_comm != "hier" or self._topo_tree is None:
            return 0.0
        rates = (self._link_bw or {}).get("level_bytes_per_s")
        sizes = self._topo_tree.sizes
        wires = self._grad_comm_wires
        if not rates or len(rates) != len(sizes) or len(wires) != len(sizes):
            return 0.0
        r = [float(x) if x and float(x) > 0 else 0.0 for x in rates]
        if any(x <= 0.0 for x in r):
            return 0.0
        from dynamic_load_balance_distributeddnn_tpu.parallel.wire import (
            tree_hop_widths,
            wire_payload_bytes,
        )

        if not hasattr(self, "_param_elems"):
            self._param_elems = int(
                sum(p.size for p in jax.tree_util.tree_leaves(self.state.params))
            )
        elems = self._param_elems
        widths = tree_hop_widths(elems, sizes, pad_multiple=0)
        k = len(sizes) - 1
        total = 2.0 * elems * 4 / r[k]  # innermost f32 RS + AG
        for i in range(1, k):  # middle hops: wire up, f32 down
            total += widths[i] * (
                wire_payload_bytes(wires[i], sizes[i]) + 4
            ) / r[i]
        total += widths[0] * wire_payload_bytes(wires[0], sizes[0]) / r[0]
        return float(total)

    def _aot_resolve(self, kind: str, b: int, d: int, win: Optional[int], fallback):
        """Compiled executable for a dispatch site, or the lazy jit
        fallback. Non-blocking: an in-flight or failed job falls back."""
        if self._aot is None:
            return fallback
        return self._aot.get(self._aot_step_key(kind, b, d, win)) or fallback

    def _scoped(self, key: tuple, fn, args):
        """The executable to dispatch for a program that is resolved outside
        the AOT registry (validation's step, a lazy-jit fallback). With the
        tracer off that is ``fn`` itself. While it is on, graftscope's scope
        map (obs/scopes.py) needs the program's compiled text, and the
        service writes a line for every program it compiles: so the wrapper
        is compiled through the service, once per ``key``, and that
        executable is dispatched in its place (same HLO; the lazy path would
        trace and lower it a second time)."""
        if not self._trace.enabled or self._aot is None or isinstance(fn, jax.stages.Compiled):
            return fn
        try:
            return self._aot.compile_now(key, fn, args)
        except Exception as e:  # the run goes on, this program unmapped
            if key not in self._aot_failed_logged:
                self._aot_failed_logged.add(key)
                self.logger.warning(f"scope map of {key} not written: {e!r}")
            return fn

    def _aot_submit_worker_steps(
        self, d: int, b: int, wins, want_acc: bool, want_plain: bool,
        speculative: bool = False,
    ) -> list:
        """Queue the worker-step executables for one (device, rung): the
        plain single-step pair (probes + step-mode dispatch) and the
        window-sliced pair per window length (window-mode dispatch). Returns
        the submitted/deduped keys. ``_dummy_batch`` output is used purely
        as a host-side shape/dtype template — nothing is transferred."""
        svc = self._aot
        if svc is None:
            return []
        use_cache = self._use_device_cache
        suffix = "_idx" if use_cache else ""
        kinds = []
        if want_plain:
            kinds.append(("worker_first" + suffix, None))
            if want_acc:
                kinds.append(("worker_acc" + suffix, None))
        for win in wins or ():
            kinds.append(("worker_first_win" + suffix, win))
            if want_acc:
                kinds.append(("worker_acc_win" + suffix, win))
        keys = [self._aot_step_key(kind, b, d, win) for kind, win in kinds]
        if all(svc.has(k) for k in keys):
            return keys  # steady state: skip all spec construction
        dev = self.topology.devices[d]
        sds = lambda shape, dt: self._aot_sds(shape, dt, dev)  # noqa: E731
        view = self._aot_view_spec(d)
        (xs_, xd), (ys_, yd), (ws_sh, wd) = self._dummy_arg_shapes(b)
        key_t = sds((2,), jnp.uint32)
        slow_t = sds((), jnp.int32)
        acc_t = jax.tree_util.tree_map(
            lambda p: self._aot_sds((1,) + tuple(p.shape), p.dtype, dev), view
        )
        cache = self._device_cache_for(d) if use_cache else ()
        targets = []
        if want_plain:
            if use_cache:
                data = cache + (sds((b,), jnp.int32), sds(ws_sh, wd))
            else:
                data = (sds(xs_, xd), sds(ys_, yd), sds(ws_sh, wd))
            targets.append(("worker_first" + suffix, (view,) + data + (key_t, slow_t), None))
            if want_acc:
                targets.append(
                    ("worker_acc" + suffix, (view, acc_t) + data + (key_t, slow_t), None)
                )
        for win in wins or ():
            kw_t = sds((win, 2), jnp.uint32)
            s_t = sds((), jnp.int32)
            if use_cache:
                data = cache + (sds((win, b), jnp.int32), sds((win,) + ws_sh, wd))
            else:
                data = (
                    sds((win,) + xs_, xd),
                    sds((win,) + ys_, yd),
                    sds((win,) + ws_sh, wd),
                )
            targets.append(
                ("worker_first_win" + suffix, (view,) + data + (kw_t, s_t, slow_t), win)
            )
            if want_acc:
                targets.append(
                    ("worker_acc_win" + suffix, (view, acc_t) + data + (kw_t, s_t, slow_t), win)
                )
        lows = self.steps.aot_lowerables()
        keys = []
        for kind, args, win in targets:
            k = self._aot_step_key(kind, b, d, win)
            if not svc.has(k):
                svc.submit(k, lows[kind], args, speculative=speculative)
            keys.append(k)
        return keys

    def _aot_submit_superstep(self, padded, win: int, speculative: bool = False) -> list:
        """Queue one scan-mode superstep (shape-tuple, window) key. The
        TrainState rides into lowering as the live tree (exact leaf
        shardings/weak types — a spec cannot express committed-ness), which
        is also why no zeros dummy state is needed anymore."""
        svc = self._aot
        if svc is None:
            return []
        topo = self.topology
        d0 = topo.used_device_indices[0]
        dev = topo.devices[d0]
        use_cache = self._use_device_cache
        name = "group_superstep_idx" if use_cache else "group_superstep"
        shape_key = topo.group_shape_key(list(padded), win)
        # register the key for the compile-once sentinel cross-check exactly
        # like the legacy warm did
        self._superstep_keys.add(shape_key)
        k = (name, shape_key, d0, self._aot_gen)
        if svc.has(k):
            return [k]
        sds = lambda shape, dt: self._aot_sds(shape, dt, dev)  # noqa: E731
        cols = []
        for b in padded:
            (xs_, xd), (ys_, yd), (ws_sh, wd) = self._dummy_arg_shapes(b)
            kw_t = sds((win, 2), jnp.uint32)
            ww_t = sds((win,) + ws_sh, wd)
            if use_cache:
                cols.append((sds((win, b), jnp.int32), ww_t, kw_t))
            else:
                cols.append(
                    (sds((win,) + xs_, xd), sds((win,) + ys_, yd), ww_t, kw_t)
                )
        tup = tuple(zip(*cols))
        slows = tuple(sds((), jnp.int32) for _ in padded)
        if use_cache:
            args = (self.state,) + self._device_cache_for(d0) + tup + (slows,)
        else:
            args = (self.state,) + tup + (slows,)
        svc.submit(k, self.steps.aot_lowerables()[name], args, speculative=speculative)
        return [k]

    def _aot_fused_key(self, n_win: int, width: int, slow_len: int) -> tuple:
        name = "fused_epoch_idx" if self._use_device_cache else "fused_epoch"
        return (
            (name, int(n_win), int(width), int(slow_len), self._aot_gen)
            + self._comm_sig
        )

    def _aot_submit_fused(self, n_win: int, width: int, slow_len: int) -> list:
        """Queue one fused whole-epoch-scan window executable
        (``fused_epoch``/``fused_epoch_idx``) as an AOT job: the MESH-sharded
        program lowers from ``ShapeDtypeStruct`` specs carrying explicit
        ``NamedSharding``s (batch axis split over the data mesh, replicated
        scalars), with the live TrainState riding in for exact leaf
        shardings/committed-ness — the multi-device lowering the service was
        previously gated away from (single-host probes only). Single-process
        only: multi-host runs keep the lazy path."""
        svc = self._aot
        if svc is None or self.n_proc > 1:
            return []
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
            batch_sharding,
        )

        k = self._aot_fused_key(n_win, width, slow_len)
        if svc.has(k):
            return [k]
        mesh = self.mesh
        use_cache = self._use_device_cache

        def sds(shape, dt, sh):
            return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dt, sharding=sh)

        bx = self._batch_axes

        def win_spec(shape, dt):
            full = (n_win, width) + tuple(shape)
            return sds(full, dt, batch_sharding(mesh, len(full), axis=bx, axis_dim=1))

        (xs_, xd), (ys_, yd), (ws_sh, wd) = [
            (s[1:], dt) for s, dt in self._dummy_arg_shapes(1)
        ]
        w_t = win_spec(ws_sh, wd)
        slow_t = sds((slow_len,), jnp.int32, batch_sharding(mesh, 1, axis=bx))
        seed_t = sds((), jnp.int32, replicated_sharding(mesh))
        if use_cache:
            cache_x, cache_y = self._device_cache_replicated()
            args = (
                self.state, cache_x, cache_y,
                win_spec((), jnp.int32), w_t, slow_t, seed_t,
            )
        else:
            args = (self.state, win_spec(xs_, xd), win_spec(ys_, yd), w_t,
                    slow_t, seed_t)
        svc.submit(k, self.steps.aot_lowerables()[k[0]], args)
        return [k]

    def _resolve_fused_epoch(self, n_win: int, width: int, slow_len: int, args):
        """Compiled fused-epoch executable for one window geometry: the
        service registry if present, a blocking inline ``compile_now`` on a
        cold key (same wall position as the lazy compile, but the executable
        registers for reuse and the compile attributes as deliberate AOT
        work, not a sentinel-visible foreground recompile), the lazy jit
        wrapper on failure or multi-host."""
        name = "fused_epoch_idx" if self._use_device_cache else "fused_epoch"
        lazy = self.steps.aot_lowerables()[name]
        if self._aot is None or self.n_proc > 1:
            return lazy
        k = self._aot_fused_key(n_win, width, slow_len)
        fn = self._aot.get(k)
        if fn is not None:
            return fn
        try:
            return self._aot.compile_now(k, lazy, args)
        except Exception as e:
            if k not in self._aot_failed_logged:
                self._aot_failed_logged.add(k)
                self.logger.warning(
                    f"AOT fused compile failed for {k}: {e!r} — using lazy jit"
                )
            return lazy

    def _aot_submit_combine(self) -> list:
        """Queue the mesh-wide combine twins (``combine_update`` +
        ``combine_probe``): their stacked-grads input is the params tree with
        a leading [n_dev] axis sharded over the data mesh
        (steps.stack_partials), a shape that never changes across the run —
        one key each. Every elastic epoch dispatches combine_update per step
        and every probe runs combine_probe, so these were the last
        steady-state executables compiling lazily on the multi-device path."""
        svc = self._aot
        if svc is None or self.n_proc > 1:
            return []
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P(self._batch_axes))
        stacked_t = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(
                (self.n_dev,) + tuple(p.shape), p.dtype, sharding=sh
            ),
            self.state.params,
        )
        keys = []
        for name in self._combine_names():
            k = (name, self._aot_gen) + self._comm_sig
            if not svc.has(k):
                svc.submit(k, getattr(self.steps, name), (self.state, stacked_t))
            keys.append(k)
        return keys

    def _aot_resolve_combine(self, name: str, fallback):
        if self._aot is None:
            return fallback
        return self._aot.get((name, self._aot_gen) + self._comm_sig) or fallback

    def _submit_warm_aot(self) -> None:
        """AOT warm-start: submit the whole compile universe and return
        immediately — the pool compiles while the engine builds epoch 0's
        plan (rebalance, partitioning, fault setup, probe scheduling); the
        remaining jobs drain at run_epoch's pre-wall barrier so no TIMED
        region ever shares cores with the compiler."""
        cfg = self.cfg
        self._aot_warm_t0 = time.perf_counter()
        ladder, max_b = self._warm_ladder()
        warm_acc = any(len(g) > 1 for g in self.topology.groups.values())
        mode = self._elastic_mode()
        wins: tuple = ()
        plan0 = self._build_plan(0, integer_batch_split(self.shares, cfg.batch_size))
        if mode in ("window", "scan"):
            wins = tuple(
                sorted({s1 - s0 for s0, s1 in self._elastic_ranges(plan0.num_steps)})
            )
        n = n_fused = self._submit_warm_fused(plan0)
        if n_fused:
            # fused-path runs never dispatch the elastic ladder or the
            # combine twins (the combine lives inside the SPMD program) —
            # only the standalone probe rungs at the plan's TRUE shapes feed
            # the balancer signal (fused-DBS mode)
            if cfg.dynamic_batch_size or self._needs_iter_cost:
                for d in self.topology.used_device_indices:
                    for r in self.topology.groups[d]:
                        b = plan0.workers[self.rank_lo + r].padded_batch
                        n += len(
                            self._aot_submit_worker_steps(
                                d, b, (), want_acc=False, want_plain=True
                            )
                        )
            self.logger.info(
                f"AOT warm: submitted {n} compile jobs ({n_fused} fused "
                "mesh programs + probe rungs) — no dummy execution; compiles "
                "overlap epoch-0 plan build, drained before its wall"
            )
            return
        for d in self.topology.used_device_indices:
            for b in ladder:
                n += len(
                    self._aot_submit_worker_steps(
                        d, b, wins if mode == "window" else (), warm_acc, want_plain=True
                    )
                )
        if mode == "scan":
            d0 = self.topology.used_device_indices[0]
            group = self.topology.groups[d0]
            padded = [plan0.workers[self.rank_lo + r].padded_batch for r in group]
            for win in wins:
                n += len(self._aot_submit_superstep(padded, win))
        else:
            n += len(self._aot_submit_combine())
        self.logger.info(
            f"AOT warm: submitted {n} compile jobs ({len(ladder)} ladder rungs "
            f"up to {max_b}, windows {list(wins)}) — no dummy execution; "
            "compiles overlap epoch-0 plan build, drained before its wall"
        )

    def _submit_warm_fused(self, plan0) -> int:
        """Warm-submit the fused whole-epoch executables when epoch 0 will
        take a fused path (mirrors _dispatch_epoch's selection on the
        epoch-0 plan): the mesh program's compile overlaps the plan build
        instead of landing inside the excluded epoch 0. Returns the number
        of submitted keys (0 = elastic run)."""
        cfg = self.cfg
        if self._aot is None or self.n_proc > 1:
            return 0
        if self._can_use_fused(plan0):
            width = sum(w.padded_batch for w in plan0.workers)
            slow_len = self.world_size
        elif self._can_use_fused_dbs(plan0):
            width = self.world_size * self._cap_b
            slow_len = self.world_size
        elif self._can_use_packed(plan0):
            width = self._cap_packed
            slow_len = 1
        else:
            return 0
        n = 0
        for s0, s1 in self._chunk_ranges(plan0.num_steps):
            n += len(self._aot_submit_fused(s1 - s0, width, slow_len))
        return n

    def _aot_stage_plan(self, plan) -> tuple:
        """Submit this plan's missing executables (a mid-run rebalance on a
        cold service compiles concurrently instead of serially-lazily) plus
        speculative adjacent ladder rungs, and return the keys the epoch's
        dispatch will barrier on."""
        if self._aot is None:
            return ()
        cfg = self.cfg
        mode = self._elastic_mode()
        topo = self.topology
        ranges = self._elastic_ranges(plan.num_steps)
        wins = tuple(sorted({s1 - s0 for s0, s1 in ranges}))
        needed: list = []
        if mode == "scan":
            d0 = topo.used_device_indices[0]
            group = topo.groups[d0]
            padded = [plan.workers[self.rank_lo + r].padded_batch for r in group]
            for win in wins:
                needed += self._aot_submit_superstep(padded, win)
            # the standalone probes still run the plain single-step rungs
            for r in group:
                b = plan.workers[self.rank_lo + r].padded_batch
                needed += self._aot_submit_worker_steps(
                    d0, b, (), want_acc=False, want_plain=True
                )
        else:
            for d in topo.used_device_indices:
                group = topo.groups[d]
                want_acc = len(group) > 1
                for r in group:
                    b = plan.workers[self.rank_lo + r].padded_batch
                    needed += self._aot_submit_worker_steps(
                        d, b, wins if mode == "window" else (), want_acc, want_plain=True
                    )
            needed += self._aot_submit_combine()
        return tuple(dict.fromkeys(needed))

    def _maybe_speculate(self, plan) -> None:
        """Background-compile the executables the NEXT rebalance is likely to
        dispatch. Ladder modes: the rungs ADJACENT to this plan's (±bucket,
        capacity-clamped) — the next rebalance moves each worker at most a
        few rungs. Scan mode (config.speculate_scan): the superstep shape
        TUPLES have no finite adjacency, so the solver's next share vector is
        PREDICTED (ShareTrajectoryPredictor) and run through the plan
        builder's own quantization — a share hit is a tuple-key hit. Called
        from run_epoch AFTER the timed region — the jobs overlap the untimed
        validation tail (and drain at the next epoch's pre-wall barrier), so
        timed walls never share cores with the compiler; a misprediction
        costs only background work."""
        cfg = self.cfg
        if self._aot is None or not cfg.aot_speculate or not cfg.dynamic_batch_size:
            return
        if self._elastic_mode() == "scan":
            if cfg.speculate_scan:
                self._speculate_scan_tuple()
            return
        wins = ()
        if self._elastic_mode() == "window":
            wins = tuple(
                sorted({s1 - s0 for s0, s1 in self._elastic_ranges(plan.num_steps)})
            )
        self._aot_speculate(plan, wins)

    def _speculate_scan_tuple(self) -> None:
        """Predict the next epoch's quantized share vector, build the plan it
        implies (host-side arithmetic only), and queue its superstep
        (shape-tuple, window) keys speculatively. A converged run predicts
        the tuple it already dispatches — the submit dedups to a lookup."""
        cfg = self.cfg
        bucket = cfg.bucket if (cfg.snap_to_bucket and self.SNAP_BATCHES) else 0
        cap = min(1.0, cfg.capacity_factor / self.world_size)
        if cap * self.world_size < 1.0:
            return  # infeasible cap (capacity_factor < 1): nothing to match
        ctl = self._rebalance_ctl
        if ctl is not None and ctl.last_candidate_batches is not None:
            # window-cadence runs: speculation is RE-AIMED at the online
            # controller's candidate plan — its EMA-rate solve is the plan a
            # mid-epoch switch (or the next epoch's boundary solve, seeded
            # from the switched shares) will actually dispatch, so a hit
            # keeps switches foreground-compile-free
            batches = np.asarray(ctl.last_candidate_batches, dtype=np.int64)
        else:
            batches = self._share_predictor.predict_batches(
                cfg.batch_size, bucket=bucket, max_share=cap
            )
        if batches is None:
            return
        # epoch index only seeds the plan's permutation; shapes are epoch-free
        pred = self._build_plan(0, batches)
        topo = self.topology
        d0 = topo.used_device_indices[0]
        group = topo.groups[d0]
        padded = [pred.workers[self.rank_lo + r].padded_batch for r in group]
        for s0, s1 in self._elastic_ranges(pred.num_steps):
            self._aot_submit_superstep(padded, s1 - s0, speculative=True)

    def _aot_speculate(self, plan, wins) -> None:
        cfg = self.cfg
        if not (cfg.snap_to_bucket and self.SNAP_BATCHES):
            return
        max_b = self._cap_b
        for d in self.topology.used_device_indices:
            group = self.topology.groups[d]
            want_acc = len(group) > 1
            for r in group:
                b = plan.workers[self.rank_lo + r].padded_batch
                for nb in (b - cfg.bucket, b + cfg.bucket):
                    if cfg.bucket <= nb <= max_b:
                        self._aot_submit_worker_steps(
                            d, nb, wins, want_acc, want_plain=True, speculative=True
                        )

    def _aot_wait_needed(self, keys, epoch: int) -> None:
        """Barrier on the keys this epoch dispatches. Failed jobs log once
        and dispatch falls back to the lazy jit wrappers (``get`` returns
        None for a failed key)."""
        if self._aot is None or not keys:
            return
        t0 = time.perf_counter()
        for k, e in self._aot.wait(keys):
            if k not in self._aot_failed_logged:
                self._aot_failed_logged.add(k)
                self.logger.warning(
                    f"AOT compile failed for {k}: {e!r} — falling back to lazy jit"
                )
        dt = time.perf_counter() - t0
        if self._aot_warm_t0 is not None:
            self.logger.info(
                f"AOT warm: epoch-{epoch} dispatch barrier {dt:.2f}s "
                f"({time.perf_counter() - self._aot_warm_t0:.1f}s since "
                "submission; remaining jobs keep compiling in the background)"
            )
            self._aot_warm_t0 = None

    def _warm_shapes(self) -> None:
        """LEGACY execute-to-compile warm (``--aot_warm off``): pre-compile
        the elastic step for every padded batch shape the balancer can
        produce (multiples of ``bucket`` up to the capacity cap), on every
        used device, by executing dummy steps serially. Kept as the
        serial-vs-concurrent reference (tests/test_aot_compiler.py) — the AOT
        service above is the production path. Without any warm, each
        rebalance's fresh shape pays its XLA compile inside a timed epoch —
        on short benchmark runs the compiles dominate and bury the
        balancer's actual win."""
        cfg = self.cfg
        ladder, max_b = self._warm_ladder()
        key = jax.random.PRNGKey(0)
        slow = jnp.int32(0)
        t0 = time.perf_counter()
        views = shard_views(self.state.params, self.topology.devices)
        # the accumulate variant only runs where workers share a device
        warm_acc = any(len(g) > 1 for g in self.topology.groups.values())
        use_cache = self._use_device_cache
        for d in self.topology.used_device_indices:
            dev = self.topology.devices[d]
            cache = self._device_cache_for(d) if use_cache else ()
            for b in ladder:
                x, y, w = self._dummy_batch(b)
                if use_cache:
                    args = cache + (
                        jax.device_put(np.zeros((b,), np.int32), dev),
                        jax.device_put(w, dev),
                        jax.device_put(key, dev),
                        jax.device_put(slow, dev),
                    )
                    step_first = self.steps.worker_step_first_idx
                    step_acc = self.steps.worker_step_acc_idx
                else:
                    args = (
                        jax.device_put(x, dev),
                        jax.device_put(y, dev),
                        jax.device_put(w, dev),
                        jax.device_put(key, dev),
                        jax.device_put(slow, dev),
                    )
                    step_first = self.steps.worker_step_first
                    step_acc = self.steps.worker_step_acc
                # deliberate execute-to-compile: this IS the serial A/B
                # reference leg (aot_warm off)
                acc, aux = step_first(views[d], *args)  # graftlint: disable=G007
                if warm_acc:
                    acc, aux = step_acc(views[d], acc, *args)
                jax.block_until_ready(aux)
                heartbeat()  # one ladder compile done — the watchdog's unit
        n_win = self._warm_windowed_shapes(ladder, views, warm_acc)
        n_win += self._warm_superstep_shapes()
        self.logger.info(
            f"Warm start: compiled {len(ladder)} batch shapes "
            f"(up to {max_b}, + {n_win} windowed/superstep variants) in "
            f"{time.perf_counter() - t0:.1f}s"
        )

    def _warm_windowed_shapes(self, ladder, views, warm_acc: bool) -> int:
        """Warm the window-sliced executables the superstep hot loop actually
        dispatches (the per-step ladder above still serves the probes). The
        window lengths come from a representative epoch-0 plan — the
        equal-step invariant keeps num_steps (and so the body/tail window
        lengths) constant across rebalanced plans, so (rung, window) covers
        the epochs' compiled-shape universe. Scan mode is excluded: its
        executables specialize on whole shape TUPLES (combinatorial — they
        compile lazily, once per (shapes, window), sentinel-checked)."""
        if self._elastic_mode() != "window":
            return 0
        cfg = self.cfg
        plan0 = self._build_plan(0, integer_batch_split(self.shares, cfg.batch_size))
        wins = sorted({s1 - s0 for s0, s1 in self._elastic_ranges(plan0.num_steps)})
        use_cache = self._use_device_cache
        key = jax.random.PRNGKey(0)
        slow = jnp.int32(0)
        s0_i = np.int32(0)
        n = 0
        for d in self.topology.used_device_indices:
            dev = self.topology.devices[d]
            cache = self._device_cache_for(d) if use_cache else ()
            for b in ladder:
                x, y, w = self._dummy_batch(b)
                for win in wins:
                    kwin = jax.device_put(jax.random.split(key, win), dev)
                    ww = jax.device_put(np.broadcast_to(w, (win,) + w.shape).copy(), dev)
                    if use_cache:
                        args = cache + (
                            jax.device_put(np.zeros((win, b), np.int32), dev),
                            ww,
                            kwin,
                            s0_i,
                            jax.device_put(slow, dev),
                        )
                        step_first = self.steps.worker_step_first_win_idx
                        step_acc = self.steps.worker_step_acc_win_idx
                    else:
                        args = (
                            jax.device_put(np.broadcast_to(x, (win,) + x.shape).copy(), dev),
                            jax.device_put(np.broadcast_to(y, (win,) + y.shape).copy(), dev),
                            ww,
                            kwin,
                            s0_i,
                            jax.device_put(slow, dev),
                        )
                        step_first = self.steps.worker_step_first_win
                        step_acc = self.steps.worker_step_acc_win
                    # deliberate execute-to-compile (serial A/B reference leg)
                    acc, aux = step_first(views[d], *args)  # graftlint: disable=G007
                    if warm_acc:
                        acc, aux = step_acc(views[d], acc, *args)
                    jax.block_until_ready(aux)
                    n += 1
                    heartbeat()
        return n

    def _warm_superstep_shapes(self) -> int:
        """Scan-mode warm: compile the epoch-0 (uniform) plan's superstep
        (shape-tuple, window) keys against a zeros dummy state (donated and
        discarded), so the run's opening epochs pay no unrolled-scan compile
        inside a timed wall. Rebalanced plans' fresh shape TUPLES are
        combinatorial and still compile lazily, once per key — warmed keys
        register in ``_superstep_keys`` so the compile-once sentinel's
        cache-vs-keys comparison stays exact."""
        if self._elastic_mode() != "scan":
            return 0
        cfg = self.cfg
        plan0 = self._build_plan(0, integer_batch_split(self.shares, cfg.batch_size))
        wins = sorted({s1 - s0 for s0, s1 in self._elastic_ranges(plan0.num_steps)})
        topo = self.topology
        d0 = topo.used_device_indices[0]
        group = topo.groups[d0]
        dev = topo.devices[d0]
        use_cache = self._use_device_cache
        key = jax.random.PRNGKey(0)
        n = 0
        for win in wins:
            padded = [plan0.workers[self.rank_lo + r].padded_batch for r in group]
            self._superstep_keys.add(topo.group_shape_key(padded, win))
            cols = []
            for b in padded:
                x, y, w = self._dummy_batch(b)
                kwin = jax.device_put(jax.random.split(key, win), dev)
                ww = jax.device_put(
                    np.broadcast_to(w, (win,) + w.shape).copy(), dev
                )
                if use_cache:
                    cols.append((
                        jax.device_put(np.zeros((win, b), np.int32), dev),
                        ww,
                        kwin,
                    ))
                else:
                    cols.append((
                        jax.device_put(np.broadcast_to(x, (win,) + x.shape).copy(), dev),
                        jax.device_put(np.broadcast_to(y, (win,) + y.shape).copy(), dev),
                        ww,
                        kwin,
                    ))
            tup = tuple(zip(*cols))
            slows = tuple(jax.device_put(jnp.int32(0), dev) for _ in group)
            # the dummy must replicate the REAL state's shardings AND
            # committed-ness, not just shapes/dtypes: zeros_like drops the
            # NamedSharding, and committing a leaf the real state leaves
            # uncommitted (the injected-hyperparams lr scalar) changes the
            # pjit signature either way — the mismatch compiles a second,
            # never-reused superstep variant
            def zero_like(t):
                z = jnp.zeros(t.shape, t.dtype)
                if getattr(t, "_committed", True):
                    z = jax.device_put(z, t.sharding)
                return z

            dummy = jax.tree_util.tree_map(zero_like, self.state)
            if use_cache:
                idxs, ws_, ks = tup
                # deliberate execute-to-compile (serial A/B reference leg)
                _, aux = self.steps.group_superstep_idx(  # graftlint: disable=G007
                    dummy, *self._device_cache_for(d0), idxs, ws_, ks, slows
                )
            else:
                xs, ys, ws_, ks = tup
                # deliberate execute-to-compile (serial A/B reference leg)
                _, aux = self.steps.group_superstep(  # graftlint: disable=G007
                    dummy, xs, ys, ws_, ks, slows
                )
            jax.block_until_ready(aux)
            n += 1
            heartbeat()
        return n

    def run(self, epochs: Optional[int] = None) -> MetricsRecorder:
        cfg = self.cfg
        epochs = cfg.epoch_size if epochs is None else epochs
        self.logger.info(
            f"Starting: {cfg.model}/{cfg.dataset}, ws={cfg.world_size}, "
            f"B={cfg.batch_size}, devices={self.n_dev}, dbs={cfg.dynamic_batch_size}"
        )
        self._maybe_warm()
        start_epoch = 0
        if cfg.ckpt_dir:
            start_epoch = self._maybe_restore()
        if cfg.profile_dir:
            jax.profiler.start_trace(cfg.profile_dir)
        try:
            for epoch in range(start_epoch, epochs):
                if cfg.elastic == "on":
                    self._run_epoch_elastic_world(epoch)
                else:
                    self.run_epoch(epoch)
                if cfg.ckpt_dir:
                    self._save_checkpoint(epoch)
        finally:
            if cfg.profile_dir:
                jax.profiler.stop_trace()
            if cfg.ckpt_dir:
                # epoch-tail saves are async (train/checkpoint.py): drain
                # them before declaring the run complete, and drop the
                # cached manager's thread pools (long-lived processes build
                # many engines)
                from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
                    flush_checkpoints,
                )

                flush_checkpoints(cfg.ckpt_dir, close=True)
                heartbeat()  # checkpoint drain answered — not a stall
        if self._aot is not None:
            # a failed AOT job is replaced by lazy jit with a warning; the
            # count rides in the artifact so the replacement is never silent
            self.recorder.meta["aot_stats"] = self._aot.stats()
        if self.proc_id == 0:
            # rank-0-only artifact, like the reference (dbs.py:440-442)
            self.recorder.save(cfg.stat_dir, cfg.base_filename())
        self.save_trace()
        self.logger.info(
            f"Total wallclock: {self.total_wallclock:.3f}s"
            + (
                f" (+{self.total_probe_s:.3f}s probe/instrumentation)"
                if self.total_probe_s > 0
                else ""
            )
        )
        return self.recorder

    def close_spool(self):
        """Drain and close the flight-recorder spool (idempotent; returns
        the closed writer for byte accounting, or None). The ONE external
        teardown surface — bench arms and test harnesses that drive epochs
        without run() call this instead of reaching into the tracer."""
        if self._spool_writer is None:
            return None
        sp = self._trace.detach_spool()
        self._spool_writer = None
        if sp is not None:
            self.logger.info(
                f"flight recorder: spool closed ({sp.path}, "
                f"{sp.bytes_written} bytes)"
            )
        return sp

    def save_trace(self) -> Optional[str]:
        """Persist the graftscope trace (Chrome-trace JSON under
        cfg.trace_dir, config-encoded filename per process) when tracing is
        enabled; returns the path. Summarize with `graftscope summarize`,
        or open in ui.perfetto.dev next to a --profile_dir device trace."""
        if not self._trace.enabled:
            return None
        # flight recorder: a clean end of run drains and closes the spool
        # (everything buffered reaches disk) — the crash path needs no
        # cooperation, the flusher thread already wrote all but the tail
        self.close_spool()
        path = os.path.join(
            self.cfg.trace_dir,
            self.cfg.base_filename().format(self.proc_id) + ".trace.json",
        )
        # process-backend compile workers buffer their own spans and write
        # them at exit: flush (shut down) the worker pool first, then stitch
        # the files into the run trace as pid-tagged tracks
        worker_traces = []
        if self._aot is not None:
            worker_traces = self._aot.flush_workers()
        self._trace.save(path)
        if worker_traces:
            from dynamic_load_balance_distributeddnn_tpu.obs.trace import (
                merge_trace_files,
            )

            merge_trace_files(path, worker_traces)
        self.logger.info(
            f"graftscope trace saved: {path} "
            f"({len(self._trace.events())} events"
            + (f"; stitched {len(worker_traces)} compile-worker trace files"
               if worker_traces else "")
            + "; `graftscope summarize` for the per-phase epoch-attribution "
            "table)"
        )
        return path

    def _save_checkpoint(self, epoch: int) -> None:
        from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
            save_checkpoint,
        )

        save_checkpoint(
            self.cfg.ckpt_dir,
            epoch,
            self.state,
            {
                "shares": self.shares,
                "node_times": self.node_times,
                "total_wallclock": self.total_wallclock,
                "total_probe_s": self.total_probe_s,
                # elastic resume-after-loss: the fleet this checkpoint was
                # taken at (original ranks); _maybe_restore adopts it
                "active_ranks": list(self.active_ranks),
            },
        )

    def _zero1_restore_template(self, sidecar: dict):
        """Restore template matching a checkpoint saved at a REDUCED fleet
        (elastic × shard_update): the saved 1/N optimizer chunks are padded
        to the survivor device count's multiple, so the fresh full-world
        template's flat shapes would mismatch. Rebuild the opt-state chunk
        leaves at the saved padding (replicated placement — addressable for
        the restore; the post-restore reshard re-chunks). None = the stamp
        matches the current fleet, keep the ordinary template."""
        saved_active = sidecar.get("active_ranks")
        if saved_active is None:
            return None
        # same validity gate as _maybe_restore's adopt branch, applied
        # BEFORE indexing: a stamp from a different world_size (stale dir,
        # re-configured resume) must fall back to the ordinary template,
        # not crash the restore
        if not all(
            isinstance(r, (int, float)) and 0 <= int(r) < self.cfg.world_size
            for r in saved_active
        ):
            return None
        from dynamic_load_balance_distributeddnn_tpu.train.state import (
            zero1_padded_size,
            zero1_param_count,
        )

        local_devices = sorted(jax.local_devices(), key=lambda d: d.id)
        ids_global = self.cfg.worker_device_ids(len(local_devices))
        n_dev_saved = len({ids_global[int(r)] for r in saved_active})
        saved_padded = zero1_padded_size(self.state.params, n_dev_saved)
        if saved_padded == self._zero1_padded:
            return None
        total = zero1_param_count(self.state.params)
        rep = replicated_sharding(self.mesh)

        def resize(leaf):
            if not (hasattr(leaf, "ndim") and leaf.ndim >= 1):
                return leaf
            if leaf.shape[0] < total:
                return leaf
            shape = (saved_padded,) + tuple(leaf.shape[1:])
            return jax.device_put(jnp.zeros(shape, leaf.dtype), rep)

        return self.state.replace(
            opt_state=jax.tree_util.tree_map(resize, self.state.opt_state)
        )

    def _maybe_restore(self) -> int:
        from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
            restore_checkpoint,
        )

        template_fn = None
        if self.cfg.elastic == "on" and self.cfg.shard_update:
            template_fn = self._zero1_restore_template
        # a respawned JOINER entering the grown world (DBS_MH_IDENT marks
        # it): measure our own ranks' per-example costs on their local
        # devices (no collectives) and publish them into the grow
        # rendezvous's probe exchange BEFORE the restore barrier both sides
        # synchronize on — the survivors publish theirs at the matching
        # point in _mh_rerendezvous, so after the restore every publication
        # is on disk and both sides collect the identical set (ISSUE 17)
        joiner = (
            self.cfg.elastic == "on"
            and self.n_proc > 1
            and self._rdzv is not None
            and os.environ.get("DBS_MH_IDENT") is not None
        )
        if joiner:
            own_costs = {}
            for r in self._ranks_of_proc(self._orig_proc_id):
                c = self._probe_local_cost(int(r))
                if c is not None:
                    own_costs[int(r)] = float(c)
            self._publish_probe_costs(own_costs)
        restored = restore_checkpoint(
            self.cfg.ckpt_dir, self.state, template_fn=template_fn
        )
        if restored is None:
            return 0
        epoch, state, controller = restored
        self.state = state
        # Elastic resume-after-loss: a run that checkpointed at a REDUCED
        # fleet stamps its active ranks; adopt them (re-shard to the saved
        # survivor set) so the controller vectors below line up. Without
        # elastic (or with a stale/not-applicable stamp) a length-mismatched
        # controller vector resets to uniform rather than poisoning the
        # solver with a wrong-shaped state.
        saved_active = controller.get("active_ranks")
        if self.cfg.elastic == "on" and self.n_proc > 1:
            # Multi-host: the LIVE rendezvous roster is authoritative, not
            # the checkpoint stamp — a joiner restoring a shrink-era
            # checkpoint (stamped with the survivor fleet) is entering the
            # GROWN world its join rendezvous just established
            live = sorted(
                r
                for r in range(self.cfg.world_size)
                if self._proc_of_rank(r) in set(self._proc_roster)
            )
            if live != self.active_ranks:
                self._reshard_world(live)
                self.state = retry_transient(
                    lambda: self._state_from_host(
                        self._state_to_host(self.state)
                    ),
                    logger=self.logger,
                    desc="resume state re-placement",
                    tick=heartbeat,
                )
                self._fix_comm_residual()
                for r in range(self.cfg.world_size):
                    if r not in self.active_ranks:
                        self.health.mark_down(r)
            base = (
                [int(r) for r in saved_active]
                if saved_active
                and all(
                    0 <= int(r) < self.cfg.world_size for r in saved_active
                )
                else list(self.active_ranks)
            )
            if "shares" in controller and len(controller["shares"]) == len(
                base
            ):
                self._adopt_controller_vectors(
                    base,
                    controller["shares"],
                    controller.get("node_times", controller["shares"]),
                )
            elif "shares" in controller:
                # a stamp from a different world layout: keep the fresh
                # uniform vectors rather than poisoning the solver — same
                # contract as the single-process resume path below
                self.logger.warning(
                    f"Resume: sidecar vectors ({len(controller['shares'])} "
                    f"entries) do not match the stamped fleet "
                    f"({len(base)}) — resetting to uniform"
                )
            if joiner:
                # upgrade the sidecar-derived seed to the equilibrium of the
                # exchanged probe costs (ISSUE 17). The restore above was a
                # global barrier, so every process's probe file is on disk;
                # collect is all-or-nothing, so an incomplete exchange keeps
                # the identical sidecar vectors on every process instead
                self._collect_probe_seed()
            if "total_wallclock" in controller:
                self.total_wallclock = float(controller["total_wallclock"])
            if "total_probe_s" in controller:
                self.total_probe_s = float(controller["total_probe_s"])
            self.logger.info(
                f"Resumed from checkpoint at epoch {epoch} over the live "
                f"fleet {self.active_ranks} (roster {self._proc_roster})"
            )
            return epoch + 1
        if (
            self.cfg.elastic == "on"
            and saved_active is not None
            and sorted(int(r) for r in saved_active) != self.active_ranks
            and all(0 <= int(r) < self.cfg.world_size for r in saved_active)
        ):
            self._reshard_world(sorted(int(r) for r in saved_active))
            # _reshard_world leaves state placement to its caller: the
            # restored state is still replicated over the FULL original
            # mesh, and a mixed device set poisons every state-fed
            # executable on the survivor mesh — re-place onto it
            self.state = retry_transient(
                lambda: self._state_from_host(self._state_to_host(self.state)),
                logger=self.logger,
                desc="resume state re-placement",
                tick=heartbeat,
            )
            self._fix_comm_residual()
            for r in range(self.cfg.world_size):
                if r not in self.active_ranks:
                    self.health.mark_down(r)
            self.logger.info(
                f"Resume: adopted checkpointed survivor fleet "
                f"{self.active_ranks} (world size {self.world_size})"
            )
        for key, fallback in (
            ("shares", lambda: initial_partition(self.world_size)),
            ("node_times", lambda: np.ones(self.world_size, dtype=np.float64)),
        ):
            if key in controller:
                vec = np.asarray(controller[key], dtype=np.float64)
                if len(vec) == self.world_size:
                    setattr(self, key, vec)
                else:
                    self.logger.warning(
                        f"Resume: checkpointed {key} has length {len(vec)} "
                        f"but the fleet is {self.world_size} — resetting to "
                        "uniform"
                    )
                    setattr(self, key, fallback())
        if "total_wallclock" in controller:
            self.total_wallclock = float(controller["total_wallclock"])
        if "total_probe_s" in controller:
            self.total_probe_s = float(controller["total_probe_s"])
        self.logger.info(f"Resumed from checkpoint at epoch {epoch}")
        return epoch + 1

    # ------------------------------------------------- elastic world size
    # (ISSUE 6). Degradation ladder: the solver re-routes data away from a
    # SLOW worker every epoch (the paper's story); a LOST worker — dead or
    # preempted — used to kill the run. With cfg.elastic on, worker loss is
    # detected (health checks at window boundaries, fed by the preemption
    # injector's virtual schedule or real peer heartbeats), CONFIRMED
    # (detect_misses consecutive misses), and survived: drain, re-solve the
    # partition over the survivors (the same solver code path as the
    # straggler re-route — balance/solver.py restarts its velocity track on
    # world-size change by design), re-shard the data, re-warm the new
    # world size's executables through the AOT service, and continue from
    # the epoch-start consistent snapshot. A recovered worker is readmitted
    # at the next epoch boundary with a probe-seeded share.

    def _arm_peer_heartbeats(self) -> None:
        """Multi-host detection + recovery channel: each process beacons its
        own heartbeat file under DBS_PEER_HB_DIR; health checks scan peers
        for staleness (and the watchdog's exit-reason tag), and the SAME
        directory carries the re-rendezvous protocol files
        (runtime/rendezvous.py) — a confirmed peer-process loss is survived
        by tearing down ``jax.distributed`` and re-initializing over the
        survivor roster at the epoch boundary (``_recover_multihost``).
        Workers that want that recovery must have brought the world up
        through ``rendezvous.elastic_initialize`` (a stock-initialized
        world's coordination service aborts every survivor on peer death);
        detection alone works either way."""
        hb_dir = os.environ.get("DBS_PEER_HB_DIR")
        if not hb_dir:
            return
        from dynamic_load_balance_distributeddnn_tpu.runtime.health import (
            ProcessHeartbeat,
        )
        from dynamic_load_balance_distributeddnn_tpu.runtime.rendezvous import (
            RendezvousStateMachine,
        )

        self._rdzv = RendezvousStateMachine(
            hb_dir, self._orig_proc_id, logger=self.logger
        )
        roster = self._rdzv.current_roster()
        if len(roster) == self.n_proc:
            self._proc_roster = roster
        # the ORIGINAL fleet shape anchors worker-rank ownership
        # (_ranks_of_proc slices world_size by the GEN-0 process count). A
        # long-lived survivor inherited it from its own gen-0 n_proc, but a
        # respawned JOINER builds its engine inside the grown world — if
        # the fleet grew back to fewer processes than gen 0 had, the live
        # process count is the WRONG divisor. ack_g0 records the original
        # roster; adopt its size when present.
        import json as _json

        try:
            with open(os.path.join(hb_dir, "ack_g0.json")) as f:
                g0 = _json.load(f)
            roster0 = [int(p) for p in g0.get("roster", ())]
            if roster0 and len(roster0) != self._n_proc0:
                self.logger.info(
                    f"elastic: adopting generation-0 fleet shape "
                    f"({len(roster0)} processes) for rank ownership "
                    f"(live world has {self.n_proc})"
                )
                self._n_proc0 = len(roster0)
        except (OSError, ValueError):
            pass
        self._hb_beacon = ProcessHeartbeat(
            period_s=float(os.environ.get("DBS_PEER_HB_PERIOD_S", "1.0"))
        )
        beacon_path = self._hb_beacon.beacon(hb_dir, f"proc{self._orig_proc_id}")
        self._hb_beacon_path = beacon_path
        # a stall-watchdog abort must be readable by the PEERS too, not just
        # the parent watching this process's own heartbeat file — register
        # the beacon so the abort path tags it with the exit reason
        from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
            register_exit_tag_path,
            unregister_exit_tag_path,
        )

        register_exit_tag_path(beacon_path)
        # tie beacon/watcher threads and the tag registration to THIS
        # trainer's lifetime: long-lived processes build many engines, and
        # a later run's abort must not rewrite a finished run's beacon file
        import weakref

        beacon = self._hb_beacon  # finalize must not capture self

        def _teardown() -> None:
            beacon.stop()
            unregister_exit_tag_path(beacon_path)

        weakref.finalize(self, _teardown)
        # detection must run OFF the controller thread: when a peer dies
        # mid-collective, the controller is wedged inside that collective —
        # the watcher thread still sees the stale pulse, logs it, and drops
        # a marker file the launcher (bench retry loop, test harness) reads
        stale_s = float(os.environ.get("DBS_PEER_HB_STALE_S", "10.0"))
        peers = [
            f"proc{p}" for p in self._proc_roster if p != self._orig_proc_id
        ]
        # the callback must not capture self either: the WATCHER thread
        # holds it, and a closed-over trainer would be pinned reachable —
        # the finalize above would then never fire
        logger, proc_id = self.logger, self._orig_proc_id

        def _on_stale(ident: str, info: dict) -> None:
            reason = ProcessHeartbeat.stale_reason(info)
            logger.warning(
                f"elastic: peer {ident} unreachable ({reason}) — survivors "
                "will re-rendezvous at the next boundary (a wedged "
                "collective against the dead peer errors or aborts first)"
            )
            # flight-recorder detection edge: emitted from the WATCHER
            # thread — exactly the thread that still runs when the
            # controller is wedged in a collective against the dead peer
            get_tracer().instant(
                "peer_stale", cat="elastic",
                args={"peer": ident, "reason": reason},
            )
            try:
                import json

                path = os.path.join(
                    hb_dir, f"elastic_detected_{ident}_by_proc{proc_id}.json"
                )
                # G017 protocol-file discipline: sibling watchers read this
                # marker while we write it, so publish atomically (tmp +
                # os.replace) — a torn in-place write here is exactly the
                # half-JSON the rendezvous readers must otherwise survive
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump({"peer": ident, "reason": reason}, f)
                os.replace(tmp, path)
            except OSError:
                pass

        self._hb_beacon.watch(hb_dir, peers, stale_s, _on_stale)
        # re-armable watcher factory for fleet growth (a rejoined peer — or
        # one the original watcher already fired on — needs a fresh watch
        # thread; closures capture the beacon, never self)
        beacon_ref = self._hb_beacon
        self._peer_watch = lambda idents: beacon_ref.watch(
            hb_dir, idents, stale_s, _on_stale
        )
        self.logger.info(
            f"elastic: process heartbeat beacon + peer watcher armed under "
            f"{hb_dir}"
        )

    def _ranks_of_proc(self, p: int) -> range:
        """ORIGINAL worker ranks owned by ORIGINAL process ``p`` — the
        gen-0 contiguous slice, invariant across re-rendezvous (compact
        runtime ranks re-derive from these via ``active_ranks``)."""
        wsp = self.cfg.world_size // max(self._n_proc0, 1)
        return range(p * wsp, (p + 1) * wsp)

    def _proc_of_rank(self, r: int) -> int:
        return int(r) // (self.cfg.world_size // max(self._n_proc0, 1))

    def _scan_peer_heartbeats(self, force: bool = False) -> set:
        """Original ranks owned by peers whose heartbeat files went stale
        (multi-host only) — plus ranks of peers another SURVIVOR already
        claimed lost for this generation (rendezvous loss files), so
        detection stays coherent across survivors whose beacon scans lag.
        Single-process runs return an empty set. Throttled to the heartbeat
        period (``force`` bypasses — the collective-failure attribution
        path needs a fresh verdict NOW): this runs at every window boundary
        inside the timed epoch, and a fresh listdir + per-file read there
        cannot learn anything a sub-period rescan didn't — while on a slow
        shared filesystem it would bill real I/O stalls to the epoch
        wall."""
        hb_dir = os.environ.get("DBS_PEER_HB_DIR")
        if not hb_dir or self.n_proc == 1:
            return set()
        period_s = float(os.environ.get("DBS_PEER_HB_PERIOD_S", "1.0"))
        now = time.perf_counter()
        cached = self._peer_scan_cache
        if not force and cached is not None and now - cached[0] < period_s:
            return cached[1]
        from dynamic_load_balance_distributeddnn_tpu.runtime.health import (
            ProcessHeartbeat,
        )

        stale_s = float(os.environ.get("DBS_PEER_HB_STALE_S", "10.0"))
        down: set = set()
        scan = ProcessHeartbeat.scan(hb_dir)
        claimed = (
            self._rdzv.claimed_losses() if self._rdzv is not None else set()
        )
        for p in self._proc_roster:
            if p == self._orig_proc_id:
                continue
            if p in claimed:
                # another survivor's published verdict: adopt it instead of
                # dispatching one more collective against the dead process
                down.update(self._ranks_of_proc(p))
                continue
            info = scan.get(f"proc{p}")
            if info is None:
                continue
            if ProcessHeartbeat.is_stale(info, stale_s):
                self.logger.warning(
                    f"elastic: peer process {p} unreachable "
                    f"({ProcessHeartbeat.stale_reason(info)})"
                )
                down.update(self._ranks_of_proc(p))
        self._peer_scan_cache = (now, down)
        return down

    def _check_health(self, epoch: int, frac: float = 0.0) -> None:
        """One liveness round over the active fleet, at epoch-time
        ``epoch + frac`` (window boundaries during the elastic epoch, 0.0
        at epoch start). A worker scheduled down by the preemption
        injector — or owned by a stale peer process — accrues a miss;
        ``detect_misses`` consecutive misses raise :class:`WorkerLost` and
        the run loop enters the recovery path."""
        if self.cfg.elastic != "on":
            return
        t = float(epoch) + min(max(frac, 0.0), 0.999)
        down: set = set()
        down_workers = getattr(self.injector, "down_workers", None)
        if down_workers is not None:
            down = set(down_workers(t))
        down |= self._scan_peer_heartbeats()
        confirmed = []
        for r in self.active_ranks:
            if r in down:
                if self._detect_t0 is None:
                    self._detect_t0 = time.perf_counter()  # first miss seen
                if self.health.report_miss(r):
                    confirmed.append(r)
                    self._lost_t[r] = t
            else:
                self.health.report_alive(r)
        # a DROPPED worker (no longer active) that stops reading as down —
        # its process heartbeat resumed, its injector outage ended — is
        # signalling again: LOST -> RECOVERING, picked up by _maybe_readmit
        # at the next epoch boundary. Without this, only injector-scheduled
        # rejoins could ever readmit (active-rank loops never see the rank).
        # Gated on t >= the confirmed loss time: the recovery path RE-RUNS
        # the epoch, so these rounds re-visit schedule times from before the
        # loss, where "not down" is history, not a recovery.
        for r in self.health.lost():
            if (
                r not in down
                and r not in self.active_ranks
                and t >= self._lost_t.get(r, -1.0)
            ):
                self.health.report_alive(r)
        if not any(r in down for r in self.active_ranks) and not confirmed:
            self._detect_t0 = None
        if confirmed:
            raise WorkerLost(confirmed)

    def _run_epoch_elastic_world(self, epoch: int) -> Dict[str, float]:
        """One epoch under elasticity: readmit recovered workers at the
        boundary, snapshot the consistent state, and on a confirmed loss
        recover and RE-RUN the epoch over the survivors (the snapshot makes
        the re-run exact — no example is half-applied)."""
        self._maybe_readmit(epoch)
        while True:
            self._snapshot_epoch_state()
            try:
                return self.run_epoch(epoch)
            except WorkerLost as e:
                if self._recoveries >= self.cfg.elastic_max_recoveries:
                    self.logger.error(
                        f"elastic: recovery budget exhausted "
                        f"({self._recoveries}) — giving up"
                    )
                    raise
                self._recover(e.ranks, epoch)
            except Exception as e:  # noqa: BLE001 — attributed or re-raised
                # multi-host: a peer dying MID-collective surfaces as the
                # collective's error (closed socket) long before any window-
                # boundary health check runs — attribute it to the peer
                # verdict before treating it as fatal
                lost = self._attribute_collective_failure(e, epoch)
                if lost is None:
                    raise
                if self._recoveries >= self.cfg.elastic_max_recoveries:
                    self.logger.error(
                        f"elastic: recovery budget exhausted "
                        f"({self._recoveries}) — giving up"
                    )
                    raise
                self.logger.warning(
                    f"elastic: dispatch failure attributed to lost "
                    f"worker(s) {lost} — recovering"
                )
                self._recover(lost, epoch)

    def _snapshot_epoch_state(self) -> None:
        """Host-copy of the TrainState + controller vectors at the epoch
        boundary — the 'last consistent state' recovery resumes from. A
        HOST copy is mandatory: the hot-path executables donate the state
        buffers, so a device-side reference would be invalidated by the
        very epoch the snapshot exists to undo. One copy per epoch is the
        price of elasticity (only paid with elastic on)."""
        self._epoch_snap = {
            "state": self._state_to_host(self.state),
            "shares": self.shares.copy(),
            "node_times": self.node_times.copy(),
            "per_example_cost": self.per_example_cost.copy(),
            "active": list(self.active_ranks),
            "total_wallclock": self.total_wallclock,
            "total_probe_s": self.total_probe_s,
        }

    def _state_to_host(self, state) -> tuple:
        """(leaves, treedef) with each leaf as (owned numpy copy,
        committed?, weak_type?). Committed-ness and weak types are part of
        the pjit signature (see _warm_superstep_shapes) — dropping them
        would fork fresh compiled variants of every state-fed executable
        after a recovery."""
        leaves, treedef = jax.tree_util.tree_flatten(state)
        host = [
            (
                np.array(x, copy=True),
                bool(getattr(x, "_committed", True)),
                bool(getattr(x, "weak_type", False)),
            )
            for x in leaves
        ]
        return host, treedef

    def _state_from_host(self, snap: tuple):
        """Rebuild the TrainState from a host snapshot onto the CURRENT
        mesh. Replicated leaves re-place directly; with shard_update on,
        the flat 1/N optimizer chunks re-chunk for the (possibly changed)
        survivor mesh STRAIGHT from the host arrays — unpad to the true
        parameter count, re-pad to the new device-count multiple
        (:attr:`_zero1_padded`, set by _reshard_world), place 1/N-sharded
        (the host-side all_gather→re-split of the reshard boundary; the
        snapshot already materialized the full vector). Placing them
        replicated first would transiently hold the FULL optimizer state
        on every device — the exact memory shard_update exists to avoid.
        The generation-keyed AOT registry (``_aot_gen`` in every key)
        guarantees no stale zero-1 executable can resolve against the
        re-chunked layout."""
        host, treedef = snap
        sh = replicated_sharding(self.mesh)
        chunk_idx: set = set()
        chunked_sh = None
        total = new_padded = 0
        if self.cfg.shard_update:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
                zero1_chunk_axes,
            )

            # identify the flat-init chunk vectors by TREE POSITION (the
            # opt_state subtree) + the leading-dim convention of
            # state.py shard_optimizer_state — scalars/hyperparams are 0-d
            idx_tree = jax.tree_util.tree_unflatten(
                treedef, list(range(len(host)))
            )
            total = int(
                sum(host[i][0].size
                    for i in jax.tree_util.tree_leaves(idx_tree.params))
            )
            new_padded = self._zero1_padded
            chunk_idx = {
                i
                for i in jax.tree_util.tree_leaves(idx_tree.opt_state)
                if host[i][0].ndim >= 1 and host[i][0].shape[0] >= total
            }
            chunked_sh = NamedSharding(
                self.mesh, P(zero1_chunk_axes(self.mesh))
            )
        leaves = []
        for i, (val, committed, weak) in enumerate(host):
            if i in chunk_idx:
                v = val[:total]
                v = np.pad(
                    v, [(0, new_padded - total)] + [(0, 0)] * (v.ndim - 1)
                )
                leaves.append(jax.device_put(jnp.array(v, copy=True), chunked_sh))
                continue
            if weak and val.ndim == 0:
                leaf = jnp.asarray(val.item())
            else:
                # FORCED copy into a jax-owned buffer: the CPU backend can
                # zero-copy a numpy array (jnp.asarray/device_put alias its
                # memory), and the hot-path executables DONATE these leaves
                # — donation of an aliased buffer frees memory the snapshot
                # still owns (observed: nan values + double-free after the
                # first post-restore epoch)
                leaf = jnp.array(val, copy=True)
            if committed:
                if self.n_proc > 1:
                    # collective-free placement: device_put to a
                    # non-fully-addressable sharding runs assert_equal's
                    # hidden gloo broadcast, and the multi-host recovery /
                    # grow paths run ASYMMETRIC code across processes — an
                    # unmatched broadcast there pairs with the wrong
                    # collective on the peer. Every process holds the
                    # identical host snapshot, so assembling from local
                    # per-device copies is exact.
                    leaf = jax.make_array_from_single_device_arrays(
                        leaf.shape,
                        sh,
                        [
                            jax.device_put(leaf, d)
                            for d in sh.addressable_devices
                        ],
                    )
                else:
                    leaf = jax.device_put(leaf, sh)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _resolve_topology_tree(self, mesh_devices):
        """Resolve the combine's TopologyTree over ``mesh_devices``:
        declared (--hier_levels), else the two-level host/device split
        (real process topology or the synthetic --hier_hosts count).
        Returns ``(tree or None, learn)`` where ``learn`` says the
        operator asked for the probe-driven level merge ("learned"
        prefix)."""
        from dynamic_load_balance_distributeddnn_tpu.parallel.topology import (
            TopologyTree,
        )

        cfg = self.cfg
        spec = cfg.hier_levels.strip()
        learn = False
        if spec == "learned" or spec.startswith("learned,"):
            learn = True
            spec = spec[len("learned"):].lstrip(",")
        tree = None
        if spec:
            tree = TopologyTree.declared(spec, len(mesh_devices))
            if tree is None:
                self.logger.warning(
                    f"hier_levels={spec!r} does not factor "
                    f"{len(mesh_devices)} devices — trying the two-level "
                    "host/device split"
                )
        if tree is None:
            tree = TopologyTree.from_process_topology(
                mesh_devices, requested=cfg.hier_hosts
            )
        return tree, learn

    def _learn_tree_from_probe(self, mesh_devices) -> None:
        """Probe-driven level merge (--hier_levels learned...): collapse
        adjacent tree levels whose measured link rates are the same class,
        rebuild the mesh on the merged tree, and RE-PROBE it so
        ``_link_bw``'s per-level rates align with the final structure (the
        per-hop codec choice and the gate verdict read them). A merge down
        to one level means the fabric is symmetric — fall back flat."""
        # mesh rebuild below: drain any concurrent topology readers first
        # (G019 quiesce discipline; a no-op at __init__ time, when this
        # runs before the first pipeline exists)
        self._quiesce_pipeline()
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
            data_mesh,
            probe_link_bandwidth,
            tree_mesh,
        )
        from dynamic_load_balance_distributeddnn_tpu.parallel.topology import (
            TopologyTree,
        )

        rates = (self._link_bw or {}).get("level_bytes_per_s")
        if not rates or len(rates) != len(self._topo_tree.levels):
            return
        merged = TopologyTree.learned(self._topo_tree, rates)
        if merged is None:
            self.logger.warning(
                "hier_levels=learned: every level measured as the same "
                "link class (symmetric fabric) — falling back to the flat "
                "combine"
            )
            self.grad_comm = "flat"
            self._hier_hosts = 0
            self._topo_tree = None
            self._probe_gated_flat = True
            self.mesh = data_mesh(mesh_devices)
            return
        if merged.levels != self._topo_tree.levels:
            self.logger.info(
                f"hier_levels=learned: merged {self._topo_tree.levels} "
                f"-> {merged.levels} from measured link rates"
            )
            self._topo_tree = merged
            self._hier_hosts = merged.sizes[0]
            self.mesh = tree_mesh(mesh_devices, merged.names, merged.sizes)
            self._link_bw = probe_link_bandwidth(
                self.mesh, gate_ratio=self.cfg.dcn_probe_gate
            )
            heartbeat()

    def _resolve_wires(self) -> tuple:
        """Per-hop wire codecs for the CURRENT tree, outermost hop first —
        one entry per mesh level, innermost fp32. Sources, in order:
        explicit --grad_comm_wires list (must match the level count),
        "auto" (parallel/wire.py choose_wires over the probe's measured
        per-level rates), else the legacy default (--grad_comm_wire on the
        outermost hop, fp32 below)."""
        if self.grad_comm != "hier":
            return ()
        cfg = self.cfg
        sizes = self._topo_tree.sizes
        k = len(sizes)
        spec = cfg.grad_comm_wires.strip()
        if spec == "auto":
            rates = (self._link_bw or {}).get("level_bytes_per_s")
            if rates and len(rates) == k:
                from dynamic_load_balance_distributeddnn_tpu.parallel.wire import (
                    choose_wires,
                )

                wires = choose_wires(sizes, rates)
                self.logger.info(
                    f"grad_comm_wires=auto: {dict(zip(self._topo_tree.names, wires))} "
                    "from measured link rates"
                )
                return wires
            self.logger.warning(
                "grad_comm_wires=auto needs the bandwidth probe's "
                "per-level rates (single-process probe) — using the "
                "legacy default"
            )
            spec = ""
        if spec:
            wires = tuple(w.strip() for w in spec.split(","))
            if len(wires) == k:
                return wires
            self.logger.warning(
                f"grad_comm_wires={spec!r} has {len(wires)} entries but "
                f"the resolved tree has {k} levels — using the legacy "
                "default"
            )
        return (cfg.grad_comm_wire,) + ("fp32",) * (k - 1)

    def _compute_comm_sig(self) -> tuple:
        """AOT-key / plan-layout signature of the combine structure (see the
        __init__ comment) — recomputed on every fleet change: an elastic
        re-shard can re-derive the tree or fall back to flat, and two
        structures lower different programs that must never resolve to each
        other. The hier signature is the full tree with each hop's wire:
        one (name, size, wire) triple per level, outermost first."""
        return (
            (
                ("hier",)
                + tuple(
                    (name, size, wire)
                    for (name, size), wire in zip(
                        self._topo_tree.levels, self._grad_comm_wires
                    )
                )
                if self.grad_comm == "hier"
                else ("flat",)
            )
            + (("zero1",) if self.cfg.shard_update else ())
            # many-stream tenancy: the job id namespaces every comm-sig-keyed
            # executable per tenant (the _aot_gen component stays per-trainer)
            + ((("job", self.job_id),) if self.job_id is not None else ())
        )

    def _quiesce_pipeline(self) -> None:
        """Drain the concurrent readers of the topology fields before a
        mesh/world rebuild (G019 quiesce discipline). The window transfer
        pipeline's gather/stage threads read ``mesh``/``topology``/
        ``active_ranks``; "closed by program order" was the sanction for
        the unlocked writes below, and this turns that program-order
        argument into an enforced drain: if an abandoned epoch left its
        pipeline live (exception paths, mid-epoch preemption), close it —
        ``close`` joins the pool and is idempotent against the context
        manager's own exit."""
        pipe = getattr(self, "_live_pipeline", None)
        if pipe is not None:
            self._live_pipeline = None
            pipe.close()

    def _reshard_world(self, active: List[int]) -> None:
        """Point the engine at a new active fleet: compact controller
        vectors, survivor topology/mesh, a fresh StepLibrary against it,
        and every mesh/topology-keyed cache invalidated. The caller re-
        places the TrainState afterwards (`_state_from_host`). Multi-host:
        called AFTER a re-rendezvous re-initialized ``jax.distributed``
        over the survivor roster — ``jax.devices()`` is already the new
        global fleet and ``proc_id``/``n_proc``/``_proc_roster`` its
        compact shape; each surviving process keeps its own worker slice
        (loss is process-granular across hosts)."""
        self._quiesce_pipeline()
        cfg = self.cfg
        self.active_ranks = sorted(int(r) for r in active)
        # topology fields below are read by the pipeline's gather/stage
        # threads (G012 would flag the unlocked cross-thread writes); the
        # _quiesce_pipeline() drain above guarantees no staging thread is
        # alive across these statements (G019) — previously this relied on
        # the run loop having drained the epoch, unasserted
        self.world_size = len(self.active_ranks)  # graftlint: disable=G012
        if self.world_size < 1:
            raise RuntimeError("elastic: no surviving workers")
        local_devices = sorted(jax.local_devices(), key=lambda d: d.id)
        ids_global = cfg.worker_device_ids(len(local_devices))
        if self.n_proc > 1:
            # my workers: the slice of ORIGINAL ranks this process owned at
            # gen 0 (whole peers die; survivors keep their full slice).
            # Compact runtime ranks are positions in sorted(active), and my
            # originals are contiguous there — roster order (sorted original
            # ids) matches original-rank order by construction.
            mine = [
                r for r in self.active_ranks
                if self._proc_of_rank(r) == self._orig_proc_id
            ]
            if not mine:
                raise RuntimeError(
                    "elastic: this process owns no surviving workers"
                )
            self.ws_local = len(mine)  # graftlint: disable=G012
            self.rank_lo = self.active_ranks.index(mine[0])  # graftlint: disable=G012
            ids_local = [ids_global[r] for r in mine]
            used = sorted(set(ids_local))
            self.topology = WorkerTopology.build(
                self.ws_local,
                [local_devices[i] for i in used],
                [used.index(i) for i in ids_local],
            )
            # global combine mesh: every surviving process contributes the
            # same local device ordinals (symmetry validated at __init__),
            # ordered by the NEW process index — which is roster order
            by_proc: Dict[int, list] = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, []).append(d)
            mesh_devices = []
            for p in sorted(by_proc):
                proc_devs = sorted(by_proc[p], key=lambda d: d.id)
                mesh_devices.extend(proc_devs[i] for i in used)
        else:
            self.ws_local = self.world_size  # graftlint: disable=G012
            self.rank_lo = 0  # graftlint: disable=G012
            ids_active = [ids_global[r] for r in self.active_ranks]
            used = sorted(set(ids_active))
            self.topology = WorkerTopology.build(
                self.world_size,
                [local_devices[i] for i in used],
                [used.index(i) for i in ids_active],
            )
            mesh_devices = list(self.topology.devices)
        # hier×elastic (ISSUE 14 satellite, tree-aware since ISSUE 17):
        # re-derive the topology tree over the survivors so elastic runs
        # KEEP whatever hierarchy remains — TopologyTree.restrict walks
        # the previous tree keeping every level that still divides the
        # fleet (the old all-or-nothing equal-host-blocks-or-flat
        # fallback is the degenerate case); on real multi-host fleets the
        # host level re-derives from the SURVIVING process topology
        # instead (the host axis must align with real process blocks).
        # Otherwise fall back to the flat combine — logged once, and the
        # re-keyed _comm_sig makes the structure change a new
        # compiled-program universe (no hier executable can resolve
        # against a flat world).
        prev_comm = self.grad_comm
        prev_tree = self._topo_tree
        self.grad_comm = "flat"
        self._hier_hosts = 0
        self._topo_tree = None
        if cfg.grad_comm == "hier" and not self._probe_gated_flat:
            from dynamic_load_balance_distributeddnn_tpu.parallel.topology import (
                TopologyTree,
            )

            if self.n_proc > 1 and not cfg.hier_levels:
                tree = TopologyTree.from_process_topology(
                    mesh_devices, requested=0
                )
            elif prev_tree is not None:
                tree = prev_tree.restrict(len(mesh_devices))
            else:
                tree, _ = self._resolve_topology_tree(mesh_devices)
            if tree is not None:
                self.grad_comm = "hier"
                self._topo_tree = tree
                self._hier_hosts = tree.sizes[0]
            else:
                self.logger.warning(
                    f"grad_comm=hier: the {len(mesh_devices)}-device survivor "
                    "fleet keeps no topology-tree structure (fewer than two "
                    "divisible levels) — falling back to the flat combine"
                    + (" (was hier)" if prev_comm == "hier" else "")
                )
        if self.grad_comm == "hier":
            from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
                tree_mesh,
            )

            self.mesh = tree_mesh(
                mesh_devices, self._topo_tree.names, self._topo_tree.sizes
            )
        else:
            self.mesh = data_mesh(mesh_devices)
        self.n_dev = len(mesh_devices)
        self._grad_comm_wires = self._resolve_wires()
        self._comm_sig = self._compute_comm_sig()
        if cfg.shard_update:
            # the 1/N optimizer chunk layout is sized by the DEVICE count:
            # a survivor fleet re-pads the flat state to its own multiple
            # (the _state_from_host re-chunk consumes this)
            from dynamic_load_balance_distributeddnn_tpu.train.state import (
                zero1_padded_size,
            )

            self._zero1_padded = zero1_padded_size(
                self.state.params, self.n_dev
            )
        self._build_steps()
        # mesh/topology-keyed caches: all stale the moment the fleet changed
        self._aot_gen += 1
        self._aot_view_specs = {}
        self._peer_scan_cache = None
        self._cache_repl = None
        self._cache_dev = {}
        self._eval_chunk_cache = None
        self._fused_sync_per_step = None
        self._flops_per_padded_example = None
        self._seen_plan_layouts = set()
        self._superstep_keys = set()
        self._sync_per_step = 0.0
        self.timekeeper = TimeKeeper(self.world_size)
        # world-size change: the share trajectory restarts (the predictor
        # would restart its velocity track on shape change anyway; a fresh
        # instance makes it explicit)
        self._share_predictor = ShareTrajectoryPredictor()
        # the online controller's per-worker rate track and device-group
        # step-time model are fleet-shaped: rebuilt lazily against the
        # survivor topology (ledger restarts — a new fleet, a new account;
        # executed-switch events stay in self._rebalance_events). The
        # recorder's per-epoch switch-delta baseline restarts with it, or
        # the first post-reshard epoch would record a negative delta.
        self._rebalance_ctl = None
        self.obs.controller = None  # registry slot follows the rebuild
        self._switches_last = 0
        # warm-started runs re-warm the NEW world size's compile universe:
        # _maybe_warm (next epoch entry) submits the gen's ladder to the
        # AOT service and the pre-wall drain keeps the compiles out of
        # every timed epoch — zero steady-state foreground compiles
        # survive the re-solve
        self._warmed = False

    def _recover(self, lost: List[int], epoch: int) -> None:
        """Confirmed worker loss: drain, flush checkpoints, re-solve the
        partition over the survivors, re-shard, re-place the snapshot
        state, and hand control back to the run loop (which re-runs the
        epoch). Collective/compile edges are wrapped in bounded
        exponential-backoff retries — a re-shard can race the dying
        runtime's teardown."""
        if self.n_proc > 1:
            if self._rdzv is None:
                raise RuntimeError(
                    f"elastic: worker(s) {lost} lost but no rendezvous "
                    "channel is armed (set DBS_PEER_HB_DIR and bring the "
                    "world up through rendezvous.elastic_initialize) — "
                    "aborting for resume-from-checkpoint (see README "
                    "'Fault tolerance')"
                )
            if all(self._proc_of_rank(r) == self._orig_proc_id for r in lost):
                # a loss confined to THIS process's own workers: peers see
                # a live beacon and no claim, so they would never enter the
                # rendezvous — proposing one just wedges the fleet for the
                # full phase timeout. Abort with the honest verdict
                # instead (resume-from-checkpoint restarts the fleet).
                raise RuntimeError(
                    f"elastic: worker(s) {sorted(lost)} on THIS process "
                    "confirmed lost in a multi-process world — a "
                    "single-process worker shrink cannot change the "
                    "global mesh and no peer would join a rendezvous for "
                    "it; aborting for resume-from-checkpoint"
                )
            return self._recover_multihost(lost, epoch)
        cfg = self.cfg
        t0 = self._detect_t0 or time.perf_counter()
        snap = self._epoch_snap
        with self._trace.span("recover", cat="recover"):
            self._trace.instant(
                "worker_lost", cat="elastic",
                args={"ranks": sorted(int(r) for r in lost), "epoch": int(epoch)},
            )
            self.logger.warning(
                f"elastic: worker(s) {sorted(lost)} confirmed lost at epoch "
                f"{epoch} — re-solving over survivors"
            )
            if cfg.ckpt_dir:
                # durable BEFORE the re-shard mutates the fleet: a crash
                # mid-recovery must leave a consistent checkpoint behind
                from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
                    flush_checkpoints,
                )

                flush_checkpoints(cfg.ckpt_dir)
                heartbeat()
            for r in lost:
                self.health.mark_down(r)
            prev_active = snap["active"] if snap else list(self.active_ranks)
            survivors = [r for r in prev_active if r not in set(lost)]
            keep = [i for i, r in enumerate(prev_active) if r not in set(lost)]
            retry_transient(
                lambda: self._reshard_world(survivors),
                logger=self.logger,
                desc="survivor re-shard",
                tick=heartbeat,
            )
            if snap is not None:
                # restore the epoch-start controller state, restricted to
                # survivors: shares renormalize (the re-solve seed), cost
                # anchors carry over (they are per-worker, not per-fleet)
                shares = snap["shares"][keep]
                self.shares = shares / max(shares.sum(), 1e-12)
                self.node_times = snap["node_times"][keep]
                self.per_example_cost = snap["per_example_cost"][keep]
                self.total_wallclock = snap["total_wallclock"]
                self.total_probe_s = snap["total_probe_s"]
                self.state = retry_transient(
                    lambda: self._state_from_host(snap["state"]),
                    logger=self.logger,
                    desc="state re-placement",
                    tick=heartbeat,
                )
            else:  # driven epoch-by-epoch without run(): best effort
                sel = [i for i, r in enumerate(prev_active) if r in survivors]
                shares = self.shares[sel]
                self.shares = shares / max(shares.sum(), 1e-12)
                self.node_times = self.node_times[sel]
                self.per_example_cost = self.per_example_cost[sel]
                self.state = self._state_from_host(self._state_to_host(self.state))
            self._fix_comm_residual()
            jax.block_until_ready(self.state.params)
            heartbeat()  # survivor mesh answered — recovery pipeline is live
            self._recoveries += 1
            self._detect_t0 = None
            dt = time.perf_counter() - t0
            ev = {
                "epoch": int(epoch),
                "lost": sorted(int(r) for r in lost),
                "world_size": int(self.world_size),
                "detect_to_resume_s": round(dt, 4),
            }
            self._elastic_events.append(ev)
            self.recorder.meta["elastic_events"] = self._elastic_events
            self._trace.instant("recovered", cat="elastic", args=dict(ev))
            self.logger.info(
                f"elastic: recovered over {self.world_size} survivors "
                f"{self.active_ranks} in {dt:.3f}s (detection to resumed "
                "training); epoch re-runs from the consistent snapshot"
            )

    # ------------------------------------------ multi-host re-rendezvous
    # (ISSUE 14). jax cannot shrink a live multi-host mesh, so surviving a
    # peer-PROCESS loss means rebuilding the world: survivors reach roster
    # consensus through the heartbeat-file directory (propose -> agree),
    # tear down ``jax.distributed`` (retiring the old runtime — see
    # runtime/rendezvous.py for why the retired objects deliberately leak),
    # re-initialize over the survivor set on a fresh coordinator port
    # (barrier -> establish), re-shard topology/mesh/StepLibrary onto the
    # survivor fleet, restore from the flushed checkpoint re-placed onto the
    # survivor mesh, and re-run the interrupted epoch — bitwise-identical to
    # a fresh reduced-world run from the same checkpoint. A failed or
    # timed-out rendezvous degrades to the pre-ISSUE-14 abort-and-resume
    # ladder, logged with the phase that died.

    def _fix_comm_residual(self) -> None:
        """Re-base the error-feedback residual on the CURRENT combine
        structure after a fleet change: the old world's ``[n_dev, chunk]``
        rows are meaningless on a different device count (and their stale
        shape would fork every state-fed executable signature), so a hier
        survivor mesh re-attaches zeros — error feedback re-accumulates
        within an epoch — and a re-factor that fell back to flat drops the
        leaf entirely."""
        st = self.state
        if getattr(st, "comm_residual", None) is None and self.grad_comm != "hier":
            return
        st = st.replace(comm_residual=None)
        if self.grad_comm == "hier":
            from dynamic_load_balance_distributeddnn_tpu.train.state import (
                attach_comm_residual,
            )

            st = attach_comm_residual(
                st, self.mesh,
                pad_multiple=self.n_dev if self.cfg.shard_update else 0,
            )
        self.state = st

    def _adopt_controller_vectors(
        self, base_active, shares, node_times, cost=None
    ) -> None:
        """Seed the compact controller vectors for the CURRENT active fleet
        from a PREVIOUS fleet's vectors (checkpoint sidecar or epoch
        snapshot): survivors keep their entries, newcomers fill with the
        survivor mean, shares renormalize. Pure function of
        (source vectors, rosters), so every surviving process — and a
        freshly joined one reading the same sidecar — derives the identical
        seed (the replicated-controller contract across a fleet change)."""
        base = [int(r) for r in base_active]
        sel = {r: i for i, r in enumerate(base)}

        def fill(vec, fallback):
            src = np.asarray(vec, dtype=np.float64)
            out = np.full(self.world_size, np.nan)
            for i, r in enumerate(self.active_ranks):
                if r in sel and sel[r] < len(src):
                    out[i] = src[sel[r]]
            mean = np.nanmean(out) if np.isfinite(out).any() else fallback
            out[~np.isfinite(out)] = mean
            return out

        sh = fill(shares, 1.0 / max(self.world_size, 1))
        self.shares = sh / max(sh.sum(), 1e-12)
        self.node_times = np.maximum(fill(node_times, 1.0), 1e-9)
        if cost is not None:
            self.per_example_cost = fill(cost, np.nan)
        else:
            self.per_example_cost = np.full(self.world_size, np.nan)

    def _mh_rdzv_failed(self, e: Exception, epoch: int) -> None:
        """A rendezvous phase died (hard timeout, eviction, connect
        failure): degrade to the pre-ISSUE-14 abort-and-resume ladder —
        loudly. The beacon file is tagged with the failed phase so peers
        (and the launching harness) diagnose the abort instead of reading a
        silent freeze, the event lands in the recorder meta, and the raise
        unwinds the run for the outer retry/resume loop."""
        phase = getattr(e, "phase", "unknown")
        msg = (
            f"elastic: multi-host re-rendezvous FAILED in phase "
            f"'{phase}' ({e}) — degrading to abort-and-resume-from-"
            "checkpoint"
        )
        self.logger.error(msg)
        self._trace.instant(
            "rdzv_failed", cat="rdzv",
            args={"phase": str(phase), "epoch": int(epoch)},
        )
        if self._hb_beacon_path:
            from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
                tag_exit_reason,
            )

            tag_exit_reason(
                self._hb_beacon_path, f"rendezvous failed: {phase}"
            )
        self._elastic_events.append(
            {"epoch": int(epoch), "rdzv_failed_phase": str(phase)}
        )
        self.recorder.meta["elastic_events"] = self._elastic_events
        raise RuntimeError(msg) from e

    def _recover_multihost(self, lost: List[int], epoch: int) -> None:
        """Confirmed PEER-PROCESS loss on the multi-host tier: publish the
        loss verdict (peers with lagging beacon scans adopt it instead of
        dispatching another collective at the dead process), then run the
        epoch-boundary re-rendezvous over the survivors."""
        cfg = self.cfg
        if cfg.shard_update:
            # recorded exclusion: re-chunking the 1/N optimizer state across
            # a multi-host re-rendezvous needs a sharded process-local
            # restore path the engine does not build yet (ROADMAP)
            raise RuntimeError(
                f"elastic: worker(s) {sorted(lost)} lost but multi-host "
                "re-rendezvous does not compose with --shard_update yet — "
                "aborting for resume-from-checkpoint"
            )
        dead_procs = sorted(
            {self._proc_of_rank(r) for r in lost}
            - {self._orig_proc_id}
        )
        self.logger.warning(
            f"elastic: worker(s) {sorted(lost)} (peer process(es) "
            f"{dead_procs}) confirmed lost at epoch {epoch} — "
            "re-rendezvousing over survivors"
        )
        self._trace.instant(
            "peer_lost", cat="elastic",
            args={
                "ranks": sorted(int(r) for r in lost),
                "procs": [int(p) for p in dead_procs],
                "epoch": int(epoch),
            },
        )
        for r in lost:
            self.health.mark_down(r)
        self._rdzv.claim_loss(dead_procs, epoch)
        survivors = [r for r in self.active_ranks if r not in set(lost)]
        self._mh_rerendezvous(epoch, survivors, lost=sorted(lost))

    def _maybe_regrow_multihost(self, epoch: int) -> None:
        """Epoch-boundary grow: (re)spawned processes that offered to join
        (``join_p*.json`` + a fresh beacon) are admitted by re-running the
        same rendezvous with them in the roster. Every process publishes its
        own ranks' carried per-example costs into the rendezvous probe
        exchange before the restore barrier, so newcomers seed at the
        equilibrium share of the exchanged costs (falling back to the
        sidecar-derived mean fill when the exchange is incomplete); their
        engine restores from the shared checkpoint and adopts the agreed
        fleet."""
        if self._rdzv is None:
            return
        alive = self._rdzv.alive_procs()
        joins = sorted(
            p
            for p in self._rdzv.pending_joins()
            if p in alive and p not in set(self._proc_roster)
        )
        if not joins:
            return
        if not self.cfg.ckpt_dir:
            # the joiner's ONLY state source is the shared checkpoint (the
            # survivors restore the same bytes so the grown world stays
            # replicated) — admitting one without a ckpt_dir would psum
            # fresh-init params against the trained ones, silently
            # diverging every process. Refuse loudly, once per epoch.
            self.logger.warning(
                f"elastic: process(es) {joins} offered to join at epoch "
                f"{epoch} but no --ckpt_dir is configured — a joiner "
                "cannot adopt the replicated state; refusing the grow"
            )
            return
        self.logger.info(
            f"elastic: process(es) {joins} offering to join at epoch "
            f"{epoch} — re-rendezvousing to grow the fleet"
        )
        active = sorted(
            set(self.active_ranks)
            | {r for p in joins for r in self._ranks_of_proc(p)}
        )
        self._mh_rerendezvous(epoch, active, joining=joins)
        for p in joins:
            self._rdzv.clear_join(p)

    def _mh_rerendezvous(
        self,
        epoch: int,
        target_active: List[int],
        lost: Sequence[int] = (),
        joining: Sequence[int] = (),
    ) -> None:
        """The shared shrink/grow spine: drain -> flush -> agree -> retire
        -> establish -> re-shard -> restore -> re-seed. Every blocking phase
        is armored (bounded timeouts in the state machine, retry_transient
        on the collective edges, heartbeat ticks throughout), and a failed
        phase degrades through :meth:`_mh_rdzv_failed` instead of hanging."""
        from dynamic_load_balance_distributeddnn_tpu.runtime import (
            rendezvous as rdzv,
        )
        from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
            flush_checkpoints,
            materialize,
            restore_checkpoint,
        )

        cfg = self.cfg
        t0 = self._detect_t0 or time.perf_counter()
        with self._trace.span("recover_mh", cat="recover"):
            # 1. durable checkpoint, manager CLOSED: the cached orbax
            # manager's async machinery holds old-world device arrays and
            # must drain and die before the runtime is retired under it
            if cfg.ckpt_dir:
                flush_checkpoints(cfg.ckpt_dir, close=True)
                heartbeat()
            # 2. host-side recovery source. Shrink resumes the interrupted
            # epoch from its START snapshot (== the flushed checkpoint);
            # grow runs at a boundary, so the LIVE state is the source.
            snap = self._epoch_snap if not joining else None
            if snap is not None:
                host_state = snap["state"]
                prev_active = list(snap["active"])
                src = {
                    "shares": snap["shares"],
                    "node_times": snap["node_times"],
                    "cost": snap["per_example_cost"],
                }
                self.total_wallclock = snap["total_wallclock"]
                self.total_probe_s = snap["total_probe_s"]
            else:
                host_state = self._state_to_host(self.state)
                prev_active = list(self.active_ranks)
                src = {
                    "shares": self.shares.copy(),
                    "node_times": self.node_times.copy(),
                    "cost": self.per_example_cost.copy(),
                }
            # 3. roster consensus (propose -> agree): bounded rounds, hard
            # per-phase timeout, watchdog ticks — a wedged peer times the
            # rendezvous out instead of hanging it
            try:
                agreement = self._rdzv.agree(
                    lambda: (
                        self._rdzv.alive_procs() - self._rdzv.claimed_losses()
                    ),
                    epoch,
                )
            except rdzv.RendezvousError as e:
                self._mh_rdzv_failed(e, epoch)
            roster = list(agreement.roster)
            # the agreed roster is authoritative: drop ranks whose process
            # died DURING the rendezvous, admit one that raced its join in
            active = [
                r for r in target_active if self._proc_of_rank(r) in set(roster)
            ]
            for p in roster:
                if all(self._proc_of_rank(r) != p for r in active):
                    active.extend(self._ranks_of_proc(p))
            active = sorted(set(active))
            # 4. quiesce every device-holding surface, then retire the old
            # runtime (client/service leak deliberately — rendezvous.py)
            if self._aot is not None:
                try:
                    self._aot.close(wait=True)
                except Exception as e:  # noqa: BLE001 — a dying pool must not block recovery
                    self.logger.warning(
                        f"elastic: AOT service close failed ({e!r}) — "
                        "continuing recovery"
                    )
                self._aot = None
            self.state = None
            self._cache_repl = None
            self._cache_dev = {}
            self._epoch_snap = None  # re-snapshotted when the epoch re-runs
            # force the dying world's wedged collectives to resolve BEFORE
            # the new world exists — unresolved, they poison the next
            # backend's launches through XLA:CPU's process-global
            # rendezvous map (see rendezvous.drain_collective_chain)
            rdzv.drain_collective_chain(logger=self.logger, tick=heartbeat)
            rdzv.retire_runtime()
            # 5. barrier on every survivor's teardown, leader brings up the
            # new coordination service, everyone connects
            try:
                # the payload is for JOINERS (join_elastic_world returns it);
                # survivors are replicated-deterministic and ignore it
                self._rdzv.establish(
                    agreement,
                    payload=(
                        {"epoch": int(agreement.epoch), "active": active}
                        if agreement.leader
                        else None
                    ),
                )
            except rdzv.RendezvousError as e:
                self._mh_rdzv_failed(e, epoch)
            # 6. adopt the new world shape; rebuild the compile service and
            # every topology/mesh surface against it. The whole rebuild tail
            # runs under a bounded retry: the dead world's wedged collective
            # resolves at an ARBITRARY later moment (gloo socket teardown is
            # async), and whatever multi-device dispatch is in flight right
            # then inherits its error — the canary (quarantine_runtime)
            # catches an inheritance that already landed, the final
            # block_until_ready catches one that landed mid-rebuild, and a
            # poisoned attempt tears the backend down and rebuilds from
            # scratch (cheap: ~0.3s on the CPU tier). With MULTIPLE
            # survivors each attempt is a voted round (ISSUE 18:
            # rdzv.rebuild_vote / rebuild_settled): every survivor
            # publishes its verdict and the round only stands when all
            # succeeded — retry counts can no longer diverge across
            # processes, so attempt N's collectives always pair N-to-N.
            self.n_proc = len(roster)
            self.proc_id = agreement.rank
            self._proc_roster = roster
            if joining:
                # grow-path probe exchange (ISSUE 17): publish OUR ranks'
                # carried costs now — BEFORE the restore barrier both sides
                # synchronize on — so every member's publication is on disk
                # by the time anyone collects (step 8 here; the joiner's
                # _maybe_restore publishes its measured costs symmetrically)
                own_costs: Dict[int, float] = {}
                for r in self._ranks_of_proc(self._orig_proc_id):
                    if r in prev_active:
                        own_costs[r] = float(
                            np.asarray(src["cost"])[prev_active.index(r)]
                        )
                self._publish_probe_costs(own_costs)
            restored_from = "epoch snapshot"
            ctl = None
            rebuild_err: Optional[Exception] = None
            # the rebuild-vote electorate: survivors only — joiners enter
            # through join_elastic_world after the survivor world settles
            survivors = [p for p in roster if p not in set(joining)]
            for attempt in range(5):
                try:
                    rdzv.quarantine_runtime(logger=self.logger, tick=heartbeat)
                except rdzv.RendezvousError as e:
                    self._mh_rdzv_failed(e, epoch)
                # a silent async failure in the preceding stage surfaces at
                # the canary instead of poisoning the next stage's launches
                # (local devices only — see rendezvous.local_canary_launch;
                # on the GROW path the joiner runs no matching canary, so a
                # global-mesh put's hidden gloo broadcast would pair with
                # the joiner's first real collective)
                _launch_canary = rdzv.local_canary_launch

                stage = "reshard"
                try:
                    self._reshard_world(active)
                    _launch_canary()
                    # 7. restore: the flushed checkpoint re-placed onto the
                    # survivor mesh (falling back to the epoch-start
                    # snapshot when no checkpoint directory is configured or
                    # the latest step is not the interrupted epoch's
                    # boundary)
                    stage = "template"
                    template = self._state_from_host(host_state)
                    materialize(template)
                    _launch_canary()
                    stage = "restore"
                    self.state = template
                    restored_from = "epoch snapshot"
                    ctl = None
                    # the GROW path restores from the flushed checkpoint
                    # too (identical bytes to the live boundary state): the
                    # JOINER's only state source is that checkpoint, and its
                    # engine restores through the same restore_checkpoint
                    # call — orbax's manager-create/restore syncs are global
                    # collectives, so the survivor must run the SAME
                    # sequence at the same program point or the joiner's
                    # syncs pair with the wrong launch (see _launch_canary)
                    if cfg.ckpt_dir:
                        got = restore_checkpoint(cfg.ckpt_dir, template)
                        if got is not None and int(got[0]) == epoch - 1:
                            self.state, ctl = got[1], got[2]
                            restored_from = f"checkpoint[{int(got[0])}]"
                        elif got is not None:
                            self.logger.warning(
                                f"elastic: latest checkpoint is epoch "
                                f"{got[0]}, not {epoch - 1} — resuming from "
                                "the epoch-start snapshot instead"
                            )
                    _launch_canary()
                    stage = "fix-residual"
                    self._fix_comm_residual()
                    stage = "materialize"
                    # materialize EVERYTHING state-shaped before declaring
                    # the world live — a poisoned buffer must surface here,
                    # inside the retry scope, not an epoch later
                    materialize(self.state)
                    rebuild_err = None
                except Exception as e:  # noqa: BLE001 — poisoned-world rebuild
                    rebuild_err = e
                    self.state = None
                    self._cache_repl = None
                    self._cache_dev = {}
                    self.logger.warning(
                        f"elastic: survivor-world rebuild attempt "
                        f"{attempt + 1} inherited the dead world's dispatch "
                        f"chain at stage '{stage}' ({str(e)[:160]}) — "
                        "rebuilding the backend"
                    )
                    heartbeat()
                    rdzv.reset_backend()
                    # the stuck global-map entries evict when the dead
                    # ops' threads unwind — observed within ~10s; back off
                    # long enough to land past that instead of burning
                    # attempts inside the window
                    time.sleep(1.0 * (attempt + 1))
                # Multi-survivor rebuild coherence: each attempt is a voted
                # round — it stands only when EVERY survivor's rebuild
                # succeeded. Otherwise all of them (the locally-successful
                # ones included) tear down and retry together, so attempt
                # N's collectives always pair N-to-N instead of a fast
                # survivor's attempt-1 ops meeting a slow peer's attempt-2.
                # Joiners don't vote: they enter via join_elastic_world
                # only after the survivor world settles.
                if len(survivors) > 1:
                    round_ok = False
                    try:
                        self._rdzv.rebuild_vote(
                            attempt, ok=rebuild_err is None
                        )
                        round_ok = self._rdzv.rebuild_settled(
                            survivors, attempt
                        )
                    except rdzv.RendezvousError as e:
                        # a peer that exhausted its attempts aborts without
                        # voting — its silence times this wait out, and the
                        # remaining survivors abort coherently with it
                        self._mh_rdzv_failed(e, epoch)
                    if not round_ok and rebuild_err is None:
                        rebuild_err = rdzv.RendezvousError(
                            "world rebuild",
                            f"attempt {attempt + 1} voted down by a peer",
                        )
                        self.state = None
                        self._cache_repl = None
                        self._cache_dev = {}
                        self.logger.warning(
                            f"elastic: rebuild attempt {attempt + 1} "
                            "succeeded locally but a peer voted it down — "
                            "rebuilding in lockstep"
                        )
                        heartbeat()
                        rdzv.reset_backend()
                        time.sleep(1.0 * (attempt + 1))
                if rebuild_err is None:
                    break
            if rebuild_err is not None:
                self._mh_rdzv_failed(
                    rdzv.RendezvousError(
                        "world rebuild", f"never settled: {rebuild_err!r}"
                    ),
                    epoch,
                )
            self._build_aot_service()
            # 8. controller seeding: sidecar vectors when the checkpoint was
            # the source (identical bytes on every process), else the
            # replicated snapshot — restricted to survivors / mean-filled
            # for joiners, shares renormalized
            if (
                ctl
                and "shares" in ctl
                and ctl.get("active_ranks") is not None
                and len(ctl["shares"]) == len(ctl["active_ranks"])
            ):
                self._adopt_controller_vectors(
                    ctl["active_ranks"], ctl["shares"],
                    ctl.get("node_times", ctl["shares"]),
                )
            else:
                self._adopt_controller_vectors(
                    prev_active, src["shares"], src["node_times"], src["cost"]
                )
            # grow path: upgrade the mean-fill seed to the equilibrium split
            # over the exchanged per-worker costs (identical on every
            # process when the exchange completes; the mean-fill above
            # stands — identically everywhere — when it does not)
            if joining:
                self._collect_probe_seed()
            for p in joining:
                for r in self._ranks_of_proc(p):
                    self.health.readmit(r)
            jax.block_until_ready(self.state.params)
            heartbeat()  # survivor world answered — the new mesh is live
            # a rejoined (or previously fired-on) peer needs a fresh watch
            if joining and getattr(self, "_peer_watch", None) is not None:
                self._peer_watch([f"proc{p}" for p in joining])
            self._recoveries += 1
            self._detect_t0 = None
            dt = time.perf_counter() - t0
            ev = {
                "epoch": int(epoch),
                "world_size": int(self.world_size),
                "rdzv_gen": int(agreement.gen),
                "roster": [int(p) for p in roster],
                "detect_to_resume_s": round(dt, 4),
                "restored_from": restored_from,
            }
            if lost:
                ev["lost"] = [int(r) for r in lost]
            if joining:
                ev["readmitted"] = [
                    int(r) for p in joining for r in self._ranks_of_proc(p)
                ]
            self._elastic_events.append(ev)
            self.recorder.meta["elastic_events"] = self._elastic_events
            self._trace.instant("mh_recovered", cat="elastic", args=dict(ev))
            self.logger.info(
                f"elastic: re-rendezvous g{agreement.gen} complete — "
                f"{self.world_size} workers over {self.n_proc} process(es) "
                f"{roster}, state from {restored_from}, {dt:.3f}s detection "
                "to resumed training"
            )

    def _attribute_collective_failure(
        self, e: Exception, epoch: int
    ) -> Optional[List[int]]:
        """A mid-epoch exception on the multi-host elastic tier is usually
        the COLLECTIVE dying with a peer (the gloo/XLA surface errors on the
        closed socket long before the beacon goes stale). Hold the epoch for
        up to the staleness window and let the beacon/claim verdict decide:
        returns the lost ranks to recover over, or None to re-raise (a real
        error, not a fleet change)."""
        if self.cfg.elastic != "on" or self.n_proc == 1 or self._rdzv is None:
            return None
        if self._detect_t0 is None:
            self._detect_t0 = time.perf_counter()
        stale_s = float(os.environ.get("DBS_PEER_HB_STALE_S", "10.0"))
        self.logger.warning(
            f"elastic: epoch {epoch} dispatch failed ({e!r}) — waiting up "
            f"to {stale_s + 3.0:.0f}s for a peer-liveness verdict before "
            "treating it as fatal"
        )
        deadline = time.monotonic() + stale_s + 3.0
        while time.monotonic() < deadline:
            down = self._scan_peer_heartbeats(force=True)
            lost = sorted(r for r in self.active_ranks if r in down)
            if lost:
                return lost
            heartbeat()  # the wait is deliberate, not a stall
            time.sleep(0.25)
        return None

    def _maybe_readmit(self, epoch: int) -> None:
        """Epoch-boundary readmission: workers whose rejoin boundary is
        ``epoch`` (injector schedule) or that resumed signalling (health
        RECOVERING) re-enter the fleet with a PROBE-SEEDED share — one
        standalone step on the readmitted worker anchors its per-example
        cost, and the share vector seeds at the solver's equilibrium
        estimate (share_i ∝ 1/c_i) so the next rebalance starts near the
        fixed point instead of re-converging from uniform."""
        cfg = self.cfg
        if cfg.elastic != "on" or cfg.elastic_readmit != "epoch":
            return
        if self._rdzv is not None and self._n_proc0 > 1:
            # multi-host growth is process-granular: a (re)spawned process
            # offers a join file and the whole fleet re-rendezvouses. Keyed
            # by the ORIGINAL fleet shape — a world shrunk to one surviving
            # process still regrows through the rendezvous channel, never
            # through local virtual-worker readmission
            self._maybe_regrow_multihost(epoch)
            return
        rejoin: set = set(self.health.recovering())
        rejoining = getattr(self.injector, "rejoining", None)
        if rejoining is not None:
            rejoin |= set(rejoining(epoch))
        if self._n_proc0 > 1:
            # a multi-host fleet that SHRANK to one process still owns only
            # its own worker slice: a dead PEER's ranks must re-enter via a
            # process rejoin (join file + grow rendezvous), never as local
            # virtual workers — the post-shrink peer scan is empty, so
            # filter by original-process ownership explicitly
            rejoin = {
                r for r in rejoin
                if self._proc_of_rank(r) in set(self._proc_roster)
            }
        # re-check liveness AT the boundary: a candidate can have gone down
        # again since it flipped RECOVERING (chance-mode injectors schedule
        # overlapping outages) — readmitting a down worker burns a full
        # recovery cycle from the bounded budget for nothing
        down_now: set = set()
        down_workers = getattr(self.injector, "down_workers", None)
        if down_workers is not None:
            down_now = set(down_workers(float(epoch)))
        down_now |= self._scan_peer_heartbeats()
        cands = sorted(
            r
            for r in rejoin
            if r not in self.active_ranks
            and r not in down_now
            and 0 <= r < cfg.world_size
        )
        if not cands:
            return
        with self._trace.span("readmit", cat="recover"):
            self._trace.instant(
                "readmitted", cat="elastic",
                args={"ranks": [int(r) for r in cands], "epoch": int(epoch)},
            )
            self.logger.info(
                f"elastic: readmitting worker(s) {cands} at epoch {epoch}"
            )
            if cfg.ckpt_dir:
                from dynamic_load_balance_distributeddnn_tpu.train.checkpoint import (
                    flush_checkpoints,
                )

                flush_checkpoints(cfg.ckpt_dir)
                heartbeat()
            host_state = self._state_to_host(self.state)
            prev_active = list(self.active_ranks)
            prev_cost = self.per_example_cost.copy()
            new_active = sorted(prev_active + cands)
            retry_transient(
                lambda: self._reshard_world(new_active),
                logger=self.logger,
                desc="readmission re-shard",
                tick=heartbeat,
            )
            self.state = retry_transient(
                lambda: self._state_from_host(host_state),
                logger=self.logger,
                desc="state re-placement",
                tick=heartbeat,
            )
            self._fix_comm_residual()
            jax.block_until_ready(self.state.params)
            heartbeat()  # readmitted mesh answered
            # carry survivors' cost anchors to their new compact slots;
            # probe-seed the newcomers
            cost = np.full(self.world_size, np.nan)
            for i, r in enumerate(self.active_ranks):
                if r in prev_active:
                    cost[i] = prev_cost[prev_active.index(r)]
            fallback = (
                float(np.nanmean(prev_cost))
                if np.isfinite(prev_cost).any()
                else np.nan
            )
            for r in cands:
                i = self.active_ranks.index(r)
                # readmit the health slot FIRST: the probe below feeds
                # observe_latency, and readmit() resets the latency track —
                # the other order would wipe the anchor (and any SUSPECT
                # verdict on a degraded comeback) the probe just measured
                self.health.readmit(r)
                probed = self._probe_readmitted(i)
                cost[i] = probed if probed is not None else fallback
            self.per_example_cost = cost
            if np.isfinite(cost).all() and (cost > 0).all():
                self.shares = equilibrium_shares(cost)
                # t_i = c_i * p_i is the epoch-time model the solver's
                # update inverts; seeding times consistently with the
                # seeded shares makes the next rebalance a fixed point of
                # the probe-seeded estimate
                self.node_times = np.maximum(cost * self.shares, 1e-9)
            else:
                self.shares = initial_partition(self.world_size)
                self.node_times = np.ones(self.world_size, dtype=np.float64)
            ev = {
                "epoch": int(epoch),
                "readmitted": [int(r) for r in cands],
                "world_size": int(self.world_size),
                "seeded_shares": [round(float(s), 4) for s in self.shares],
            }
            self._elastic_events.append(ev)
            self.recorder.meta["elastic_events"] = self._elastic_events
            self.logger.info(
                f"elastic: fleet back to {self.world_size} workers "
                f"{self.active_ranks}; probe-seeded shares "
                f"{np.round(self.shares, 4).tolist()}"
            )

    def _probe_readmitted(self, compact_rank: int) -> Optional[float]:
        """Per-example cost of a readmitted worker from one standalone
        probe step on its device (2-rep min, blocking, untimed against any
        epoch wall — this runs at the boundary). None under a deterministic
        timing model (tests) or on probe failure (caller falls back to the
        survivor mean)."""
        if self.timing_model is not None:
            return None
        try:
            d = next(
                di
                for di, group in self.topology.groups.items()
                if compact_rank in group
            )
            dev = self.topology.devices[d]
            b = max(self.cfg.bucket, 1)
            x, y, w = self._dummy_batch(b)
            views = shard_views(self.state.params, self.topology.devices)
            args = (
                jax.device_put(x, dev),
                jax.device_put(y, dev),
                jax.device_put(w, dev),
                jax.device_put(jax.random.PRNGKey(0), dev),
                jax.device_put(jnp.int32(0), dev),
            )
            fn = self.steps.worker_step_first
            _, aux = fn(views[d], *args)
            jax.block_until_ready(aux)  # warm (compile) untimed
            heartbeat()
            dt = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                _, aux = fn(views[d], *args)
                jax.block_until_ready(aux)
                dt = min(dt, time.perf_counter() - t0)
            heartbeat()
            self.health.observe_latency(self.active_ranks[compact_rank], dt)
            return max(dt, 1e-9) / b
        except Exception as e:  # noqa: BLE001 — seeding is best-effort
            self.logger.warning(
                f"elastic: readmission probe failed ({e!r}) — seeding from "
                "the survivor mean"
            )
            return None

    def _probe_local_cost(self, r: int) -> Optional[float]:
        """Per-example cost of OUR OWN original worker rank ``r`` from one
        timed probe step on its LOCAL device — the multi-host twin of
        :meth:`_probe_readmitted`, restricted to process-local puts (a
        cross-process ``shard_views`` put would run a hidden collective the
        peers are not pairing). None under a deterministic timing model, on
        a non-local rank, or on probe failure — the probe exchange then
        publishes nothing for this rank and every process falls back
        identically."""
        if self.timing_model is not None:
            return None
        try:
            if r not in self.active_ranks:
                return None
            i = self.active_ranks.index(r)
            d = next(
                di
                for di, group in self.topology.groups.items()
                if i in group
            )
            dev = self.topology.devices[d]
            if dev.process_index != jax.process_index():
                return None
            b = max(self.cfg.bucket, 1)
            x, y, w = self._dummy_batch(b)
            params = jax.tree_util.tree_map(
                lambda p: jax.device_put(jax.device_get(p), dev),
                self.state.params,
            )
            args = (
                jax.device_put(x, dev),
                jax.device_put(y, dev),
                jax.device_put(w, dev),
                jax.device_put(jax.random.PRNGKey(0), dev),
                jax.device_put(jnp.int32(0), dev),
            )
            fn = self.steps.worker_step_first
            _, aux = fn(params, *args)
            jax.block_until_ready(aux)  # warm (compile) untimed
            heartbeat()
            dt = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                _, aux = fn(params, *args)
                jax.block_until_ready(aux)
                dt = min(dt, time.perf_counter() - t0)
            heartbeat()
            return max(dt, 1e-9) / b
        except Exception as e:  # noqa: BLE001 — seeding is best-effort
            self.logger.warning(
                f"elastic: local probe for rank {r} failed ({e!r}) — "
                "publishing no cost for it"
            )
            return None

    def _publish_probe_costs(self, costs: Dict[int, float]) -> None:
        """Publish this process's finite positive per-rank costs into the
        grow-rendezvous probe exchange (rendezvous.py ``publish_probe``);
        an empty publication is deliberate — peers must not wait on a
        process that measured nothing."""
        if self._rdzv is None:
            return
        self._rdzv.publish_probe(
            {
                int(r): float(c)
                for r, c in costs.items()
                if np.isfinite(c) and float(c) > 0.0
            }
        )

    def _collect_probe_seed(self) -> bool:
        """GROW-path share seeding (ISSUE 17): read every roster member's
        probe publication and seed the equilibrium split from the union —
        a pure function of the collected files, so survivors and the
        joiner derive IDENTICAL vectors (the replicated-controller
        contract the survivor-mean guess used to satisfy trivially).
        False — keep the sidecar-derived mean-fill seeding — when the
        exchange misses a member inside the bounded window or the union
        leaves any worker's cost unknown."""
        if self._rdzv is None:
            return False
        merged = self._rdzv.collect_probes(self._proc_roster)
        if merged is None:
            self.logger.warning(
                "elastic: probe exchange incomplete — keeping the "
                "survivor-mean seed for joined workers"
            )
            return False
        cost = np.full(self.world_size, np.nan)
        for i, r in enumerate(self.active_ranks):
            c = merged.get(int(r))
            if c is not None and np.isfinite(c) and c > 0.0:
                cost[i] = c
        if not np.isfinite(cost).all():
            return False
        self.per_example_cost = cost
        self.shares = equilibrium_shares(cost)
        # t_i = c_i * p_i: seed the times consistently with the shares so
        # the next rebalance is a fixed point of the exchanged estimate
        self.node_times = np.maximum(cost * self.shares, 1e-9)
        self.logger.info(
            "elastic: probe exchange seeded equilibrium shares "
            f"{np.round(self.shares, 4).tolist()} over "
            f"{len(self._proc_roster)} process(es)"
        )
        return True

    def _maybe_warm(self) -> None:
        if self.cfg.warm_start and not self._warmed:
            self._warmed = True
            with self._trace.span("warm", cat="warm"):
                if self._aot is not None:
                    self._submit_warm_aot()  # non-blocking; compiles overlap epoch 0
                else:
                    self._warm_shapes()

    def run_epoch(self, epoch: int) -> Dict[str, float]:
        """One epoch, wrapped in the graftscope epoch span: every event
        emitted inside (phases here, transfer/dispatch/compile spans on
        worker threads) is stamped with this epoch index, which is what the
        offline attribution (`graftscope summarize`) groups by."""
        tr = self._trace
        tr.set_epoch(epoch)
        try:
            with tr.span("epoch", cat=EPOCH_CAT):
                return self._run_epoch(epoch)
        finally:
            tr.set_epoch(None)

    def _plan_epoch(self, epoch: int):
        """The epoch's host-side control work — LR schedule, solver
        rebalance, plan build, fault-episode setup, probe scheduling —
        graftscope's ``plan_solve`` phase. Returns ``(plan, faults)``."""
        cfg = self.cfg
        lr = one_cycle_lr(
            cfg.learning_rate,
            epoch,
            cfg.epoch_size,
            enabled=cfg.one_cycle_policy,
            disable_enhancements=cfg.disable_enhancements,
        )
        if lr != self.state.learning_rate():
            self.state = self.state.with_learning_rate(lr)

        if cfg.dynamic_batch_size:
            max_share = min(1.0, cfg.capacity_factor / self.world_size)
            self.shares, batch_sizes = rebalance(
                self.node_times, self.shares, cfg.batch_size, max_share=max_share
            )
            if cfg.snap_to_bucket and self.SNAP_BATCHES:
                batch_sizes = quantize_batches(
                    batch_sizes, cfg.bucket, cfg.batch_size
                )
                self.shares = batch_sizes.astype(np.float64) / batch_sizes.sum()
            # feed the trajectory predictor the REALIZED (post-quantization)
            # shares — the quantity whose next value implies the next epoch's
            # dispatched shape tuple (scan-mode speculation)
            self._share_predictor.observe(self.shares)
            self.logger.info(
                f"Epoch {epoch}: adjusted shares to {np.round(self.shares, 4).tolist()}"
            )
        else:
            batch_sizes = integer_batch_split(self.shares, cfg.batch_size)

        plan = self._build_plan(epoch, batch_sizes)
        self.logger.info(
            f"Epoch {epoch}: batch sizes {plan.batch_sizes.tolist()}, "
            f"steps {plan.num_steps}"
        )

        # Injectors are sized/indexed by the ORIGINAL config ranks (their
        # schedules outlive fleet changes); the engine's runtime arrays are
        # compact over the active fleet. Scatter runtime vectors to original
        # rank space for the injector, select the active view back out.
        ctx = FaultContext(
            batch_sizes=self._scatter_full(plan.batch_sizes.astype(np.float64)),
            iter_cost_s=(
                (self._iter_cost_s or calibrate_iter_cost())
                if self._needs_iter_cost
                else None
            ),
            per_example_cost_s=(
                self._scatter_full(self.per_example_cost)
                if np.isfinite(self.per_example_cost).all()
                else None
            ),
        )
        # kept for the window controller's per-window faults_at queries
        # (re-derived per segment after a mid-epoch switch — _window_ctx)
        self._fault_ctx = ctx
        faults = self._faults_active(
            self.injector.epoch_faults(epoch, plan.num_steps, ctx)
        )
        self._probe_this_epoch = self._should_probe(epoch, plan, faults)
        return plan, faults

    def _scatter_full(self, vec: np.ndarray) -> np.ndarray:
        """Runtime-compact vector -> original-rank-indexed vector (zeros in
        lost workers' slots). Identity while the fleet is whole."""
        if len(self.active_ranks) == self.cfg.world_size:
            return vec
        full = np.zeros(self.cfg.world_size, dtype=np.float64)
        full[self.active_ranks] = np.asarray(vec, dtype=np.float64)
        return full

    def _faults_active(self, faults: EpochFaults) -> EpochFaults:
        """Original-rank EpochFaults -> the active fleet's compact view.
        Identity while the fleet is whole."""
        if len(self.active_ranks) == self.cfg.world_size:
            return faults
        sel = np.asarray(self.active_ranks)
        return EpochFaults(
            virtual_seconds=faults.virtual_seconds[sel],
            slow_iters_per_step=faults.slow_iters_per_step[sel],
            time_multipliers=faults.time_multipliers[sel],
        )

    def _dispatch_epoch(self, plan, faults: EpochFaults, epoch: int):
        """Path selection + the epoch's whole timed training region —
        graftscope's ``train`` phase. Returns ``(train_metrics,
        ran_elastic)``."""
        cfg = self.cfg
        # shard_update composes with the elastic dispatch since PR 13 (the
        # zero-1 combine twins); grad_accum stays fused-only, and the flat
        # compressed psum does too UNLESS the sharded update carries it
        # (the quantized reduce-scatter lives inside _zero1_update)
        if (
            cfg.grad_accum > 1 or (cfg.compress_grads and not cfg.shard_update)
        ) and not (self._can_use_fused(plan) or self._can_use_fused_dbs(plan)):
            raise RuntimeError(
                "grad_accum/compress_grads require a fused path "
                "(one worker per device); this plan fell back to the elastic "
                "path"
            )
        if cfg.rebalance == "window" and (
            self._can_use_fused(plan)
            or self._can_use_fused_dbs(plan)
            or self._can_use_packed(plan)
        ):
            # config validation already forbids fused_dbs, but packed/fused
            # selection depends on the runtime topology — without this the
            # controller would silently never engage (exactly the
            # contention topology window rebalancing targets)
            if not self._window_rebalance_logged:
                self._window_rebalance_logged = True
                self.logger.warning(
                    "rebalance=window needs the elastic dispatch paths but "
                    "this topology selected a fused/packed whole-epoch scan "
                    "— running at epoch cadence (pass --packed off to force "
                    "the elastic path)"
                )
        # which execution path each epoch engaged, in the saved artifact:
        # selection depends on the runtime topology, not just the flags
        paths = self.recorder.meta.setdefault("exec_path", [])
        if self._can_use_fused(plan):
            paths.append("fused")
            return self._train_epoch_fused(plan, faults, epoch), False
        if self._can_use_fused_dbs(plan):
            paths.append("fused_dbs")
            return self._train_epoch_fused(plan, faults, epoch, dbs_probe=True), False
        if self._can_use_packed(plan):
            paths.append("packed")
            # probes still needed for the balancer signal and/or compute-mode
            # injection calibration — mirrors the elastic path's condition
            return (
                self._train_epoch_fused(
                    plan,
                    faults,
                    epoch,
                    dbs_probe=(
                        cfg.dynamic_batch_size
                        or self._needs_iter_cost
                        or self.timing_model is not None
                    ),
                    packed=True,
                ),
                False,
            )
        paths.append("elastic:" + self._elastic_mode())
        return self._train_epoch_elastic(plan, faults, epoch), True

    def _run_epoch(self, epoch: int) -> Dict[str, float]:
        tr = self._trace
        self._maybe_warm()  # callers driving epochs directly still warm first
        # Phase set (graftscope): plan_solve -> aot_drain -> train ->
        # speculate -> validate -> record. The phases tile this method, so
        # the trace attributes the epoch span's wall to named segments
        # (`graftscope summarize` renders the table; tests/test_graftscope.py
        # asserts >= 95% coverage).
        with tr.span("plan_solve"):
            plan, faults = self._plan_epoch(epoch)
        # epoch-boundary liveness round: catches losses that landed outside
        # the elastic window checks (fused paths, inter-epoch gaps) before
        # any of this epoch's work dispatches
        self._check_health(epoch, 0.0)

        # Drain pending AOT jobs (the warm universe's tail, the previous
        # epoch's speculation) BEFORE the timed region: concurrent backend
        # compiles contend with the epoch's own compute on CPU-bound hosts
        # and would contaminate the A/B walls — the round-6 CPU insurance
        # arm measured the dbs-on arm 2.4x WORSE purely from this
        # contention. The drain wall lives exactly where the legacy warm
        # wall lived (outside every epoch wall); in steady state nothing is
        # pending and this is a no-op. The warm still overlaps everything
        # up to here — plan build, rebalance, fault setup — and speculative
        # jobs still overlap the epoch that submits them.
        if self._aot is not None and self._aot.pending():
            with tr.span("aot_drain"):
                self._aot_wait_needed(tuple(self._aot.keys()), epoch)

        t_epoch = time.perf_counter()
        with tr.span("train"):
            train_metrics, ran_elastic = self._dispatch_epoch(plan, faults, epoch)
        # The wall excludes probe/instrumentation cost on EVERY path: the
        # fused path already kept its probes out (probe_overhead); the
        # elastic path's standalone worker probes (dbs_probe_cost) were
        # inside the wall until round 4, which made re-probe epochs
        # (probe_every) 2x outliers in the dbs-on arm while the off arm's
        # shorter run never hit one. The reference's signal costs zero wall
        # (it times the epoch it already runs, dbs.py:226-250); excluding
        # ours keeps the A/B apples-to-apples, and the cost stays visible
        # as its own recorder series (probe_time) + the end-of-run total.
        probe_s = train_metrics.get("probe_overhead", 0.0) + train_metrics.get(
            "dbs_probe_cost", 0.0
        )
        epoch_wall = time.perf_counter() - t_epoch - probe_s
        self.total_wallclock += epoch_wall
        self.total_probe_s += probe_s

        # speculative adjacent-rung compiles ride the UNTIMED tail: they
        # overlap validation below and drain before the next timed region
        if ran_elastic:
            with tr.span("speculate"):
                self._maybe_speculate(plan)

        with tr.span("validate"):
            val_loss, accuracy = self.validate()

        with tr.span("record"):
            self._record_epoch(
                epoch, plan, faults, train_metrics, epoch_wall, probe_s,
                val_loss, accuracy,
            )
        return {
            "epoch_wall": epoch_wall,
            "loss": train_metrics["loss"],
            "val_loss": val_loss,
            "accuracy": accuracy,
        }

    def _record_epoch(
        self, epoch: int, plan, faults: EpochFaults, train_metrics,
        epoch_wall: float, probe_s: float, val_loss: float, accuracy: float,
    ) -> None:
        """Post-epoch bookkeeping — modeled times, the probe schedule, the
        cross-host time exchange, recorder extras and the recompile
        sentinel — graftscope's ``record`` phase."""
        cfg = self.cfg
        if (
            not self._probe_this_epoch
            and self.timing_model is None
            and (cfg.dynamic_batch_size or self._needs_iter_cost)
        ):
            # probe skipped: the solver runs on MODELED per-worker times
            self._model_compute_times(plan, faults)
        self._update_probe_schedule(epoch, plan, faults, epoch_wall, train_metrics)

        # multiplier-free compute vector: the window controller's fallback
        # rate source (node_times below bakes in the epoch-mean injection
        # multipliers — composing the instantaneous schedule on top of them
        # would double-count the injected load). Stored WITH the example
        # counts of the plan it was measured under: a boundary re-solve
        # changes per-worker counts, and normalizing old seconds by new
        # counts would skew the derived rates by the share ratio.
        self._clean_compute_s = self.timekeeper.compute_s.copy()
        self._clean_examples = np.array(
            [max(w.batch_size, 1) * max(w.steps, 1) for w in plan.workers],
            dtype=np.float64,
        )
        node_times = (
            self.timekeeper.compute_s * faults.time_multipliers
            + self.timekeeper.injected_s
        )
        # Each process contributes its own workers' slice; exchange_times
        # concatenates them rank-ordered (single-process: identity).
        fresh = exchange_times(node_times[self.rank_lo : self.rank_lo + self.ws_local])
        if cfg.time_smoothing > 0.0 and epoch > 0:
            # EMA damping against probe noise (extension; 0 = reference-exact)
            a = cfg.time_smoothing
            self.node_times = a * self.node_times + (1.0 - a) * fresh
        else:
            self.node_times = fresh
        # Gate the collective on REPLICATED state (the probes-ran flag derives
        # from config alone), never on locally-measured values: a gate that
        # could differ per process would deadlock the process_allgather.
        if self.n_proc > 1 and self._probes_ran:
            self.per_example_cost = exchange_times(
                self.per_example_cost[self.rank_lo : self.rank_lo + self.ws_local]
            )
        self.logger.info(
            f"Epoch {epoch}: node times {np.round(self.node_times, 4).tolist()}, "
            f"train_loss {train_metrics['loss']:.4f}, val_loss {val_loss:.4f}, "
            f"accuracy {accuracy:.2f}, wall {epoch_wall:.3f}s"
        )

        # Throughput/MFU extras (obs/flops.py): examples/s for vision, tokens/s
        # for the LM (n_train counts tokens there); MFU against the mesh's
        # aggregate bf16 peak, from XLA-cost-model FLOPs of the real plan.
        extras = {}
        # always recorded (0.0 on probe-free epochs) so the series stays
        # index-aligned with the per-epoch series in the saved artifact
        extras["probe_time"] = probe_s
        if cfg.elastic == "on":
            # fleet observables: the series the chaos tests read —
            # workers_alive steps down on loss and back up on readmission,
            # recoveries counts completed recovery cycles
            extras["workers_alive"] = float(self.world_size)
            extras["recoveries"] = float(self._recoveries)
        if self._rebalance_ctl is not None:
            # online controller observables: mid-epoch plan switches this
            # epoch (the no-thrash property the tests bound) + the full
            # ledger snapshot for offline tooling
            ctl = self._rebalance_ctl
            extras["plan_switches"] = float(ctl.switches - self._switches_last)
            self._switches_last = ctl.switches
            self.recorder.meta["rebalance_controller"] = ctl.snapshot()
        # bytes-on-wire series (ISSUE 12): what this epoch's gradient
        # combines moved per link class under the active structure — the
        # quantity the hierarchical collective exists to shrink on DCN
        ici_b, dcn_b = self._comm_bytes_per_step()
        extras["steps"] = float(plan.num_steps)
        extras["comm_bytes_ici"] = ici_b * plan.num_steps
        extras["comm_bytes_dcn"] = dcn_b * plan.num_steps
        # elastic-path host-overhead walls (superstep A/B instrumentation;
        # absent on the fused paths, whose dispatch is one scan per window)
        for k in ("host_dispatch_s", "host_put_s", "host_overhead_per_step_s"):
            if k in train_metrics:
                extras[k] = train_metrics[k]
        # AOT compile service: compile jobs finished during this epoch
        # (background pool + inline compile_now). Deliberate overlapped work
        # — kept OUT of the xla_compiles sentinel series below, visible here.
        if self._aot is not None:
            st = self._aot.stats()
            extras["aot_compiles"] = float(st["compiled"]) - self._aot_compiled_last
            self._aot_compiled_last = float(st["compiled"])
        # Corrected-injection reporting (compute-mode A/B hygiene): alongside
        # the NOMINAL straggler profile (meta straggler_factors), stamp the
        # REALIZED injected:clean device-compute profile derived from the
        # raw-wall-differenced calibration quantities, so an artifact whose
        # realized profile drifted past the nominal ceiling is self-evident.
        if self._needs_iter_cost:
            prof = self._realized_injection_profile(plan, faults)
            if prof is not None:
                self.recorder.meta["realized_injection_profile"] = prof
        if epoch_wall > 0:
            extras["examples_per_s"] = self.n_train / epoch_wall
        ppe = self._flops_per_padded_example
        if ppe is not None and ppe > 0:
            padded_examples = train_metrics.get("padded_examples") or float(
                sum(w.padded_batch * w.steps for w in plan.workers)
            )
            self._epoch_flops = ppe * padded_examples
            extras["flops_per_epoch"] = self._epoch_flops
            if epoch_wall > 0:
                from dynamic_load_balance_distributeddnn_tpu.obs.flops import mfu

                u = mfu(self._epoch_flops / epoch_wall, self.n_dev)
                if u is not None:
                    extras["mfu_bf16_peak"] = u

        # Recompile sentinel: a plan layout the run has already executed must
        # never compile again — if it does, a shape fell off the bucket
        # ladder or a jit wrapper was rebuilt (graftlint G001/G003). A fresh
        # layout compiling is ordinary lazy work (warm_start off). Recorded
        # every epoch so the series stays aligned.
        # the layout must capture every compiled-shape dimension a plan
        # controls: padded widths AND the step counts (fused window shapes
        # carry plan.num_steps / per-worker steps in their leading dims) AND
        # the streaming window lengths (superstep/windowed executables
        # specialize on them — ISSUE 2's (shape, window) cache key)
        plan_layout = (
            self._comm_sig
            + (int(plan.num_steps),)
            + tuple((int(w.padded_batch), int(w.steps)) for w in plan.workers)
            + tuple(s1 - s0 for s0, s1 in self._elastic_ranges(plan.num_steps))
            # mid-epoch switches (rebalance=window) dispatch ADDITIONAL
            # layouts inside the same epoch: fold their (step, sizes)
            # signature in so a lazily-compiled switch tuple never reads as
            # a recompile of an already-executed layout
            + tuple(
                (int(ev["step"]),) + tuple(ev["batches"])
                for ev in self._rebalance_events
                if ev.get("epoch") == epoch
            )
        )
        layout_seen = plan_layout in self._seen_plan_layouts
        self._seen_plan_layouts.add(plan_layout)
        epoch_compiles = self._compile_tracker.take()
        extras["xla_compiles"] = float(epoch_compiles)
        # backend-compile seconds since the previous epoch's record (epoch 0
        # carries the warm-up's), foreground and AOT pool alike: set-up time,
        # ~0 when the persistent cache served every program
        total_compile_s = compile_seconds()
        extras["compile_s"] = total_compile_s - self._compile_s_last
        self._compile_s_last = total_compile_s
        if epoch_compiles and layout_seen and epoch >= 1:
            self.logger.warning(
                f"Epoch {epoch}: {epoch_compiles} XLA backend compile(s) on "
                f"an already-executed plan layout {list(plan_layout)} — a "
                "shape fell off the bucket ladder or a jit wrapper was "
                "rebuilt (graftlint G001/G003)"
            )

        heartbeat()  # epoch complete — device answered end-to-end
        self.recorder.record_epoch(
            epoch=epoch,
            train_loss=train_metrics["loss"],
            train_time=float(self.node_times[0]),
            sync_time=train_metrics["sync_time"],
            val_loss=val_loss,
            accuracy=accuracy,
            partition=self.shares.tolist(),
            node_time=self.node_times.tolist(),
            wallclock_time=self.total_wallclock,
            **extras,
        )

    # ------------------------------------------------------ probe scheduling

    def _epoch_signature(self, plan, faults: EpochFaults) -> tuple:
        """What the wall-reference comparison must hold fixed: the plan's
        batch layout and the realized injection arrays."""
        return (
            tuple(int(b) for b in plan.batch_sizes),
            tuple(int(s) for s in faults.slow_iters_per_step),
            tuple(float(m) for m in faults.time_multipliers),
            tuple(float(v) for v in faults.virtual_seconds),
        )

    def _episode_state(self, plan, faults: EpochFaults):
        """Plan-NORMALIZED injection state for the episode-change trigger.
        Compute-mode slow_iters scale with each worker's batch (the injector
        sizes them off ctx.batch_sizes), so comparing raw iters would read
        every rebalance as a new episode and degrade adaptive mode into
        per-epoch probing (it once did; tests/test_probe_schedule.py holds
        it). The per-example iteration ratio is plan-invariant."""
        raw = np.asarray(faults.slow_iters_per_step, dtype=np.float64)
        ratio = raw / np.maximum(np.asarray(plan.batch_sizes, dtype=np.float64), 1.0)
        return (
            ratio,
            raw,
            np.asarray(faults.time_multipliers, dtype=np.float64),
            np.asarray(faults.virtual_seconds, dtype=np.float64),
        )

    def _episode_changed(self, plan, faults: EpochFaults) -> bool:
        if self._probe_episode is None:
            return False
        ratio, raw, mult, virt = self._episode_state(plan, faults)
        r0, w0, m0, v0 = self._probe_episode
        if not np.array_equal(mult, m0) or not np.allclose(virt, v0, rtol=0.05, atol=1e-9):
            return True
        # A real episode change moves BOTH views of the injected load; a mere
        # rebalance moves only one. Batch-scaled injectors (StaticStraggler)
        # keep the per-example ratio fixed across rebalances while raw iters
        # move; wall-seconds injectors (the random fault episodes,
        # faults.py:117) keep raw iters fixed while the ratio moves. 25%
        # relative hysteresis absorbs integer-rounding jitter; on/off
        # transitions trip both terms via the +eps guard.
        ratio_moved = np.abs(ratio - r0) > 0.25 * r0 + 1e-9
        raw_moved = np.abs(raw - w0) > 0.25 * w0 + 1e-9
        return bool(np.any(ratio_moved & raw_moved))

    def _should_probe(self, epoch: int, plan, faults: EpochFaults) -> bool:
        """Adaptive probe schedule (config.probe_mode): real per-worker probe
        steps anchor a linear per-example cost model on epochs 0-1; later
        epochs skip the probes (the balancer runs on modeled times) unless
        the anchor is stale — probe_every epochs elapsed, the injection
        episode changed, or a skipped epoch's wall deviated from the probed
        reference (_update_probe_schedule). The reference's time signal is
        free because it times the epoch it already ran (dbs.py:226-250);
        this gets the probe-based signal to amortized ~zero cost, fixing the
        balanced-plan regression where per-epoch probes were pure overhead."""
        cfg = self.cfg
        if self.timing_model is not None:
            return True  # deterministic model, zero probe cost (tests)
        if not (cfg.dynamic_batch_size or self._needs_iter_cost):
            return False
        if cfg.probe_mode == "always" or epoch < 2:
            return True
        lo, hi = self.rank_lo, self.rank_lo + self.ws_local
        want = False
        if not np.isfinite(self.per_example_cost[lo:hi]).all():
            want = True
        elif self._needs_iter_cost and self._iter_cost_s is None:
            want = True
        elif self._episode_changed(plan, faults):
            want = True  # injection episode changed — re-anchor on reality
        else:
            want = epoch >= self._next_probe_epoch
        if self.n_proc > 1:
            # _probe_workers ends in the mesh-wide combine_probe collective,
            # so the decision MUST be identical on every process; the local
            # terms above (wall trigger via _next_probe_epoch, per-host
            # calibration state) can diverge. OR the votes over the hosts —
            # one scalar in the existing per-epoch metadata exchange path.
            votes = exchange_times(np.array([1.0 if want else 0.0]))
            want = bool(np.any(np.asarray(votes) > 0.5))
        return want

    def _model_compute_times(self, plan, faults: EpochFaults) -> None:
        """Probe-skipped epochs: feed the solver modeled per-worker compute
        (frozen-anchor clean cost ∝ batch, plus calibrated injected load).
        The model is exactly what the probes would measure under the
        linearity assumption the solver itself makes; real probes re-anchor
        it on the _should_probe schedule."""
        iter_cost = self._iter_cost_s or 0.0
        for r in range(self.rank_lo, self.rank_lo + self.ws_local):
            w_plan = plan.workers[r]
            clean = float(self.per_example_cost[r]) * w_plan.batch_size
            inj = (
                iter_cost * float(faults.slow_iters_per_step[r])
                if self._needs_iter_cost
                else 0.0
            )
            self.timekeeper.add_compute(r, (clean + inj) * w_plan.steps)

    def _realized_injection_profile(self, plan, faults: EpochFaults):
        """Per-worker REALIZED injected:clean device-compute multipliers for
        compute-mode injection: (clean_r + iter_cost * slow_r) / clean_r.
        Both ingredients are RTT-immune by construction — the in-step
        iteration cost comes from PAIRED raw-wall differencing (the 0.2*dt
        correction floor cancels in the pair, _probe_workers/_calibrate_
        iter_cost) and the clean anchor from the dispatch-overhead-corrected
        standalone walls — so this is the profile the A/B actually ran at,
        not the nominal request. None until both anchors exist.

        Single-host only: the anchors are per-process and a collective gated
        on locally-measured finiteness could deadlock the allgather (the
        multi-host artifact keeps the nominal profile alone)."""
        if self.n_proc > 1:
            return None
        lo, hi = self.rank_lo, self.rank_lo + self.ws_local
        if not np.isfinite(self.per_example_cost[lo:hi]).all():
            return None
        iter_cost = self._iter_cost_s
        if iter_cost is None:
            return None
        prof = np.ones(self.world_size, dtype=np.float64)
        for r in range(lo, hi):
            clean = float(self.per_example_cost[r]) * max(
                plan.workers[r].batch_size, 1
            )
            if clean <= 0:
                return None
            inj = iter_cost * float(faults.slow_iters_per_step[r])
            prof[r] = (clean + inj) / clean
        return [round(float(p), 4) for p in prof]

    def _update_probe_schedule(
        self, epoch: int, plan, faults: EpochFaults, epoch_wall: float,
        train_metrics: Dict[str, float],
    ) -> None:
        cfg = self.cfg
        sig = self._epoch_signature(plan, faults)
        if self._probe_this_epoch:
            self._probe_sig = sig
            self._probe_episode = self._episode_state(plan, faults)
            # epoch_wall already excludes probe cost (run_epoch), so probed
            # and skipped epochs compare apples-to-apples as-is
            self._probe_wall_ref = epoch_wall
            self._next_probe_epoch = epoch + max(cfg.probe_every, 1)
            self._slow_streak = 0
        elif self._probe_wall_ref and sig != self._probe_sig:
            # the plan changed on a skipped epoch (model-driven rebalance):
            # the stored wall no longer describes this plan, so RE-BASE the
            # reference on this epoch's wall — otherwise the slowdown
            # trigger would be inert until the next probe_every anchor on
            # exactly the epochs adaptive mode newly skips. (If a genuine
            # slowdown starts the same epoch it gets baked into the ref and
            # is only caught by the anchor — bounded by probe_every.)
            self._probe_sig = sig
            self._probe_wall_ref = epoch_wall
            self._slow_streak = 0
        elif self._probe_wall_ref and sig == self._probe_sig:
            if epoch_wall > (1.0 + cfg.probe_wall_tol) * self._probe_wall_ref:
                # reality got SLOWER than the model (e.g. a real straggler
                # the injector didn't create) — but only a PERSISTENT
                # slowdown (two consecutive epochs over threshold) forces a
                # re-probe; a single epoch over is indistinguishable from
                # host jitter, and triggering on it would degenerate
                # adaptive mode into per-epoch probing in jittery
                # environments. Faster-than-ref is benign (compile noise
                # leaving the wall); the probe_every anchor re-anchors the
                # reference either way.
                self._slow_streak += 1
                if self._slow_streak >= 2:
                    self._next_probe_epoch = epoch + 1
            else:
                self._slow_streak = 0

    # ---------------------------------------------------------- train epoch

    def _can_use_fused(self, plan) -> bool:
        """The fused whole-epoch SPMD path applies when there is no balancer
        feedback to measure (dbs off — the reference records node times only
        under dbs, dbs.py:423-426), the plan is uniform, and workers map 1:1
        onto mesh devices."""
        return (
            not self.cfg.dynamic_batch_size
            and plan.is_uniform()
            and self.topology.one_worker_per_device
            and self.n_dev == self.world_size
            and self.timing_model is None
            # compute-mode injection needs per-worker probes (elastic path),
            # so straggler A/B arms stay comparable
            and not self._needs_iter_cost
        )

    def _can_use_fused_dbs(self, plan) -> bool:
        """The fused-DBS path (SURVEY §7.3 option b): every worker padded to
        the same CAPACITY batch so ONE compiled SPMD scan serves every
        rebalanced plan; per-worker speed is still measured by the standalone
        (untimed) probe step. Needs one worker per chip."""
        return (
            self.cfg.fused_dbs
            and self.cfg.dynamic_batch_size
            and self.topology.one_worker_per_device
            and self.n_dev == self.world_size
        )

    @property
    def _cap_b(self) -> int:
        """Fused-DBS per-worker capacity width: the largest bucketed batch the
        balancer can assign (max_share of the global batch)."""
        cfg = self.cfg
        max_share = min(1.0, cfg.capacity_factor / self.world_size)
        return -(-int(np.ceil(max_share * cfg.batch_size)) // cfg.bucket) * cfg.bucket

    @property
    def _cap_packed(self) -> int:
        """Packed-epoch concat width — ONE fixed width serving every plan.

        With bucket snapping active (the default), every plan's per-worker
        widths are bucket multiples summing to floor(B/bucket)*bucket <= B
        (quantize_batches), so the tight cap ceil(B/bucket)*bucket carries
        ZERO dead rows. The old conservative cap B + ws*bucket paid up to
        ws*bucket zero-weight rows on EVERY packed step — a 20% compute tax
        at the bench shape (B=512, ws=4, bucket=32) levied on the dbs-on arm
        only (the dbs-off arm's uniform plans ride the lean fused scan),
        eating most of the balancer's ~1.25x ceiling on a timeshared chip.
        Without snapping, per-worker ceil padding can exceed B; keep the
        conservative cap there (_can_use_packed enforces the width bound)."""
        cfg = self.cfg
        B, ws, bucket = cfg.batch_size, self.world_size, cfg.bucket
        if not cfg.dynamic_batch_size:
            # dbs off: the only plan is the uniform integer split — its exact
            # packed width is a static bound. At bucket-divisible shapes this
            # equals the dbs-on tight cap, so the A/B arms (and the clean
            # leg) share one executable with identical dead-row cost: zero.
            per_batch = -(-B // ws)  # ceil: the largest worker batch
            return ws * (-(-per_batch // bucket) * bucket)
        if cfg.snap_to_bucket and self.SNAP_BATCHES and B // bucket >= ws:
            # every dbs plan (incl. the epoch-0 uniform one) passes through
            # quantize_batches under exactly these conditions — unsnapped
            # dbs plans keep the slack cap
            return -(-B // bucket) * bucket
        return B + ws * bucket

    def _can_use_packed(self, plan) -> bool:
        """Single-device packed epochs: all workers share ONE chip (the
        reference's contention topology, -gpu 0,0,0,0), so the weighted-sum
        gradient combine over the concatenated true-width batches is the
        elastic path's exact math (psum over a 1-chip mesh is identity) in
        one compiled whole-epoch scan instead of ws+1 dispatches per step.
        The balancer's per-worker time signal still comes from the
        standalone probes. Works with or without the device cache (index
        feed vs materialized windows). Needs no per-worker grad clip (the
        LM's clip is per worker, not global) and none of the fused-only
        features; vision only (the LM's column batches stay elastic or use
        fused_dbs)."""
        cfg = self.cfg
        if cfg.packed == "off":
            return False
        ok = (
            self.n_dev == 1
            and self.n_proc == 1
            and self.bundle is not None
            and getattr(self.bundle, "train_x", None) is not None
            and cfg.grad_clip == 0
            and not cfg.compress_grads
            and cfg.grad_accum <= 1
        )
        # the plan's concat of bucketed widths must fit the fixed scan width
        # (always true for snapped dbs plans, which the tight cap mirrors; an
        # unsnapped split's per-worker ceil padding can overflow it)
        fits = (
            plan is None
            or sum(w.padded_batch for w in plan.workers) <= self._cap_packed
        )
        if cfg.packed == "on" and not (ok and fits):
            if ok and not fits:
                raise ValueError(
                    f"packed=on: plan widths "
                    f"{[w.padded_batch for w in plan.workers]} sum past the "
                    f"packed scan width {self._cap_packed}"
                )
            raise ValueError(
                "packed=on needs a single-device vision topology and no "
                "grad_clip/compress_grads/grad_accum"
            )
        return ok and fits

    def _chunk_ranges(self, num_steps: int):
        """Step windows of the streaming host path: ``stream_chunk_steps``-sized
        windows (0 = one whole-epoch window). At most two distinct window
        lengths per epoch (body + tail), so the fused scan compiles at most
        twice per geometry."""
        chunk = self.cfg.stream_chunk_steps
        if chunk <= 0 or num_steps <= chunk:
            return [(0, num_steps)]
        return [(s, min(s + chunk, num_steps)) for s in range(0, num_steps, chunk)]

    def _elastic_ranges(self, num_steps: int):
        """Elastic-path step windows. Scan mode additionally caps windows at
        ``superstep_window``: the superstep compiles a fully UNROLLED window
        (bitwise parity with per-step dispatch requires the unrolled
        lowering — steps.py group_superstep), so program size must stay
        bounded. Still at most two distinct window lengths per geometry."""
        ranges = self._chunk_ranges(num_steps)
        if self._elastic_mode() != "scan":
            return ranges
        win = max(int(self.cfg.superstep_window), 1)
        out = []
        for s0, s1 in ranges:
            out.extend((s, min(s + win, s1)) for s in range(s0, s1, win))
        return out

    def _gather_fused_window(self, plan, s0: int, s1: int, pad_to=None,
                             as_indices: bool = False, pack_total=None):
        """Host-side gather of steps [s0, s1): [n, ws*b_pad, ...] numpy arrays
        in the fused path's global layout (worker r owns slice r; each process
        materializes only its own workers' slice). ``pad_to``: fused-DBS
        capacity width per worker. ``as_indices``: device-cache mode — the
        window is (idx, w) only; rows gather on device. ``pack_total``:
        packed-epoch mode — workers keep their true bucketed widths and the
        CONCAT pads (zero weight) to this fixed global width."""
        data = [
            self._worker_inputs(
                plan, self.rank_lo + r, s0, s1, pad_to=pad_to,
                as_indices=as_indices,
            )
            for r in range(self.ws_local)
        ]
        width = sum(d[0].shape[1] for d in data)
        extra = (pack_total - width) if pack_total is not None else 0
        out = []
        for i in range(len(data[0])):
            parts = [d[i] for d in data]
            if extra > 0:
                # zero pad block folded into the single concat pass (a
                # post-hoc np.pad would copy the whole window a second time)
                a0 = parts[0]
                parts.append(
                    np.zeros((a0.shape[0], extra) + a0.shape[2:], a0.dtype)
                )
            out.append(np.concatenate(parts, axis=1))
        return tuple(out)

    def _put_fused_window(self, *arrays):
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import batch_sharding

        mesh = self.mesh
        bx = self._batch_axes
        if self.n_proc == 1:
            return tuple(
                jax.device_put(a, batch_sharding(mesh, a.ndim, axis=bx, axis_dim=1))
                for a in arrays
            )
        return tuple(
            jax.make_array_from_process_local_data(
                batch_sharding(mesh, a.ndim, axis=bx, axis_dim=1), a
            )
            for a in arrays
        )

    def _train_epoch_fused(
        self, plan, faults: EpochFaults, epoch: int, dbs_probe: bool = False,
        packed: bool = False,
    ) -> Dict[str, float]:
        """``dbs_probe=True``: the fused-DBS mode — every worker padded to the
        fixed capacity width (one compiled scan for every plan), with the
        balancer's per-worker time signal measured by the standalone probe
        step after the epoch (untimed, like the elastic path's probes).

        ``packed=True``: the single-device packed mode — workers keep their
        TRUE bucketed widths, concatenated (then padded to the fixed
        ``_cap_packed`` width) into the same scan; the 1-chip psum is an
        identity, so this is the elastic combine's math with zero per-step
        dispatch. Injected synthetic load is the per-worker total (the chip
        serializes the workers either way)."""
        cfg = self.cfg
        self.timekeeper.reset()
        pad_to = self._cap_b if (dbs_probe and not packed) else None
        pack_total = self._cap_packed if packed else None
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import batch_sharding

        mesh = self.mesh
        bx = self._batch_axes
        if packed:
            slow = jax.device_put(
                np.array(
                    [faults.slow_iters_per_step.sum()], dtype=np.int32
                ),
                batch_sharding(mesh, 1, axis=bx),
            )
        elif self.n_proc == 1:
            slow = jax.device_put(
                faults.slow_iters_per_step.astype(np.int32),
                batch_sharding(mesh, 1, axis=bx),
            )
        else:
            slow = jax.make_array_from_process_local_data(
                batch_sharding(mesh, 1, axis=bx),
                faults.slow_iters_per_step.astype(np.int32)[
                    self.rank_lo : self.rank_lo + self.ws_local
                ],
            )
        seed = jnp.int32(cfg.seed * 31 + epoch)
        if self.n_proc == 1:
            # committed replicated, matching the AOT lowering spec — an
            # uncommitted scalar would call the compiled executable with a
            # mismatched input sharding
            seed = jax.device_put(seed, replicated_sharding(mesh))

        # Streaming: gather window k+1 on the prefetch thread while the device
        # runs window k (dispatch is async — the jit call returns immediately).
        # The per-step dropout/augment rng folds in state.step, not the scan
        # index, so windowed scans are bitwise-identical to one whole-epoch
        # scan. Peak host memory: two windows, not the epoch.
        ranges = self._chunk_ranges(plan.num_steps)
        metrics_total = np.zeros(4, dtype=np.float64)
        first_window = None
        use_cache = self._use_device_cache
        if use_cache:
            cache_x, cache_y = self._device_cache_replicated()
        with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
            fut = pool.submit(
                self._gather_fused_window, plan, *ranges[0], pad_to, use_cache,
                pack_total,
            )
            for i, _ in enumerate(ranges):
                # the controller's wait on the overlapped gather thread, then
                # the put alone: transfer vs dispatch tracks in the trace
                with self._trace.span("input_wait", cat="transfer"):
                    gathered = fut.result()
                with self._trace.span("fused_put", cat="transfer"):
                    win = self._put_fused_window(*gathered)
                if i + 1 < len(ranges):
                    fut = pool.submit(
                        self._gather_fused_window, plan, *ranges[i + 1], pad_to,
                        use_cache, pack_total,
                    )
                with self._trace.span("fused_dispatch", cat="dispatch"):
                    # service-registry resolution (multi-device AOT lowering):
                    # warm-started runs dispatch the pre-compiled executable;
                    # cold keys compile inline through the service (same wall,
                    # registered + sentinel-silent); multi-host stays lazy
                    if use_cache:
                        idxs, ws_ = win
                        args = (self.state, cache_x, cache_y, idxs, ws_, slow, seed)
                        fn = self._resolve_fused_epoch(
                            idxs.shape[0], idxs.shape[1], slow.shape[0], args
                        )
                        self.state, metrics = fn(*args)
                    else:
                        xs, ys, ws_ = win
                        if first_window is None and self._fused_sync_per_step is None:
                            # retained only on the run's first epoch, for the
                            # one-time sync/FLOPs probes below — not pinned later
                            first_window = (xs, ys, ws_)
                        args = (self.state, xs, ys, ws_, slow, seed)
                        fn = self._resolve_fused_epoch(
                            xs.shape[0], xs.shape[1], slow.shape[0], args
                        )
                        self.state, metrics = fn(*args)
                    # the host's one blocking read of the device in a window:
                    # apart from the dispatch above it, so the trace tells
                    # host work from the host waiting on the chip
                    with self._trace.span("device_wait", cat="wait"):
                        metrics_total += np.asarray(jax.block_until_ready(metrics))
                heartbeat()
        metrics = metrics_total
        probe_overhead = 0.0
        if self._fused_sync_per_step is None:
            t0 = time.perf_counter()
            if first_window is None:
                # device-cache mode: materialize ONE step's batches for the
                # one-time sync/FLOPs probes (probe-overhead time, not wall)
                first_window = self._put_fused_window(
                    *self._gather_fused_window(
                        plan, 0, 1, pad_to, pack_total=pack_total
                    )
                )
            xs, ys, ws_ = first_window
            with self._trace.span("sync_probe", cat="probe"):
                self._fused_sync_per_step = self._probe_fused_sync(
                    xs, ys, ws_, slow, jnp.int32(cfg.seed * 31 + epoch)
                )
            if self._flops_per_padded_example is None:
                from dynamic_load_balance_distributeddnn_tpu.obs.flops import (
                    compiled_flops,
                )

                # the sync probe above already compiled this exact program
                # through the AOT service — reuse its executable for the
                # cost analysis instead of compiling a second copy
                pre = None
                if self._aot is not None:
                    pre = self._aot.get(
                        ("fused_step_probe", self._aot_gen)
                        + self._comm_sig
                        + tuple(int(s) for s in xs[0].shape)
                    )
                f = compiled_flops(
                    self.steps.fused_step_probe,
                    self.state, xs[0], ys[0], ws_[0], slow,
                    jnp.int32(cfg.seed * 31 + epoch),
                    compiled=pre,
                )
                # cost_analysis reports the PER-DEVICE partitioned module's
                # FLOPs (it processes global_batch / n_dev examples), so
                # normalize by the per-device slice — consistent with the
                # elastic path's single-device normalization
                per_dev_batch = max(xs.shape[1] // max(self.n_dev, 1), 1)
                self._flops_per_padded_example = (
                    f / per_dev_batch if f else -1.0
                )
            # one-time instrumentation (2 extra XLA compiles + probe steps);
            # excluded from the epoch wall so the benchmark's fused-arm
            # wallclock stays comparable to the elastic arm
            probe_overhead = time.perf_counter() - t0
        if dbs_probe:
            # The balancer's time signal: per-worker standalone probe steps at
            # the TRUE (plan-bucketed) shapes, untimed against the epoch wall
            # — the fused scan itself is one SPMD program with no per-worker
            # boundary to time.
            t0 = time.perf_counter()
            if (
                self.timing_model is None
                and self._probe_this_epoch
                and (cfg.dynamic_batch_size or self._needs_iter_cost)
            ):
                data = [
                    self._worker_inputs(
                        plan, self.rank_lo + r, 0, 1,
                        as_indices=self._use_device_cache,
                    )
                    for r in range(self.ws_local)
                ]
                with self._trace.span("probe", cat="probe"):
                    self._probe_workers(plan, data, faults, epoch)
                self._probes_ran = True
            if self.timing_model is not None:
                modeled = np.asarray(self.timing_model(plan), dtype=np.float64)
                for r in range(self.world_size):
                    self.timekeeper.add_compute(r, modeled[r])
            probe_overhead += time.perf_counter() - t0
        for r in range(self.world_size):
            self.timekeeper.add_injected(r, float(faults.virtual_seconds[r]))
        wloss, loss_sum, count = float(metrics[0]), float(metrics[1]), float(metrics[2])
        return {
            "loss": loss_sum / max(count, 1.0),
            "wloss": wloss / max(plan.num_steps, 1),
            "sync_time": self._fused_sync_per_step * plan.num_steps,
            "probe_overhead": probe_overhead,
            # executed padded examples (capacity layout runs cap_b per worker,
            # packed runs cap_packed total, regardless of true batches) — MFU
            "padded_examples": (
                float(self._cap_packed * plan.num_steps)
                if packed
                else float(self.world_size * self._cap_b * plan.num_steps)
                if dbs_probe
                else None
            ),
        }

    def _aot_fused_probe(self, name: str, fn, args, sig: tuple):
        """Resolve a fused-path probe executable through the AOT service's
        blocking ``compile_now`` (inline, deduped): the SAME compiled object
        then serves both the sync-probe timing and ``cost_analysis`` — no
        second copy of the step is ever compiled for FLOPs accounting.
        Single-host only (multi-host AOT lowering of the mesh program is
        untested armor we don't need: those runs keep the lazy path)."""
        if self._aot is None or self.n_proc > 1:
            return fn
        try:
            return self._aot.compile_now(
                (name, self._aot_gen) + self._comm_sig + sig, fn, args
            )
        except Exception as e:
            self.logger.warning(
                f"AOT compile_now({name}) failed: {e!r} — using lazy jit"
            )
            return fn

    def _probe_fused_sync(self, xs, ys, ws_, slow, seed, reps: int = 3) -> float:
        """Per-step collective cost on the fused path: time a full single
        step vs its comm-free twin (identical math, psums stripped) after
        warm-up; the delta is the sync time. If the delta drowns in timer
        noise, fall back to timing the standalone gradient psum. Restores the
        reference's compute/comm split contract (dbs.py:250, 297-299) on the
        path where comm is fused into the XLA program."""
        x0, y0, w0 = xs[0], ys[0], ws_[0]
        sig = tuple(int(s) for s in x0.shape)

        def timed(fn, *args) -> float:
            jax.block_until_ready(fn(*args))  # warm execute (pre-compiled)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                best = min(best, time.perf_counter() - t0)
            heartbeat()
            return best

        full_args = (self.state, x0, y0, w0, slow, seed)
        f_full = self._aot_fused_probe(
            "fused_step_probe", self.steps.fused_step_probe, full_args, sig
        )
        f_local = self._aot_fused_probe(
            "fused_step_nocomm", self.steps.fused_step_nocomm, full_args, sig
        )
        t_full = timed(f_full, *full_args)
        t_local = timed(f_local, *full_args)
        # The standalone-psum fallback must run UNCONDITIONALLY: gating it on
        # the locally-measured delta would make processes execute different
        # collective programs in multi-host runs (timer noise differs per
        # host) and deadlock the mesh.
        zeros = jax.tree_util.tree_map(jnp.zeros_like, self.state.params)
        f_psum = self._aot_fused_probe("comm_probe", self.steps.comm_probe, (zeros,), ())
        t_psum = timed(f_psum, zeros)
        delta = t_full - t_local
        return float(delta) if delta > 0.0 else float(t_psum)

    def _worker_inputs(
        self,
        plan,
        rank: int,
        s0: int = 0,
        s1: Optional[int] = None,
        *,
        pad_to: Optional[int] = None,
        as_indices: bool = False,
    ):
        """Materialize one worker's steps [s0, s1) (default: the whole epoch):
        [n, b_pad, ...] batches, labels and per-example weights (the
        weighted-combine contract). The gather runs through the native C++
        runtime when available (multithreaded row pack; runtime/native.py),
        numpy otherwise — identical results.

        ``pad_to``: zero-pad the batch axis up to this width (weights 0 on the
        padding) — the fused-DBS capacity layout, where every worker presents
        the same static shape regardless of its true batch (SURVEY §7.3).

        ``as_indices``: device-cache mode — return ``(idx_i32, w)`` and let
        the compiled step gather the rows from the HBM-resident arrays
        (identical rows and weights; the host-side row pack is skipped)."""
        from dynamic_load_balance_distributeddnn_tpu.runtime import take_rows

        idx, mask = plan.epoch_indices(rank, s0, s1)
        w = np.stack(
            [
                example_weights(
                    mask[s],
                    total_true=int(plan.batch_sizes.sum()),
                    worker_count=int(mask[s].sum()),
                    world_size=self.world_size,
                    uniform_worker_weight=self.cfg.disable_enhancements,
                )
                for s in range(mask.shape[0])
            ]
        )
        if as_indices:
            if pad_to is not None and idx.shape[1] < pad_to:
                extra = pad_to - idx.shape[1]
                idx = np.pad(idx, ((0, 0), (0, extra)))
                w = np.pad(w, ((0, 0), (0, extra)))
            return idx.astype(np.int32), w
        x = take_rows(self.bundle.train_x, idx)
        y = take_rows(self.bundle.train_y, idx)
        if pad_to is not None and x.shape[1] < pad_to:
            extra = pad_to - x.shape[1]
            pad1 = ((0, 0), (0, extra))
            x = np.pad(x, pad1 + ((0, 0),) * (x.ndim - 2))
            y = np.pad(y, pad1[: y.ndim])
            w = np.pad(w, pad1)
        return x, y, w

    def _elastic_mode(self) -> str:
        """How the elastic hot loop executes (config.superstep):

        ``"scan"`` — ONE device hosts every worker (the full contention
        topology), so the per-step cross-worker combine is chip-local and a
        whole window runs as one compiled ``lax.scan`` carrying the
        TrainState: one dispatch per window, bitwise-identical math.

        ``"window"`` — workers span several devices, so step k's combine is
        a mesh collective that step k+1's gradients depend on; the per-step
        cadence stays, but each worker-step is ONE window-sliced executable
        call (on-device step indexing) instead of ~5 host-issued dispatches.

        ``"step"`` — the legacy per-step loop (superstep="off"), kept as the
        bitwise-parity and dispatch-overhead reference.

        shard_update composes with scan mode (the PR-13 fallback, closed):
        the superstep body routes into the axis-free zero-1 twin
        (``_zero1_update(..., with_comm=False, local_index=0)``), bitwise-
        identical to the windowed combine twin's identity collectives on
        the single-device mesh. The one remaining exclusion is
        shard_update x compress_grads — the quantized reduce-scatter is
        NOT an identity even over a size-1 axis (stochastic rounding), so
        that pair keeps the windowed per-step combine cadence."""
        if self.cfg.superstep == "off":
            return "step"
        if (
            self.topology.single_group
            and self.n_proc == 1
            and not (self.cfg.shard_update and self.cfg.compress_grads)
        ):
            return "scan"
        return "window"

    def _dispatch_superstep_window(
        self, staged_d: Dict, d: int, group, win_key, slow_dev, aux_windows
    ) -> None:
        """Scan mode: one compiled superstep for the whole worker group's
        window. ``staged_d[r]`` holds worker r's window arrays (+ rng keys);
        the per-worker tuples transpose into the scan's pytree inputs."""
        cols = tuple(zip(*(staged_d[r] for r in group)))
        slows = tuple(slow_dev[r] for r in group)
        self._superstep_keys.add(win_key)
        use_cache = self._use_device_cache
        name = "group_superstep_idx" if use_cache else "group_superstep"
        fn = None
        if self._aot is not None:
            fn = self._aot.get((name, win_key, d, self._aot_gen))
        if fn is None:
            fn = self.steps.group_superstep_idx if use_cache else self.steps.group_superstep
        with self._host_meter.dispatch():
            if use_cache:
                idxs, ws_, ks = cols
                self.state, aux = fn(
                    self.state, *self._device_cache_for(d), idxs, ws_, ks, slows
                )
            else:
                xs, ys, ws_, ks = cols
                self.state, aux = fn(self.state, xs, ys, ws_, ks, slows)
        aux_windows.append(aux)

    def _dispatch_combine_steps(
        self, staged: Dict, win: int, slow_dev, aux_acc, windowed: bool
    ) -> None:
        """Per-step combine cadence, shared by window mode and the legacy
        per-step mode (superstep="off" — the dispatch-overhead reference of
        tests/test_superstep.py). ``windowed`` picks how
        a worker-step gets its data: ONE window-sliced executable call (the
        step index rides in as a traced scalar, the window slices on device)
        vs host-side slicing plus the single-step executables (one dispatch
        per slice)."""
        topo = self.topology
        steps = self.steps
        use_cache = self._use_device_cache
        if windowed:
            step_first = steps.worker_step_first_win_idx if use_cache else steps.worker_step_first_win
            step_acc = steps.worker_step_acc_win_idx if use_cache else steps.worker_step_acc_win
        else:
            step_first = steps.worker_step_first_idx if use_cache else steps.worker_step_first
            step_acc = steps.worker_step_acc_idx if use_cache else steps.worker_step_acc
        # Resolve each worker's executables once per window: service-compiled
        # (AOT) when present, the lazy jit wrapper otherwise. Shapes come
        # from the staged arrays themselves so the key can never drift from
        # what is actually dispatched.
        suffix = ("_win" if windowed else "") + ("_idx" if use_cache else "")
        resolved = {}
        for d in topo.used_device_indices:
            for r in topo.groups[d]:
                arrs = staged[d][r]
                b = int(arrs[0].shape[1])
                wl = int(arrs[0].shape[0]) if windowed else None
                resolved[r] = (
                    self._aot_resolve("worker_first" + suffix, b, d, wl, step_first),
                    self._aot_resolve("worker_acc" + suffix, b, d, wl, step_acc),
                )
        up_name = self._combine_names()[0]
        combine = self._aot_resolve_combine(up_name, getattr(steps, up_name))
        for s in range(win):
            s_i = np.int32(s)
            with self._host_meter.dispatch():
                partials = {}
                views = shard_views(self.state.params, topo.devices)
                for d in topo.used_device_indices:
                    acc = None
                    cache = self._device_cache_for(d) if use_cache else ()
                    for r in topo.groups[d]:
                        arrs = staged[d][r]
                        if windowed:
                            args = cache + arrs + (s_i, slow_dev[r])
                        else:
                            args = cache + tuple(a[s] for a in arrs) + (
                                slow_dev[r],
                            )
                        f_first, f_acc = resolved[r]
                        if acc is None:
                            acc, aux = f_first(views[d], *args)
                        else:
                            acc, aux = f_acc(views[d], acc, *args)
                        aux_acc.append(aux)
                    partials[d] = acc
                stacked = stack_partials(
                    [partials[d] for d in topo.used_device_indices], self.mesh
                )
                self.state = combine(self.state, stacked)

    # ------------------------------------------- online window rebalancing
    # (ISSUE 11, balance/controller.py). The epoch-cadence loop re-solves
    # the partition once per epoch; under a time-varying straggler (the
    # sin/ramp schedules) that lag is the whole cost. At window cadence the
    # controller folds the per-window signal (EMA rates x the injector's
    # instantaneous multipliers, scaled by measured step-wall feedback) into
    # the same inverse-time solve, and — under hysteresis plus a regret-
    # style budget — retires the REMAINING windows under the new plan:
    # staged windows keep their data (nothing on device is re-staged,
    # train/pipeline.py), future windows re-slice the unvisited example
    # pool through data/partitioner.py build_remainder_plan.

    def _window_controller(self) -> Optional[OnlineRebalanceController]:
        cfg = self.cfg
        if cfg.rebalance != "window" or not cfg.dynamic_batch_size:
            return None
        if self.n_proc > 1:
            # the switch decision folds LOCALLY measured walls — a gate that
            # can diverge per process would desynchronize the combine
            # collectives mid-epoch
            if not self._window_rebalance_logged:
                self._window_rebalance_logged = True
                self.logger.warning(
                    "rebalance=window is single-process only — falling back "
                    "to epoch cadence"
                )
            return None
        if self._rebalance_ctl is None:
            topo = self.topology
            self._rebalance_ctl = OnlineRebalanceController(
                self.world_size,
                cfg.batch_size,
                [topo.groups[d] for d in topo.used_device_indices],
                bucket=(
                    cfg.bucket if (cfg.snap_to_bucket and self.SNAP_BATCHES) else 0
                ),
                max_share=min(1.0, cfg.capacity_factor / self.world_size),
                hysteresis=cfg.rebalance_hysteresis,
                margin=cfg.rebalance_margin,
                budget_frac=cfg.rebalance_budget_frac,
                rate_alpha=cfg.rebalance_rate_alpha,
                logger=self.logger,
            )
            # decision journal on the registry snapshot (ISSUE 15): the
            # controller's ledgers + last verdict become queryable live
            self.obs.attach(controller=self._rebalance_ctl)
        # refresh each call: the tree/wires (and therefore the modeled comm
        # floor) can change across re-resolutions while the controller lives
        self._rebalance_ctl.comm_step_s = self._modeled_comm_step_s()
        return self._rebalance_ctl

    def _window_rates(self) -> Optional[np.ndarray]:
        """Base (injection-free) per-worker per-example rates for the
        controller: the probe anchors when they exist, else the last
        epoch's multiplier-free compute vector normalized by the plan's
        per-worker example counts. None before any real signal exists
        (epoch 0 cold start) — the caller then evaluates on a unit base
        (the schedule's relative multipliers still steer the solve) but
        MUST NOT fold the placeholder into the controller's EMA: its
        arbitrary scale would drown the absolute compute-mode injection
        term for many evaluations (0.5-EMA half-life)."""
        c = self.per_example_cost.copy()
        if np.isfinite(c).all() and (c > 0).all():
            return np.maximum(c, 1e-12)
        clean = self._clean_compute_s
        examples = getattr(self, "_clean_examples", None)
        if (
            clean is not None
            and examples is not None
            and len(clean) == self.world_size
            and len(examples) == self.world_size
            and (clean > 0).all()
        ):
            # normalize by the example counts of the SAME epoch the seconds
            # were measured under, not the current plan's
            return np.maximum(clean / np.maximum(examples, 1.0), 1e-12)
        return None

    def _window_ctx(self, pl) -> FaultContext:
        """FaultContext against the CURRENT segment's batch sizes (after a
        switch the injected compute must track the new split, or the
        delivered slowdown factors drift off the schedule)."""
        return FaultContext(
            batch_sizes=self._scatter_full(pl.batch_sizes.astype(np.float64)),
            iter_cost_s=self._iter_cost_s if self._needs_iter_cost else None,
            per_example_cost_s=(
                self._scatter_full(self.per_example_cost)
                if np.isfinite(self.per_example_cost).all()
                else None
            ),
        )

    def _window_faults_at(self, t: float, pl) -> Optional[EpochFaults]:
        """The injector's instantaneous (window-cadence) fault view at
        epoch-time ``t``, compacted to the active fleet — None for
        injectors without a time-varying surface."""
        fa = getattr(self.injector, "faults_at", None)
        if fa is None:
            return None
        return self._faults_active(fa(t, self._window_ctx(pl)))

    def _effective_rates(
        self, rates: np.ndarray, wf: Optional[EpochFaults], batches: np.ndarray
    ) -> np.ndarray:
        """Compose the base rates with the window's fault view: virtual
        multipliers scale, compute-mode slow iters add their per-example
        equivalent at the current split."""
        eff = np.asarray(rates, dtype=np.float64).copy()
        if wf is None:
            return eff
        eff = eff * np.asarray(wf.time_multipliers, dtype=np.float64)
        if self._needs_iter_cost and self._iter_cost_s:
            extra = self._iter_cost_s * np.asarray(
                wf.slow_iters_per_step, dtype=np.float64
            )
            eff = eff + extra / np.maximum(
                np.asarray(batches, dtype=np.float64), 1.0
            )
        return eff

    def _aot_submit_candidate(
        self, batches: np.ndarray, ranges, j: int
    ) -> tuple:
        """Speculatively queue the executables a switch onto ``batches``
        would dispatch for windows >= j (scan: the superstep shape-tuple
        keys; ladder modes: the per-worker rungs at the remaining window
        lengths). The engine only EXECUTES a switch once these resolve —
        warm gating — so a switch never pays a foreground compile."""
        if self._aot is None:
            return ()
        cfg = self.cfg
        topo = self.topology
        padded = [
            -(-int(max(b, 1)) // cfg.bucket) * cfg.bucket for b in batches
        ]
        wins = tuple(sorted({s1 - s0 for s0, s1 in ranges[j:]}))
        keys: list = []
        if self._elastic_mode() == "scan":
            d0 = topo.used_device_indices[0]
            group_pad = [padded[self.rank_lo + r] for r in topo.groups[d0]]
            for win in wins:
                keys += self._aot_submit_superstep(
                    group_pad, win, speculative=True
                )
        else:
            win_arg = wins if self._elastic_mode() == "window" else ()
            for d in topo.used_device_indices:
                group = topo.groups[d]
                want_acc = len(group) > 1
                for r in group:
                    keys += self._aot_submit_worker_steps(
                        d, padded[self.rank_lo + r], win_arg, want_acc,
                        want_plain=True, speculative=True,
                    )
        return tuple(dict.fromkeys(keys))

    def _maybe_window_rebalance(
        self, ctl, plan, seg_plans, ranges, pipe, i, epoch,
        aux_acc, aux_windows, eval_state,
    ) -> None:
        """One controller evaluation at the boundary after window ``i``:
        fold the signal, propose, speculate at the candidate, and — when
        the hysteresis verdict is a warm-gated switch — re-slice the
        remaining windows under the new plan."""
        j = pipe.next_unlaunched()
        if j >= len(ranges):
            return  # every window already staged — no horizon left to act on
        s_switch = ranges[j][0]
        remaining = plan.num_steps - s_switch
        if remaining <= 0:
            return
        with self._trace.span(
            "controller", cat="solve", args={"window": i, "epoch": epoch}
        ):
            t_eval0 = time.perf_counter()
            cur_pl, cur_off = self._seg_for_step(seg_plans, s_switch)
            cur_batches = np.asarray(cur_pl.batch_sizes, dtype=np.int64)
            base = self._window_rates()
            if base is not None:
                ctl.observe_rates(base)
            t_next = float(epoch) + (ranges[j][0] + ranges[j][1]) / (
                2.0 * max(plan.num_steps, 1)
            )
            wf = self._window_faults_at(t_next, cur_pl)
            rates = ctl.rates
            if rates is None:
                rates = np.ones(self.world_size, dtype=np.float64)
            eff = self._effective_rates(rates, wf, cur_batches)
            # step-wall feedback (real clocks only): sync on the last
            # dispatched window and compare the measured wall of the steps
            # since the previous evaluation against the model's prediction
            if self.timing_model is None:
                last_aux = (aux_windows or aux_acc)[-1:] or None
                if last_aux is not None:
                    with self._trace.span("device_wait", cat="wait"):
                        jax.block_until_ready(last_aux)
                now = time.perf_counter()
                # host-side dispatch walls since the last evaluation
                # (balance/timing.py mark_window): the measured wall below
                # includes them, the model predicts device compute only —
                # subtracting keeps the feedback scale a compute signal
                host_s, _, _ = self._host_meter.mark_window()
                done = ranges[i][1] - eval_state["step"]
                if eval_state["step"] > 0 and done > 0 and eval_state.get("pred_step"):
                    # compare against the prediction STORED at the previous
                    # evaluation — the same windows, the same schedule
                    # phase, the same batch split; modeling the past stretch
                    # with the NEXT window's fault view would bias the scale
                    # under exactly the time-varying schedules the
                    # controller targets
                    ctl.observe_wall(
                        max(now - eval_state["t"] - host_s, 1e-9),
                        eval_state["pred_step"] * done,
                    )
                eval_state["t"] = now
                eval_state["step"] = ranges[i][1]
            # position tag merged into the journal entry at decision time
            # (ISSUE 19): HOLD verdicts carry their epoch/window too, not
            # just the committed switches commit() annotates
            ctl.eval_context = {"epoch": int(epoch), "window": int(j)}
            dec = ctl.propose(eff, cur_batches, remaining)
            keys: tuple = ()
            if dec.candidate_batches is not None and not np.array_equal(
                dec.candidate_batches, cur_batches
            ):
                keys = self._aot_submit_candidate(
                    dec.candidate_batches, ranges, j
                )
            apply = dec.switch
            if apply and self._aot is not None and keys:
                missing = [k for k in keys if self._aot.get(k) is None]
                dead = [k for k in missing if self._aot.failed(k)]
                if dead:
                    # a candidate executable FAILED to compile: deferring
                    # would silently disable window rebalancing for the
                    # rest of the run (failed keys never resolve) — switch
                    # anyway and let dispatch's lazy-jit fallback compile
                    # foreground, logging once per key
                    for k in dead:
                        if k not in self._aot_failed_logged:
                            self._aot_failed_logged.add(k)
                            self.logger.warning(
                                f"online-dbs: candidate executable {k} "
                                "failed its background compile — switching "
                                "via the lazy fallback (one foreground "
                                "compile)"
                            )
                elif missing:
                    # warm gate: still compiling in the background — defer;
                    # the hysteresis re-evaluates at the next cadence
                    # boundary, by which time the speculative submit above
                    # has usually landed
                    ctl.note_deferred()
                    apply = False
            if apply:
                rplan = build_remainder_plan(
                    cur_pl, s_switch - cur_off, dec.candidate_batches,
                    bucket=self.cfg.bucket,
                )
                # the append is program-order safe only while the launch
                # frontier still sits at j: gather threads resolve steps
                # >= s_switch through this table, and only the controller
                # thread advances the frontier — assert that contract
                # instead of assuming it (G019 quiesce-discipline family)
                assert pipe.next_unlaunched() == j, (
                    "window rebalance raced the transfer pipeline: launch "
                    f"frontier moved {j} -> {pipe.next_unlaunched()} "
                    "during the solve"
                )
                seg_plans.append((s_switch, rplan))
                self.shares = np.asarray(dec.candidate_shares, dtype=np.float64)
                # the MEASURED switch cost covers the whole evaluation-to-
                # apply wall (device sync, signal build, solve, candidate
                # staging, remainder re-slice) — the host price an extra
                # switch actually pays. The plan build alone is microseconds
                # and would hollow out the margin/budget gates from the
                # second switch on.
                ev = ctl.commit(
                    dec,
                    time.perf_counter() - t_eval0,
                    epoch=int(epoch),
                    window=int(j),
                    step=int(s_switch),
                )
                self._rebalance_events.append(ev)
                self.recorder.meta["rebalance_events"] = self._rebalance_events
            if self.timing_model is None:
                # prediction for the stretch about to run, under the plan
                # that will actually govern it (the switched segment when
                # one was just applied) — next evaluation's feedback
                # reference
                nxt_pl, _ = self._seg_for_step(seg_plans, ranges[j][0])
                groups_list = [
                    self.topology.groups[d]
                    for d in self.topology.used_device_indices
                ]
                eval_state["pred_step"] = step_time(
                    eff, np.asarray(nxt_pl.batch_sizes, dtype=np.float64),
                    groups_list,
                )

    @staticmethod
    def _seg_for_step(seg_plans, s: int):
        """The (plan, step_offset) governing absolute epoch step ``s``:
        segments are (start_step, plan) in increasing order; a plan's local
        step index is ``s - start_step``."""
        pl, off = seg_plans[0][1], seg_plans[0][0]
        for start, p in seg_plans:
            if s >= start:
                pl, off = p, start
        return pl, off

    def _run_elastic_windows(
        self, plan, seg_plans, ranges, wkeys, faults: EpochFaults, epoch: int,
        aux_acc: List, aux_windows: List, aot_needed=(), controller=None,
    ):
        """The elastic window loop over an (extensible) segment schedule:
        gather/stage window k+1 on the transfer pipeline while window k
        dispatches, with each window's plan resolved through ``seg_plans``
        — the table a mid-epoch switch appends to for windows not yet
        staged. Shared by the epoch path and the switch-parity replay
        helper so both dispatch through identical machinery. Returns the
        first window's host data (the probes reuse it)."""
        cfg = self.cfg
        topo = self.topology
        mode = self._elastic_mode()
        meter = self._host_meter
        groups = topo.groups
        dev_order = topo.used_device_indices
        use_cache = self._use_device_cache

        def gather_window(s0: int, s1: int):
            # segment lookup by STEP: gather runs on pipeline threads, but
            # seg_plans only ever grows for windows the pipeline has not
            # launched yet — ordered by the executor's submit, program-order
            # safe (same discipline as _reshard_world's quiesced writes)
            pl, off = self._seg_for_step(seg_plans, s0)
            return [
                self._worker_inputs(
                    pl, self.rank_lo + r, s0 - off, s1 - off,
                    as_indices=use_cache,
                )
                for r in range(self.ws_local)
            ]

        def stage_window(d: int, i: int, data):
            """One device's puts for one window: each worker's arrays plus
            that window's absolute-step rng keys. Runs on the pipeline's
            per-device threads, concurrently across devices and with the
            controller's dispatch of the previous window."""
            w0, w1 = ranges[i]
            dev = topo.devices[d]
            staged = {}
            for r in groups[d]:
                gr = self.rank_lo + r
                kwin = wkeys[np.arange(w0, w1) * self.world_size + gr]
                staged[r] = tuple(
                    jax.device_put(a, dev) for a in data[r]
                ) + (jax.device_put(kwin, dev),)
            return staged

        # Per-worker constants for the whole epoch: one transfer, not one
        # per step (each device_put is a host round trip — 5 puts/worker/
        # step was most of the elastic path's dispatch overhead). Under a
        # time-varying schedule the values re-stage per window below.
        slow_dev = {}
        slow_vals: Dict[int, int] = {}
        for d in dev_order:
            dev = topo.devices[d]
            for r in groups[d]:
                gr = self.rank_lo + r
                slow_vals[r] = int(faults.slow_iters_per_step[gr])
                slow_dev[r] = jax.device_put(jnp.int32(slow_vals[r]), dev)
        time_varying = (
            getattr(self.injector, "faults_at", None) is not None
            and self._needs_iter_cost
        )

        eval_state = {"t": time.perf_counter(), "step": 0}
        first_data = None
        # Streaming host path, double-buffered per device: window k+1's host
        # gather AND its per-device puts run on the transfer pipeline while
        # window k dispatches/executes (train/pipeline.py). Window-local
        # rows, absolute-step rng keys — identical math to the whole-epoch
        # gather. Peak host memory: two windows, not the epoch.
        with WindowTransferPipeline(
            ranges, gather_window, stage_window, dev_order, meter=meter
        ) as pipe:
            # published for _quiesce_pipeline (G019): a recovery path
            # entered while this epoch's pipeline is live must drain it
            # before mutating the topology fields its threads read
            self._live_pipeline = pipe
            # kick window 0's gather/puts, then drain the compile barrier
            # while the staging threads work — compile time and transfer
            # time overlap instead of stacking
            pipe.prefetch(0)
            self._aot_wait_needed(aot_needed, epoch)
            for i, (w0, w1) in enumerate(ranges):
                # liveness at every window boundary: a mid-epoch preemption
                # is detected (and the epoch abandoned for re-solve) within
                # detect_misses windows, not at the next epoch
                self._check_health(epoch, w0 / max(plan.num_steps, 1))
                with self._trace.span("input_wait", cat="transfer"):
                    data, staged = pipe.get(i)
                if first_data is None:
                    first_data = data
                pl, _ = self._seg_for_step(seg_plans, w0)
                if time_varying:
                    # re-stage compute-mode injection at the window's
                    # instantaneous schedule value (scalar puts, only on
                    # change) — the injected load follows the schedule at
                    # window granularity, not the epoch mean
                    t_mid = float(epoch) + (w0 + w1) / (
                        2.0 * max(plan.num_steps, 1)
                    )
                    wf = self._window_faults_at(t_mid, pl)
                    if wf is not None:
                        for d in dev_order:
                            for r in groups[d]:
                                gr = self.rank_lo + r
                                v = int(wf.slow_iters_per_step[gr])
                                if slow_vals.get(r) != v:
                                    slow_vals[r] = v
                                    slow_dev[r] = jax.device_put(
                                        jnp.int32(v), topo.devices[d]
                                    )
                # one span per window (not per step): the dispatch track in
                # the trace shows window boundaries without per-step cost
                with self._trace.span("dispatch_window", cat="dispatch"):
                    if mode == "scan":
                        d0 = dev_order[0]
                        win_key = topo.group_shape_key(
                            [pl.workers[self.rank_lo + r].padded_batch
                             for r in groups[d0]],
                            w1 - w0,
                        )
                        self._dispatch_superstep_window(
                            staged[d0], d0, groups[d0], win_key, slow_dev,
                            aux_windows,
                        )
                    else:
                        self._dispatch_combine_steps(
                            staged, w1 - w0, slow_dev, aux_acc,
                            windowed=(mode == "window"),
                        )
                if controller is not None and (i + 1) % cfg.rebalance_every == 0:
                    self._maybe_window_rebalance(
                        controller, plan, seg_plans, ranges, pipe, i, epoch,
                        aux_acc, aux_windows, eval_state,
                    )
        # normal exit: the context manager already drained the pool; drop
        # the reference so _quiesce_pipeline skips the redundant close. On
        # exception paths the reference survives deliberately — recovery's
        # _reshard_world drains through it before touching topology.
        self._live_pipeline = None
        return first_data

    def _replay_window_segment(
        self, base_plan, rplan, s_offset: int, epoch: int, faults: EpochFaults
    ):
        """TEST/DEBUG: dispatch ONLY the remainder segment of an epoch from
        the CURRENT state — the 'fresh run started on the new plan from the
        same state' reference leg of the mid-epoch switch-parity contract
        (tests/test_online_dbs.py). Uses the same window loop, rng-key
        stream (absolute step indices over the BASE plan's step count) and
        dispatch machinery as the in-epoch switch path."""
        cfg = self.cfg
        base_key = jax.random.PRNGKey(cfg.seed * 7919 + epoch)
        wkeys = jax.random.split(
            base_key, self.world_size * max(base_plan.num_steps, 1)
        )
        ranges = [
            w for w in self._elastic_ranges(base_plan.num_steps)
            if w[0] >= s_offset
        ]
        aux_acc: List = []
        aux_windows: List = []
        self._run_elastic_windows(
            base_plan, [(s_offset, rplan)], ranges, wkeys, faults, epoch,
            aux_acc, aux_windows,
        )
        jax.block_until_ready(self.state.params)
        for aux in aux_windows:
            aux_acc.extend(self._aux_rows(aux))
        return aux_acc

    def _aux_rows(self, aux) -> np.ndarray:
        """A scanned window's aux ``[win, n_workers, 4 + r]`` as float64 rows
        ``[win * n_workers, 4]`` in (step, worker) order. The ``r`` entries a
        routed model's step adds (its arrivals, train/steps.py) are set
        aside for :meth:`_record_routing`."""
        rows = np.asarray(aux, dtype=np.float64)
        rows = rows.reshape(-1, rows.shape[-1])
        if rows.shape[1] > 4:
            self._routing_rows.extend(rows[:, 4:])
        return rows[:, :4]

    def _record_routing(self, epoch: int) -> None:
        """Hand the epoch's routing counts to graftscope (obs/routing.py)."""
        rows, self._routing_rows = self._routing_rows, []
        if rows:
            from dynamic_load_balance_distributeddnn_tpu.obs import routing

            routing.record_epoch(epoch, rows, self.spec.aux_shape)

    def _train_epoch_elastic(self, plan, faults: EpochFaults, epoch: int) -> Dict[str, float]:
        cfg = self.cfg
        topo = self.topology
        self.timekeeper.reset()
        mode = self._elastic_mode()
        meter = self._host_meter
        meter.reset()

        # Local topo ranks r (0..ws_local-1) own global worker rank_lo + r.
        aux_acc: List = []
        aux_windows: List = []  # scan mode: [win, n_workers, 4] per window
        sync_probe = 0.0
        base_key = jax.random.PRNGKey(cfg.seed * 7919 + epoch)
        wkeys = jax.random.split(base_key, self.world_size * max(plan.num_steps, 1))

        use_cache = self._use_device_cache
        ranges = self._elastic_ranges(plan.num_steps)

        # AOT service: queue this plan's missing executables (concurrent
        # background compiles) + speculative adjacent rungs; the barrier
        # below overlaps with the first window's staging.
        aot_needed = self._aot_stage_plan(plan)

        # Segment schedule: the whole epoch under the boundary plan, until
        # the online controller (rebalance=window) appends a remainder
        # segment at a mid-epoch switch.
        seg_plans: List = [(0, plan)]
        first_data = self._run_elastic_windows(
            plan, seg_plans, ranges, wkeys, faults, epoch,
            aux_acc, aux_windows, aot_needed=aot_needed,
            controller=self._window_controller(),
        )
        if mode == "scan":
            # flatten the scanned aux back into the per-step path's exact
            # (step, worker) row order so the float64 metric summation below
            # reproduces per-step results bit for bit; the first read waits
            # for the windows dispatched above
            with self._trace.span("device_wait", cat="wait"):
                for aux in aux_windows:
                    aux_acc.extend(self._aux_rows(aux))
                self._record_routing(epoch)
            cache_n = self.steps.superstep_cache_size()
            if cache_n > len(self._superstep_keys):
                self.logger.warning(
                    f"Epoch {epoch}: {cache_n} compiled superstep variants "
                    f"exceed the {len(self._superstep_keys)} dispatched "
                    "(shape, window) keys — a superstep input fell off its "
                    "static layout (graftlint G003/G006)"
                )
        data = first_data  # probes below reuse the first window's batches

        with self._trace.span("device_wait", cat="wait"):
            jax.block_until_ready(self.state.params)
        heartbeat()  # epoch pipeline drained
        # Probe AFTER the epoch's async pipeline has drained, so per-worker
        # timings measure that worker's executable alone, not queueing noise.
        # Compute-mode fault injection needs the probes too (per-example cost
        # calibration), even with the balancer off — otherwise a dbs-off A/B
        # arm would silently run without its injected straggler.
        dbs_probe_cost = 0.0
        if (
            self.timing_model is None
            and self._probe_this_epoch
            and (cfg.dynamic_batch_size or self._needs_iter_cost)
        ):
            t0p = time.perf_counter()
            with self._trace.span("probe", cat="probe"):
                sync_probe = self._probe_workers(plan, data, faults, epoch)
            dbs_probe_cost = time.perf_counter() - t0p
            self._sync_per_step = sync_probe
            # Replicated-state flag: everyone probes epoch 0 (pure config +
            # epoch), so gating later collectives on it can never diverge
            # across hosts even though LATER probe decisions are local.
            self._probes_ran = True
        else:
            sync_probe = self._sync_per_step
        if self.timing_model is not None:
            modeled = np.asarray(self.timing_model(plan), dtype=np.float64)
            for r in range(self.world_size):
                self.timekeeper.add_compute(r, modeled[r])
        for r in range(self.world_size):
            self.timekeeper.add_injected(r, float(faults.virtual_seconds[r]))

        flops_probe_overhead = 0.0
        if self._flops_per_padded_example is None:
            from dynamic_load_balance_distributeddnn_tpu.obs.flops import (
                compiled_flops,
            )

            # Cost analysis reads the ALREADY-COMPILED executable from the
            # AOT service when it holds this rung (zero extra compiles);
            # the lower+compile fallback only runs with the service off.
            # Excluded from the epoch wall either way (mirrors the fused
            # path's probe_overhead).
            t0 = time.perf_counter()
            d0 = topo.used_device_indices[0]
            r0 = topo.groups[d0][0]
            views = shard_views(self.state.params, topo.devices)
            b_pad = int(data[r0][0].shape[1])
            kind = "worker_first_idx" if use_cache else "worker_first"
            pre = None
            if self._aot is not None:
                pre = self._aot.get(self._aot_step_key(kind, b_pad, d0, None))
            if use_cache:
                idx0, w = data[r0]
                f = compiled_flops(
                    self.steps.worker_step_first_idx,
                    views[d0],
                    *self._device_cache_for(d0),
                    jnp.asarray(idx0[0]), jnp.asarray(w[0]),
                    base_key, jnp.int32(0),
                    compiled=pre,
                )
            else:
                x, y, w = data[r0]
                f = compiled_flops(
                    self.steps.worker_step_first,
                    views[d0],
                    jnp.asarray(x[0]), jnp.asarray(y[0]), jnp.asarray(w[0]),
                    base_key, jnp.int32(0),
                    compiled=pre,
                )
            self._flops_per_padded_example = f / max(b_pad, 1) if f else -1.0
            flops_probe_overhead = time.perf_counter() - t0

        with self._trace.span("device_wait", cat="wait"):  # per-step aux reads
            wloss = float(np.sum([float(a[0]) for a in aux_acc]))
            loss_sum = float(np.sum([float(a[1]) for a in aux_acc]))
            count = float(np.sum([float(a[2]) for a in aux_acc]))
        if self.n_proc > 1:
            # Per-process partial sums -> global (per-epoch metadata, host path)
            from jax.experimental import multihost_utils

            sums = multihost_utils.process_allgather(
                np.array([wloss, loss_sum, count], dtype=np.float64)
            )
            wloss, loss_sum, count = np.asarray(sums).reshape(-1, 3).sum(axis=0)
        return {
            "loss": loss_sum / max(count, 1.0),
            "wloss": wloss / max(plan.num_steps, 1),
            "sync_time": sync_probe * plan.num_steps,
            "probe_overhead": flops_probe_overhead,
            # run_epoch excludes this from epoch_wall (all paths) and
            # accounts it under total_probe_s / the probe_time series —
            # do NOT subtract it again anywhere downstream
            "dbs_probe_cost": dbs_probe_cost,
            # host-side cost of driving the epoch (enqueue + transfer walls,
            # balance/timing.py HostOverheadMeter) — the quantity the
            # superstep path exists to shrink (tests/test_superstep.py
            # reads the per-step value)
            "host_dispatch_s": meter.dispatch_s,
            "host_put_s": meter.put_s,
            "host_overhead_per_step_s": meter.per_step(plan.num_steps),
        }

    def _probe_workers(
        self, plan, data, faults: EpochFaults, epoch: int, reps: int = 3
    ) -> float:
        """Time each worker's step standalone (blocking, min over ``reps``)
        plus one combine — the balancer's signal. Called after the epoch's
        dispatch queue has drained. A full untimed warm pass runs first so
        every shape is compiled before any timing starts — otherwise a
        background compile of one worker's fresh shape contaminates another
        worker's host-side wall clock."""
        topo = self.topology
        cfg = self.cfg
        use_cache = self._use_device_cache
        key = jax.random.PRNGKey(cfg.seed * 104729 + epoch)
        views = shard_views(self.state.params, topo.devices)
        probe_step = (
            self.steps.worker_step_first_idx
            if use_cache
            else self.steps.worker_step_first
        )
        probe_kind = "worker_first_idx" if use_cache else "worker_first"
        staged = {}
        for d in topo.used_device_indices:
            dev = topo.devices[d]
            for r in topo.groups[d]:
                gr = self.rank_lo + r
                cache = self._device_cache_for(d) if use_cache else ()
                # AOT-compiled probe executable when the service holds this
                # rung (warm/stage submitted it); lazy jit otherwise
                b = int(data[r][0].shape[1])
                fn = self._aot_resolve(probe_kind, b, d, None, probe_step)
                staged[r] = (
                    cache
                    + tuple(jax.device_put(a[0], dev) for a in data[r])
                    + (
                        jax.device_put(key, dev),
                        jax.device_put(
                            jnp.int32(faults.slow_iters_per_step[gr]), dev
                        ),
                    ),
                    d,
                    fn,
                )
        # warm pass: execute everything once, untimed (with the AOT service
        # this compiles nothing — the executables already exist)
        for r, (args, d, fn) in staged.items():
            fn = self._scoped(
                self._aot_step_key(probe_kind, int(data[r][0].shape[1]), d, None),
                fn, (views[d],) + args,
            )
            staged[r] = (args, d, fn)
            _, aux = fn(views[d], *args)
            jax.block_until_ready(aux)
            heartbeat()

        # Dispatch-overhead floor (config.probe_overhead_correction): every
        # blocking probe wall includes one dispatch+sync round trip that is
        # NOT per-example device compute — O(100us) on a local backend, far
        # more on a remotely attached device. Measure it per device with a
        # tiny jitted op under BOTH sync
        # disciplines a probe may hit (block_until_ready and a scalar
        # readback) and take the MIN, so the correction can only be
        # conservative; the subtraction below is additionally floored at 20%
        # of the raw wall so a pathological overhead estimate can never
        # zero out a real measurement.
        ovh_by_dev: dict = {}
        if getattr(cfg, "probe_overhead_correction", True):
            for d in topo.used_device_indices:
                tx = jax.device_put(jnp.float32(0.0), topo.devices[d])
                y = _tiny_sync_probe(tx)
                jax.block_until_ready(y)
                float(y)  # compile + warm both sync paths
                e_block = e_read = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(_tiny_sync_probe(tx))
                    e_block = min(e_block, time.perf_counter() - t0)
                    t0 = time.perf_counter()
                    float(_tiny_sync_probe(tx))
                    e_read = min(e_read, time.perf_counter() - t0)
                ovh_by_dev[d] = min(e_block, e_read)
            self._probe_overhead_s = max(ovh_by_dev.values())
            # sanctioned bare wall: the dispatch-overhead estimate IS a raw
            # min-over-reps perf_counter pair by construction (a span cannot
            # express the paired-min discipline), and it is provenance
            # metadata, not a timed phase
            self.recorder.meta["probe_dispatch_overhead_s"] = round(  # graftlint: disable=G008
                self._probe_overhead_s, 6
            )

        def timed(d: int, args2, fn=probe_step):
            """(corrected wall, raw wall, last partial) of one probe step:
            min-over-reps blocking wall, minus the device's dispatch overhead
            for the corrected value. PAIRED measurements (the closed-loop
            iteration-cost tracking and _calibrate_iter_cost) must difference
            the RAW walls: the correction's 0.2*dt floor binds only on the
            small (clean) leg of a pair, so differencing corrected values
            re-introduces exactly the overhead the pairing exists to cancel.
            Standalone anchors (per-example cost, the solver's time vector)
            keep the corrected value."""
            dt, acc = float("inf"), None
            for _ in range(reps):
                t0 = time.perf_counter()
                acc, aux = fn(views[d], *args2)
                jax.block_until_ready(aux)
                dt = min(dt, time.perf_counter() - t0)
            heartbeat()
            return max(dt - ovh_by_dev.get(d, 0.0), 0.2 * dt), dt, acc

        lo, hi = self.rank_lo, self.rank_lo + self.ws_local
        init_epoch = bool(np.isnan(self.per_example_cost[lo:hi]).any())
        partials = {}
        for d in topo.used_device_indices:
            acc = None
            for r in topo.groups[d]:
                args, _, fn = staged[r]
                gr = self.rank_lo + r
                # probe with the non-donating first-step executable so reps
                # are safe; each worker is measured standalone
                dt, dt_raw, acc = timed(d, args, fn)
                # the probe wall doubles as the health monitor's latency
                # signal (original-rank indexed; SUSPECT verdicts feed the
                # degradation-ladder observability, the solver already
                # re-routes)
                self.health.observe_latency(self.active_ranks[gr], dt)
                w_plan = plan.workers[gr]
                self.timekeeper.add_compute(gr, dt * w_plan.steps)
                slow_n = float(faults.slow_iters_per_step[gr])
                if np.isnan(self.per_example_cost[gr]):
                    # First (injection-free) measurement seeds the clean
                    # cost; the refresh pass below re-anchors it fully warm,
                    # then it stays frozen. Re-deriving it every epoch by
                    # subtracting estimated injected cost is a positive
                    # feedback loop: any underestimate of the in-step
                    # iteration cost inflates "clean", which inflates next
                    # epoch's injection, without bound.
                    self.per_example_cost[gr] = max(dt, 1e-9) / max(
                        w_plan.batch_size, 1
                    )
                elif slow_n > 0 and not self._iter_cost_calibrated:
                    # Closed-loop iteration-cost tracking, ONLY until the
                    # fixed-point calibration has run. Two lessons from the
                    # round-3 TPU A/B (off-arm walls ramped 1.8->2.5s over 5
                    # "equal-injection" epochs):
                    #  - realized cost must come from a PAIRED measurement
                    #    (injected minus fresh-uninjected, below), not from
                    #    the frozen epoch-0 clean anchor: session drift
                    #    (runtime latency settling, chip clocks) between
                    #    the anchor and dt otherwise leaks into the estimate
                    #    and the EMA pumps slow_n without bound;
                    #  - once calibrated, the cost stays FROZEN so every
                    #    counted epoch injects the same strength, so two
                    #    arms of a comparison see the same injection.
                    zero = jax.device_put(jnp.int32(0), topo.devices[d])
                    _, raw_clean, _ = timed(d, args[:-1] + (zero,), fn)
                    # raw-minus-raw: the per-probe dispatch overhead appears
                    # in both walls and cancels; corrected values would pair
                    # a floored clean leg against an unfloored injected leg
                    realized = (dt_raw - raw_clean) / slow_n
                    if realized > 0 and np.isfinite(realized):
                        prev = self._iter_cost_s or realized
                        self._iter_cost_s = 0.5 * prev + 0.5 * realized
                elif slow_n == 0:
                    # Uninjected re-probe: drift the clean-cost anchor slowly
                    # toward reality so the adaptive scheduler's model tracks
                    # genuine speed changes. No feedback risk — injected
                    # measurements never enter this branch (explicitly gated:
                    # an injected dt leaking in here compounds into runaway
                    # slow_iters), so the calibration anchor stays clean.
                    fresh = max(dt, 1e-9) / max(w_plan.batch_size, 1)
                    self.per_example_cost[gr] = (
                        0.7 * self.per_example_cost[gr] + 0.3 * fresh
                    )
            partials[d] = acc
        if init_epoch:
            # Anchor-refresh pass: the very first timed probes run cold
            # (allocator, host caches, runtime settling) and over-read the
            # clean cost ~2x (measured on the CPU mesh). One more pass, now
            # fully warm, re-anchors every
            # uninjected worker BEFORE the calibration sizes the injection
            # off these anchors — otherwise the straggler factors are scaled
            # against an inflated "clean" and overshoot for the whole run
            # (anchors freeze after this epoch).
            for d in topo.used_device_indices:
                for r in topo.groups[d]:
                    gr = self.rank_lo + r
                    args, _, fn = staged[r]
                    if float(faults.slow_iters_per_step[gr]) != 0:
                        # a worker can be injected on its very first probed
                        # epoch (LuckyFaultInjector seeds iter cost from the
                        # standalone estimate) — its anchor was seeded from a
                        # cold AND injected dt; re-anchor on a zero-slow probe
                        zero = jax.device_put(jnp.int32(0), topo.devices[d])
                        args = args[:-1] + (zero,)
                    dt, _, _ = timed(d, args, fn)
                    self.per_example_cost[gr] = max(dt, 1e-9) / max(
                        plan.workers[gr].batch_size, 1
                    )
        if (
            self._needs_iter_cost
            and not self._iter_cost_calibrated
            and float(np.max(faults.slow_iters_per_step)) == 0
        ):
            # Converge the in-step iteration cost on the injection-free epoch,
            # BEFORE the first injected epoch. Without this, injection ramps
            # up over the first few epochs as the closed loop corrects the
            # standalone seed estimate — and an A/B benchmark would compare
            # arms at different injection strengths (the early weak-injection
            # epochs win every min(), systematically favoring whichever arm
            # sampled more of them).
            self._calibrate_iter_cost(staged, timed, plan)
            self._iter_cost_calibrated = True
        stacked = stack_partials(
            [partials[d] for d in topo.used_device_indices], self.mesh
        )
        # warm (compile) untimed, then time the pure collective+update; the
        # combine twin resolves from the AOT registry (warm-submitted) so the
        # warm call is a dispatch, not a lazy compile
        probe_name = self._combine_names()[1]
        combine_probe = self._aot_resolve_combine(
            probe_name, getattr(self.steps, probe_name)
        )
        combine_probe = self._scoped(
            (probe_name, self._aot_gen) + self._comm_sig, combine_probe, (self.state, stacked)
        )
        jax.block_until_ready(combine_probe(self.state, stacked).params)
        t0 = time.perf_counter()
        probed = combine_probe(self.state, stacked)
        jax.block_until_ready(probed.params)
        return time.perf_counter() - t0

    def _calibrate_iter_cost(self, staged, timed, plan) -> None:
        """Fixed-point iteration for the in-step synthetic-load cost: probe a
        step with a test trip count sized to ~double the clean step time,
        measure the realized per-iteration cost, and repeat until stable
        (each realized measurement IS the quantity being estimated, so this
        converges in 1-2 rounds). ``timed`` is _probe_workers' own probe
        timer, so calibration measures EXACTLY like the per-epoch tracking
        path — an asymmetry between the two is the kind of drift that caused
        the round-3 injection ramp. Runs on one worker, a handful of probe
        steps — calibration-epoch overhead only."""
        r0 = next(iter(staged))
        args, d, fn = staged[r0]
        gr = self.rank_lo + r0
        clean = float(self.per_example_cost[gr]) * max(
            plan.workers[gr].batch_size, 1
        )
        if not np.isfinite(clean) or clean <= 0:
            return
        dev = self.topology.devices[d]
        guess = self._iter_cost_s or calibrate_iter_cost()

        def timed_probe(slow_n: int) -> float:
            test_args = args[:-1] + (jax.device_put(jnp.int32(slow_n), dev),)
            # RAW wall: both legs of the paired delta below carry the same
            # dispatch overhead, so it cancels; the corrected value's 0.2*dt
            # floor fires only on the short clean leg and would bias the pair
            return timed(d, test_args, fn)[1]

        for _ in range(4):
            slow_n = max(int(round(clean / max(guess, 1e-12))), 1)
            # PAIRED measurement: a fresh uninjected step in the same breath,
            # so the delta isolates the synthetic load from session drift
            # (the frozen epoch-0 clean anchor bakes in early-session dispatch
            # latency — subtracting it mis-measured the realized cost ~3x on
            # the round-3 TPU run and the closed loop ramped injection).
            dt = timed_probe(slow_n)
            dt_clean = timed_probe(0)
            realized = (dt - dt_clean) / slow_n
            if realized <= 0 or not np.isfinite(realized):
                break
            done = abs(realized - guess) <= 0.05 * guess
            guess = realized
            if done:
                break
        self._iter_cost_s = guess
        self.logger.info(
            f"injection calibrated: {guess * 1e6:.2f}us/iter (in-step)"
        )

    # ------------------------------------------------------------- validate

    def _eval_sharded(self, xs, ys, mask=None, per_dev_cap: int = 1024,
                      cache_tag: Optional[str] = None):
        """Run ``fused_eval_step`` over the mesh on (xs, ys) in fixed-shape
        chunks (one compile), each chunk split across every device.
        ``mask``: optional per-element weight array (e.g. the LM's per-token
        mask, [n, bptt]); default is a per-row validity mask. Returns
        (loss_sum, correct, count)."""
        n = len(xs)
        # Evenly split the ceil'd chunk count so the final chunk wastes less
        # than one padded row per device (vs up to chunk-1 rows with a naive
        # cap-sized chunk), while keeping a single compiled shape.
        n_chunks = max(-(-n // (per_dev_cap * self.n_dev)), 1)
        per_dev = max(-(-n // (self.n_dev * n_chunks)), 1)
        chunk = per_dev * self.n_dev
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import batch_sharding

        bx = self._batch_axes

        def put(arr):
            if self.n_proc == 1:
                return jax.device_put(
                    arr, batch_sharding(self.mesh, arr.ndim, axis=bx)
                )
            rows = chunk // self.n_proc
            lo_p = self.proc_id * rows
            return jax.make_array_from_process_local_data(
                batch_sharding(self.mesh, arr.ndim, axis=bx),
                arr[lo_p : lo_p + rows],
            )

        # With the device cache on and a caller-declared stable input set
        # (cache_tag), the padded+sharded chunks upload once and are reused
        # every epoch — the reference re-walks its val DataLoader per epoch
        # on every rank (dbs.py:147). Untagged or cache-off calls stream one
        # chunk at a time (bounded HBM), exactly as before.
        cache_ok = self._use_device_cache and cache_tag is not None
        key = (cache_tag, chunk, n)
        cached = getattr(self, "_eval_chunk_cache", None)
        staged = None
        if cache_ok and cached is not None and cached[0] == key:
            staged = cached[1]
        elif cached is not None:
            # release before any restaging (drop BOTH references — the local
            # would otherwise pin the old chunk set in HBM through the loop)
            self._eval_chunk_cache = None
            cached = None

        loss_sum = correct = count = 0.0

        def run_chunk(xb, yb, mb):
            nonlocal loss_sum, correct, count
            args = (self.state.params, xb, yb, mb)
            step = self._scoped(
                ("fused_eval_step", self._aot_gen) + tuple(xb.shape),
                self.steps.fused_eval_step, args,
            )
            stats = step(*args)
            with self._trace.span("device_wait", cat="wait"):
                stats = np.asarray(jax.block_until_ready(stats))
            heartbeat()
            loss_sum += float(stats[0])
            correct += float(stats[1])
            count += float(stats[2])

        if staged is not None:
            for xb, yb, mb in staged:
                run_chunk(xb, yb, mb)
            return loss_sum, correct, count

        keep = [] if cache_ok else None
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            pad = chunk - (hi - lo)
            xb = np.pad(xs[lo:hi], ((0, pad),) + ((0, 0),) * (xs.ndim - 1))
            yb = np.pad(ys[lo:hi], ((0, pad),) + ((0, 0),) * (ys.ndim - 1))
            if mask is None:
                mb = np.zeros(chunk, dtype=np.float32)
                mb[: hi - lo] = 1.0
            else:
                mb = np.pad(mask[lo:hi], ((0, pad),) + ((0, 0),) * (mask.ndim - 1))
            dx, dy, dm = put(xb), put(yb), put(mb)
            if keep is not None:
                keep.append((dx, dy, dm))
            run_chunk(dx, dy, dm)
        if keep is not None:
            self._eval_chunk_cache = (key, keep)
        return loss_sum, correct, count

    def validate(self) -> "tuple[float, float]":
        """Full-test-set loss/accuracy, sharded over the mesh (the reference
        redundantly evaluates the full test set on EVERY rank, dbs.py:141-161;
        here it is evaluated once, split across all devices — same math)."""
        loss_sum, correct, count = self._eval_sharded(
            self.bundle.test_x, self.bundle.test_y, cache_tag="vision_test"
        )
        return loss_sum / max(count, 1.0), 100.0 * correct / max(count, 1.0)
