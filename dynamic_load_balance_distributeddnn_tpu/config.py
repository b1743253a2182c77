"""Run configuration.

Mirrors the reference CLI surface (parser.py:40-80 — 13 flags with the same
short names, defaults, and coercion rules) and adds TPU-specific knobs that
have no reference counterpart (bucketing, capacity headroom, fault-injection
mode, precision). The reference parses at module import into globals
(dbs.py:22, 32-44); here everything lives in one frozen dataclass that is
passed explicitly.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import List, Optional, Sequence, Tuple

# Family-default names mirror the reference switch (dbs.py:345-362); explicit
# variants expose the full Net/ constructor surface (e.g. ResNet-18 for
# BASELINE acceptance config #2).
MODELS = [
    "mnistnet",
    "resnet", "resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
    "densenet", "densenet121", "densenet169", "densenet201", "densenet161",
    "googlenet",
    "regnet", "regnetx200mf", "regnetx400mf", "regnety400mf",
    "transformer",
]
DATASETS = ["cifar10", "cifar100", "mnist", "wikitext2"]
# --lm_arch by name: "paper", or a published config kept as models/<name>.json.
# Any other value is the path of such a file (tests bring theirs at test widths)
LM_ARCHS = ["paper", "trinity_mini", "qwen3_next"]


def str2bool(v) -> bool:
    """Boolean coercion with the reference's accepted spellings (parser.py:8-16)."""
    if isinstance(v, bool):
        return v
    if v.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if v.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError("Boolean value expected.")


def _env_int(name: str, default: int) -> int:
    """Integer from the environment with a diagnosable failure: argparse's
    type= only validates CLI-passed values, so an env-driven DEFAULT that
    fails int() would otherwise kill parser construction with a contextless
    ValueError. Empty/whitespace counts as unset."""
    v = os.environ.get(name, "").strip()
    if not v:
        return default
    try:
        return int(v)
    except ValueError:
        raise SystemExit(f"env var {name} must be an integer, got {v!r}")


def device_map(v):
    """Worker→device map: a single device ordinal or a comma list, one entry
    per worker (the analogue of the reference's `-gpu 0,0,0,1`, parser.py:19-25).
    """
    if isinstance(v, int):
        return v
    if isinstance(v, (list, tuple)):
        return [int(g) for g in v]
    if "," in v:
        return [int(g) for g in v.split(",")]
    return int(v)


@dataclasses.dataclass(frozen=True)
class Config:
    # ---- reference-parity flags (parser.py:40-80) ----
    debug: bool = True                 # -d: tiny CPU-friendly smoke mode
    world_size: int = 4                # -ws: number of logical workers
    batch_size: int = 64               # -b: global batch size
    learning_rate: float = 0.01        # -lr
    epoch_size: int = 10               # -e
    dataset: str = "wikitext2"         # -ds
    dynamic_batch_size: bool = True    # -dbs: the DBS balancer on/off
    device: object = None              # -gpu analogue: worker→device map;
                                       # None = round-robin over all devices
    model: str = "transformer"         # -m
    fault_tolerance: bool = False      # -ft: straggler injection on/off
    fault_tolerance_chance: float = 0.1  # -ftc
    one_cycle_policy: bool = False     # -ocp
    disable_enhancements: bool = False  # -de: uniform grad weights + no OCP

    # ---- TPU-native knobs (new in this framework) ----
    seed: int = 1234                   # partitioner/model seed (dbs.py:313, 329)
    n_train: int = 0                   # >0: truncate the train split to this
                                       # many examples (tokens for the LM) —
                                       # controlled-scale runs through the real
                                       # entry point; 0 = full dataset
    momentum: float = 0.9              # SGD momentum (dbs.py:369)
    bucket: int = 16                   # batch shapes rounded up to a multiple of
                                       # this, bounding XLA recompiles while
                                       # keeping real per-worker compute ∝ batch
    capacity_factor: float = 2.0       # max worker share = factor/world_size;
                                       # bounds memory of the padded fast path
    snap_to_bucket: bool = True        # quantize per-worker batches to bucket
                                       # multiples: padded shape == true batch,
                                       # shape universe = a fixed ladder, so
                                       # time noise can't churn XLA compiles
    time_smoothing: float = 0.0        # EMA factor on the measured node-time
                                       # vector (0 = off, exact reference
                                       # semantics: raw last-epoch times)
    probe_overhead_correction: bool = True
                                       # subtract the per-device dispatch/sync
                                       # overhead (measured on a tiny jitted
                                       # op, the same blocking discipline as
                                       # the probes) from standalone probe
                                       # walls before they anchor the
                                       # per-example cost model or the
                                       # balancer signal. On a local backend
                                       # this is O(100us) and invisible; on a
                                       # remotely attached device an
                                       # uncorrected anchor inflates the
                                       # per-example cost, which oversizes
                                       # compute-mode injection by the same
                                       # factor. chip_smoke.py prints the
                                       # measured overhead and the realised
                                       # injection ratio (ROADMAP D1 decides
                                       # the flag from them). Paired
                                       # measurements (iter-cost calibration)
                                       # were already immune: it cancels
                                       # in their subtraction.
    probe_mode: str = "adaptive"       # "always": per-worker probe steps every
                                       # epoch (round-2 behavior; the reference
                                       # analogue, since it re-times every
                                       # epoch, dbs.py:226-250). "adaptive":
                                       # probe epochs 0-1 to anchor a linear
                                       # per-example cost model, then SKIP
                                       # probes and feed the solver modeled
                                       # times, re-probing every probe_every
                                       # epochs, when the injection episode
                                       # changes, or when a skipped epoch's
                                       # wall deviates probe_wall_tol from the
                                       # last probed wall — so the balancer's
                                       # signal costs ~nothing once converged
                                       # (the reference's signal is free too:
                                       # it times the epoch it already ran)
    probe_every: int = 5               # adaptive mode: max epochs between real
                                       # probe anchors
    probe_wall_tol: float = 0.25       # adaptive mode: relative epoch-wall
                                       # deviation (vs the last probed epoch,
                                       # probe cost excluded) that forces a
                                       # re-probe next epoch
    fault_mode: str = "virtual"        # "virtual": add simulated seconds to the
                                       # measured time vector (exact reference
                                       # semantics, dbs.py:94-129);
                                       # "compute": inject real on-device FLOPs
    straggler: str = ""                # deterministic per-worker slowdown
                                       # factors, e.g. "3,1,1,1" — the analogue
                                       # of the reference's contended GPU map
                                       # `-gpu 0,0,0,1` (README.md:23-28); mode
                                       # taken from fault_mode; "" = off
    precision: str = "float32"         # "float32" | "bfloat16" compute dtype
    data_dir: str = "./data"
    lm_data_dir: str = "./rnn_data/wikitext-2"
    log_dir: str = "./logs"
    stat_dir: str = "./statis"
    ckpt_dir: str = ""                 # non-empty → orbax checkpointing on
    bptt: int = 35                     # LM window (dbs.py:343)
    lm_arch: str = "paper"             # which language model -m transformer
                                       # builds: "paper" (the reference's
                                       # 2-layer, 200-wide post-norm LM), the
                                       # name of a published config kept in
                                       # models/ ("trinity_mini": AFMoE), or
                                       # the path of such a .json file
    lm_layers: str = ""                # published layers kept ("1,4,5,6,7");
                                       # "" = all. Published architectures only
    lm_experts_held: str = ""          # routed experts this chip holds, as
                                       # "first:end"; "" = all. The router
                                       # still ranges over every published one
    lm_dropout: float = 0.2            # the paper LM's dropout (dbs.py:341);
                                       # a published architecture has none
    seq_parallel: str = ""             # "ring" | "ulysses": train the LM with
                                       # the SEQUENCE axis sharded over the
                                       # mesh (long-context mode; bptt scales
                                       # with the mesh). "" = DBS data-parallel
    grad_clip: float = 0.0             # LM path uses 0.25 (dbs.py:274)
    profile_dir: str = ""              # non-empty → jax.profiler traces
    use_pallas: bool = False           # route GroupNorm/xent through the
                                       # Pallas kernels (ops/pallas/) —
                                       # numerics-preserving kernel routing
    remat: bool = False                # jax.checkpoint the training forward:
                                       # activations recomputed in the
                                       # backward (exact math; HBM for ~1/3
                                       # extra FLOPs — the standard TPU
                                       # memory lever)
    fused_dbs: bool = False            # run the DBS balancer on the fused
                                       # capacity-padded SPMD path: every
                                       # worker is padded to the max bucketed
                                       # batch, so ONE compiled scan serves
                                       # every rebalanced plan (no per-step
                                       # Python dispatch); the time signal
                                       # comes from untimed per-worker probe
                                       # steps. Trades <= capacity_factor x
                                       # padding FLOPs for zero dispatch.
                                       # Needs one worker per chip.
    grad_comm: str = "flat"            # "flat": one psum over the whole data
                                       # mesh (the reference structure).
                                       # "hier": two-level ICI/DCN collective
                                       # (ISSUE 12) — full-precision in-host
                                       # reduce-scatter, ONE compressed
                                       # all-reduce hop across hosts on
                                       # grad_comm_wire (error-feedback
                                       # residuals in the TrainState), then
                                       # an in-host all-gather. Needs a
                                       # (host, device) factorization: real
                                       # multi-host processes, or a
                                       # synthetic --hier_hosts split on CPU
                                       # tiers; falls back to flat (one log
                                       # line) when none exists.
    grad_comm_wire: str = "int8"       # hier DCN hop wire format
                                       # (parallel/wire.py): "fp32" = exact
                                       # (structure-only win), "int8" = 127
                                       # levels, stochastic rounding
                                       # (unbiased), int16 wire sum — half
                                       # the f32 bytes on 1/D of the tree;
                                       # "int4" = 7 levels, round-to-nearest
                                       # (biased; the error-feedback
                                       # residual makes it convergent),
                                       # int8 wire sum — a quarter.
    dcn_bandwidth_probe: bool = False  # measure both link classes at init
                                       # (parallel/mesh.py
                                       # probe_link_bandwidth) and fall back
                                       # to the flat combine when the
                                       # three-phase hier structure does not
                                       # beat one flat psum on this fabric
                                       # (single-host meshes, symmetric
                                       # links). Off = trust --grad_comm.
    hier_hosts: int = 0                # synthetic host-axis size for
                                       # single-process meshes (CPU tiers,
                                       # tests): split
                                       # the n devices into this many "host"
                                       # groups. 0 = derive from the real
                                       # process topology.
    hier_levels: str = ""              # N-level topology declaration
                                       # (ISSUE 17): comma list of
                                       # name:size OUTER levels,
                                       # outermost (slowest link) first —
                                       # e.g. "pod:2,host:2"; the innermost
                                       # device level is implicit and
                                       # absorbs the remainder. Prefix
                                       # "learned" (bare, or
                                       # "learned,host:2,...") merges
                                       # adjacent levels the bandwidth
                                       # probe measures as the same link
                                       # class. "" = the two-level
                                       # host/device split (hier_hosts /
                                       # process topology).
    grad_comm_wires: str = ""          # per-hop wire codecs for the tree
                                       # combine, outermost hop first,
                                       # comma list (innermost must be
                                       # fp32), e.g. "int4,int8,fp32";
                                       # "auto" = choose per hop from the
                                       # bandwidth probe's measured link
                                       # rates (parallel/wire.py
                                       # choose_wires). "" = legacy:
                                       # grad_comm_wire on the outermost
                                       # hop, fp32 below.
    dcn_probe_gate: float = 0.95       # hier-vs-flat probe verdict ratio:
                                       # hier wins when its measured wall
                                       # < gate * flat wall (the margin a
                                       # structural change must clear
                                       # before it is worth a recompile
                                       # universe).
    compress_grads: str = ""           # "int8": gradient collective quantized
                                       # to 127 levels (shared pmax scale,
                                       # stochastic rounding — unbiased, no
                                       # error feedback needed), summed in
                                       # int16: half the wire bytes. Opt-in;
                                       # fused paths, and with shard_update
                                       # the ZeRO-1 reduce-scatter rides the
                                       # same wire (PR 13).
    grad_accum: int = 1                # fused-path micro-batching: each step's
                                       # per-device batch is processed in this
                                       # many scanned slices, grads summed
                                       # before the collective (exact under
                                       # per-example weighting); activation
                                       # memory / grad_accum. Absent in the
                                       # reference (SURVEY §2.5).
    shard_update: bool = False         # cross-replica weight-update sharding
                                       # (ZeRO-1 analogue), generic over
                                       # optax transforms since PR 13:
                                       # reduce-scatter grads, tx.update on
                                       # the 1/n flat opt-state chunk,
                                       # all-gather the delta — optimizer
                                       # memory / n_dev. Composes with the
                                       # fused paths, the elastic DBS
                                       # dispatch (zero-1 combine twins),
                                       # elastic world size (chunks re-shard
                                       # onto the survivor mesh),
                                       # compress_grads (quantized
                                       # reduce-scatter) and grad_comm=hier
                                       # (the in-host RS + compressed DCN
                                       # hop), and since PR 18 scan-mode
                                       # supersteps and packed epochs (the
                                       # axis-free zero-1 twin runs inside
                                       # the compiled window). Excluded:
                                       # shard_update x compress_grads keeps
                                       # the windowed cadence in scan
                                       # topologies (stochastic rounding is
                                       # no identity even on a size-1 axis),
                                       # and non-elementwise transforms
                                       # (global-norm clipping INSIDE tx)
                                       # are out of contract — the per-worker
                                       # grad_clip runs before the combine
                                       # and is fine.
    stream_chunk_steps: int = 128      # host data path streams the epoch in
                                       # windows of this many steps (gather +
                                       # device_put of window k+1 overlaps
                                       # device compute of window k), bounding
                                       # peak host memory to O(2·chunk·batch)
                                       # instead of the whole epoch; 0 = off.
                                       # No-op when the epoch fits one window.
    warm_start: bool = False           # pre-compile the whole bucketed batch
                                       # shape ladder before epoch 0, so DBS
                                       # rebalances never pay an XLA compile
                                       # inside a timed epoch (benchmarks set
                                       # this; the persistent compile cache
                                       # makes it cheap on reruns)
    aot_warm: bool = True              # run the compile universe through the
                                       # async AOT compile service
                                       # (runtime/compiler.py): executables
                                       # are jit(...).lower(abstract).
                                       # compile()d concurrently on a thread
                                       # pool — no dummy execution, no
                                       # device_put traffic — and hot
                                       # dispatch resolves the compiled
                                       # objects from the service. off = the
                                       # legacy execute-to-compile warm loop
                                       # (the reference leg of
                                       # tests/test_aot_compiler.py; see
                                       # graftlint G007)
    aot_pool: int = 0                  # AOT compile pool width; 0 = auto
                                       # (min(8, cpus), >= 2). Lowering is
                                       # single-flight (GIL-bound) either
                                       # way; the pool parallelizes the
                                       # backend-compile phase
    aot_backend: str = "thread"        # "thread": backend compiles run on
                                       # the in-process pool (XLA releases
                                       # the GIL, but concurrent program
                                       # compiles contend on a shared
                                       # resource in the XLA:CPU emitter —
                                       # and on small hosts on the machine
                                       # itself). "process": the backend-
                                       # compile phase runs in subprocess
                                       # workers feeding the run's pinned
                                       # persistent cache; the in-process
                                       # step becomes a guaranteed cache-hit
                                       # replay (runtime/compile_worker.py).
                                       # Worth it on many-core hosts where
                                       # per-program compiles no longer
                                       # share an emitter (no chip run
                                       # has measured it: ROADMAP D4).
    aot_workers: int = 0               # process-backend subprocess count
                                       # (0 = auto: min(4, cpus)); each
                                       # worker is a full spawned JAX
                                       # runtime (~2-4 s startup, paid once,
                                       # overlapped with the run's own
                                       # warm-up)
    release_on_close: bool = False     # the AOT service, when closed, also
                                       # clears JAX's in-memory caches, so
                                       # that the device gives back what its
                                       # programs had reserved: for a process
                                       # that uses the chip after its trainer
    aot_speculate: bool = True         # when a rebalance dispatches a
                                       # ladder rung, background-compile the
                                       # ADJACENT rungs (±bucket) while the
                                       # epoch executes, so the next
                                       # rebalance's fresh layout is already
                                       # compiled and the recompile sentinel
                                       # stays silent (dbs runs only)
    speculate_scan: bool = True        # scan-mode shape-TUPLE speculation:
                                       # predict the solver's next share
                                       # vector (EMA of per-worker share
                                       # deltas, balance/solver.py
                                       # ShareTrajectoryPredictor), quantize
                                       # it exactly like the plan builder,
                                       # and background-compile the
                                       # predicted superstep (shapes,
                                       # window) keys in the epoch's untimed
                                       # tail. Mispredictions cost only
                                       # background work; hits remove the
                                       # last steady-state foreground
                                       # compile class (tuples have no
                                       # finite ±bucket adjacency).
                                       # Requires aot_speculate.
    device_cache: str = "auto"         # "auto"|"on"|"off": keep the train
                                       # arrays resident in HBM and feed each
                                       # epoch by INDEX (on-device gather in
                                       # the compiled step). The reference
                                       # rebuilds a DataLoader per epoch
                                       # (dbs.py:394-395); the TPU-native
                                       # equivalent makes the per-epoch
                                       # reshard an index permutation — per
                                       # epoch host->device traffic drops
                                       # from the whole dataset to [steps,
                                       # batch] int32. auto = on when the
                                       # arrays fit device_cache_mb (vision
                                       # path; multi-host replicates the
                                       # cache on every process's devices).
    device_cache_mb: int = 512         # HBM budget for the device cache
    coordinator: str = ""              # multi-host rendezvous: coordinator
                                       # "host:port" — the analogue of the
                                       # reference's MASTER_ADDR/MASTER_PORT +
                                       # init_process_group (dbs.py:513-515),
                                       # mapped to jax.distributed.initialize.
                                       # Non-empty -> the CLI initializes the
                                       # distributed runtime before building
                                       # the engine. Env: DBS_COORDINATOR.
    num_processes: int = 0             # multi-host: total process count
                                       # (dbs.py:538's world of processes; on
                                       # TPU pods 0 lets JAX autodetect).
                                       # Env: DBS_NUM_PROCESSES.
    process_id: int = -1               # multi-host: this process's id; -1
                                       # lets JAX autodetect (TPU pods).
                                       # Env: DBS_PROCESS_ID.
    superstep: str = "auto"            # "auto"|"on"|"off": elastic-path
                                       # supersteps (ISSUE 2). auto/on: the
                                       # elastic hot loop runs windowed — a
                                       # single-device worker group executes
                                       # a whole window as ONE compiled
                                       # lax.scan (combine cadence inside the
                                       # scan, bitwise-identical math), and
                                       # multi-device groups dispatch one
                                       # window-sliced executable per worker
                                       # per step (on-device step slicing)
                                       # behind a per-device double-buffered
                                       # transfer pipeline. off: the legacy
                                       # per-step dispatch loop (kept as the
                                       # parity/overhead reference).
    superstep_window: int = 16         # scan-mode superstep window cap: the
                                       # compiled window is a fully UNROLLED
                                       # scan (a rolled while-loop lowers
                                       # with different reduction blocking
                                       # and breaks bitwise parity with the
                                       # per-step path), so program size and
                                       # compile time scale with the window;
                                       # 16 already amortizes dispatch 16x.
                                       # Windowed (multi-device) mode streams
                                       # by stream_chunk_steps as before.
    trace: str = "off"                 # graftscope span tracing (obs/trace.py):
                                       # "on" = unbounded event buffer, "ring"
                                       # = keep the last trace_ring events
                                       # (long runs), "off" = zero-cost no-op
                                       # (every call site degrades to one
                                       # attribute check; no jax is touched,
                                       # so disabled mode is sentinel-silent
                                       # under the compile guards). Traces
                                       # save as Chrome-trace JSON under
                                       # trace_dir at end of run — open in
                                       # ui.perfetto.dev or summarize with
                                       # the `graftscope` CLI.
    trace_ring: int = 1_000_000        # ring-mode event cap (~100 bytes/event)
    trace_dir: str = "./traces"        # where run traces are written
    trace_annotations: bool = False    # ALSO wrap each span in a
                                       # jax.profiler.TraceAnnotation so host
                                       # spans line up with device timelines
                                       # inside a --profile_dir trace
    trace_spool: str = ""              # flight recorder (ISSUE 15): non-empty
                                       # = directory for a crash-durable
                                       # per-process spool file the tracer
                                       # streams into via a background
                                       # flusher (length-framed JSONL; a
                                       # SIGKILL loses at most the last
                                       # flush interval). Stitch the
                                       # survivors' + victims' spools with
                                       # `graftscope postmortem <dir>`.
                                       # Requires trace != off.
    trace_spool_flush_s: float = 0.25  # spool flush cadence (also flushes
                                       # at the 512-event watermark)
    trace_spool_fsync: bool = False    # fsync each spool flush: survives
                                       # power loss, not just process death
                                       # (costs flush latency)
    elastic: str = "off"               # "on"|"off": elastic world size
                                       # (ISSUE 6). on: a per-worker health
                                       # monitor (runtime/health.py) feeds
                                       # the engine's recovery path — a
                                       # CONFIRMED-lost worker is dropped,
                                       # the partition re-solved over
                                       # survivors (the same solver code
                                       # path as a straggler re-route),
                                       # data re-sharded, executables for
                                       # the new world size warmed through
                                       # the AOT service, and training
                                       # continues from the epoch-start
                                       # consistent snapshot; a recovered
                                       # worker is readmitted at the next
                                       # epoch boundary with a probe-seeded
                                       # share. Costs one host snapshot of
                                       # the TrainState per epoch while on.
                                       # Single-process recovery only
                                       # (multi-host runs get detection +
                                       # a diagnosable abort; see README
                                       # "Fault tolerance").
    elastic_detect_misses: int = 2     # consecutive missed liveness checks
                                       # that CONFIRM a worker loss (1 miss
                                       # is indistinguishable from jitter —
                                       # same two-strike hysteresis as the
                                       # adaptive probe scheduler)
    elastic_latency_factor: float = 8.0  # probe latency over this multiple
                                       # of the fleet median marks a worker
                                       # SUSPECT (observability; the solver
                                       # already re-routes data away)
    elastic_readmit: str = "epoch"     # "epoch": recovered workers rejoin
                                       # at the next epoch boundary with a
                                       # probe-seeded share; "off": once
                                       # lost, a worker stays out (strictly
                                       # shrinking fleet)
    elastic_max_recoveries: int = 8    # recovery attempts before the run
                                       # gives up (a fleet losing workers
                                       # faster than this is not a fleet)
    rebalance: str = "epoch"           # "epoch"|"window": DBS control-loop
                                       # cadence (ISSUE 11). epoch: the
                                       # reference semantics — one inverse-
                                       # time re-solve per epoch boundary.
                                       # window: an online hysteresis
                                       # controller (balance/controller.py)
                                       # re-evaluates every rebalance_every
                                       # windows inside the elastic epoch,
                                       # and retires the remaining windows
                                       # under a new plan when the predicted
                                       # remaining-epoch win beats the
                                       # measured switch cost — the time-
                                       # varying straggler scenario
                                       # (sin/ramp schedules) the epoch
                                       # cadence cannot touch. Elastic
                                       # dispatch paths only; single-process
                                       # only (the switch decision folds
                                       # locally measured walls).
    rebalance_every: int = 1           # window cadence: evaluate the online
                                       # controller every K dispatch windows
    rebalance_hysteresis: float = 0.1  # relative hysteresis: switch only
                                       # when the predicted win is at least
                                       # this fraction of the predicted
                                       # remaining-epoch time
    rebalance_margin: float = 3.0      # absolute hysteresis: predicted win
                                       # must exceed margin x the measured
                                       # (EMA) switch cost
    rebalance_budget_frac: float = 0.5 # regret-style budget: cumulative
                                       # switch spend may never exceed this
                                       # fraction of cumulative banked wins
                                       # (+ the pending win) — the no-thrash
                                       # brake when costs drift above
                                       # estimates. Needs margin >= 1/frac
                                       # for the first switch to be
                                       # admissible.
    rebalance_rate_alpha: float = 0.5  # EMA weight on the newest per-worker
                                       # rate sample in the controller
    fault_schedule: str = "none"       # "none"|"sin"|"ramp"|"spike"|
                                       # "diurnal"|"brownout"|"killstorm":
                                       # time-VARYING straggler schedule over
                                       # the --straggler factors (faults.py
                                       # ScheduledStragglerInjector): factors
                                       # follow the schedule gain within
                                       # epochs — the scenario the window-
                                       # cadence controller exists for.
                                       # none = the static profile; brownout/
                                       # killstorm draw per-worker victim
                                       # sets from --seed.
    fault_period: float = 2.0          # schedule period in epochs (sin:
                                       # full cycle; ramp: rise time)
    packed: str = "auto"               # "auto"|"on"|"off": single-device
                                       # packed epochs — when every worker
                                       # lives on ONE chip (the contention
                                       # topology, e.g. the reference's
                                       # -gpu 0,0,0,0), concatenate the
                                       # workers' true-width batches into one
                                       # compiled whole-epoch scan (psum on a
                                       # 1-chip mesh is identity, so the
                                       # weighted-sum combine is unchanged).
                                       # True per-worker batch sizes — only
                                       # <= ws*bucket rows of padding, vs the
                                       # capacity layout's 2x — and zero
                                       # per-step Python dispatch. Balancer
                                       # signal still comes from the
                                       # standalone per-worker probes.

    def __post_init__(self):
        if self.model not in MODELS:
            raise ValueError(f"invalid model {self.model!r}; choose from {MODELS}")
        if self.dataset not in DATASETS:
            raise ValueError(f"invalid dataset {self.dataset!r}; choose from {DATASETS}")
        if self.lm_arch not in LM_ARCHS and not self.lm_arch.endswith(".json"):
            raise ValueError(f"invalid lm_arch {self.lm_arch!r}; choose from {LM_ARCHS} "
                             "or give the path of a published config (.json)")
        if self.world_size < 1:
            raise ValueError("world_size must be >= 1")
        if isinstance(self.device, list) and len(self.device) != self.world_size:
            raise ValueError("device map length must equal world_size")
        if self.fault_mode not in ("virtual", "compute"):
            raise ValueError("fault_mode must be 'virtual' or 'compute'")
        if self.probe_mode not in ("adaptive", "always"):
            raise ValueError("probe_mode must be 'adaptive' or 'always'")
        if self.straggler and len(self.straggler_factors()) != self.world_size:
            raise ValueError("straggler factor list length must equal world_size")
        if self.compress_grads not in ("", "int8"):
            raise ValueError("compress_grads must be '' or 'int8'")
        if self.grad_comm not in ("flat", "hier"):
            raise ValueError("grad_comm must be 'flat' or 'hier'")
        if self.grad_comm_wire not in ("fp32", "int8", "int4"):
            raise ValueError("grad_comm_wire must be 'fp32', 'int8' or 'int4'")
        if self.hier_hosts < 0:
            raise ValueError("hier_hosts must be >= 0 (0 = real topology)")
        if self.hier_levels:
            from dynamic_load_balance_distributeddnn_tpu.parallel.topology import (
                parse_hier_levels,
            )

            spec = self.hier_levels.strip()
            if spec == "learned" or spec.startswith("learned,"):
                spec = spec[len("learned"):].lstrip(",")
            parse_hier_levels(spec)  # raises on malformed entries
        if self.grad_comm_wires and self.grad_comm_wires != "auto":
            for w in self.grad_comm_wires.split(","):
                if w.strip() not in ("fp32", "int8", "int4"):
                    raise ValueError(
                        f"grad_comm_wires entry {w.strip()!r} must be "
                        "'fp32', 'int8' or 'int4' (or the whole flag "
                        "'auto')"
                    )
        if not (0.0 < self.dcn_probe_gate <= 1.5):
            raise ValueError("dcn_probe_gate must be in (0, 1.5]")
        if self.grad_comm == "hier" and self.compress_grads:
            raise ValueError(
                "grad_comm=hier subsumes compress_grads: the cross-host hop "
                "already rides --grad_comm_wire (the flat int8 collective "
                "stays available via compress_grads with grad_comm=flat)"
            )
        if self.grad_comm == "hier" and self.seq_parallel:
            raise ValueError(
                "grad_comm=hier applies to the data-parallel gradient "
                "combine; the sequence-parallel modes shard the sequence "
                "axis instead"
            )
        if self.device_cache not in ("auto", "on", "off"):
            raise ValueError("device_cache must be 'auto', 'on' or 'off'")
        if self.packed not in ("auto", "on", "off"):
            raise ValueError("packed must be 'auto', 'on' or 'off'")
        if self.superstep not in ("auto", "on", "off"):
            raise ValueError("superstep must be 'auto', 'on' or 'off'")
        if self.elastic not in ("on", "off"):
            raise ValueError("elastic must be 'on' or 'off'")
        if self.elastic_detect_misses < 1:
            raise ValueError("elastic_detect_misses must be >= 1")
        if self.elastic_readmit not in ("epoch", "off"):
            raise ValueError("elastic_readmit must be 'epoch' or 'off'")
        if self.rebalance not in ("epoch", "window"):
            raise ValueError("rebalance must be 'epoch' or 'window'")
        if self.rebalance_every < 1:
            raise ValueError("rebalance_every must be >= 1")
        if self.rebalance_hysteresis < 0 or self.rebalance_margin < 0:
            raise ValueError("rebalance hysteresis/margin must be >= 0")
        if self.rebalance_budget_frac <= 0:
            raise ValueError("rebalance_budget_frac must be > 0")
        if not 0.0 < self.rebalance_rate_alpha <= 1.0:
            raise ValueError("rebalance_rate_alpha must be in (0, 1]")
        if self.fault_schedule not in (
            "none", "sin", "ramp", "spike", "diurnal", "brownout", "killstorm"
        ):
            raise ValueError(
                "fault_schedule must be 'none', 'sin', 'ramp', 'spike', "
                "'diurnal', 'brownout' or 'killstorm'"
            )
        if self.fault_period <= 0:
            raise ValueError("fault_period must be > 0 epochs")
        if self.fault_schedule != "none" and not self.straggler:
            raise ValueError(
                "fault_schedule needs --straggler factors to modulate"
            )
        if self.rebalance == "window" and not self.dynamic_batch_size:
            raise ValueError(
                "rebalance=window is a DBS control-loop cadence; it needs "
                "dynamic_batch_size on"
            )
        if self.rebalance == "window" and self.fused_dbs:
            raise ValueError(
                "rebalance=window retires windows mid-epoch on the elastic "
                "dispatch paths; the fused-DBS whole-epoch scan has no "
                "window boundary to act at"
            )
        if self.trace not in ("on", "off", "ring"):
            raise ValueError("trace must be 'on', 'off' or 'ring'")
        if self.trace_ring < 1:
            raise ValueError("trace_ring must be >= 1")
        if self.trace_spool_flush_s <= 0:
            raise ValueError("trace_spool_flush_s must be > 0")
        if self.trace_spool and self.trace == "off":
            # the flight recorder streams TRACER events — with tracing off
            # it would silently record nothing for exactly the chaos run it
            # was configured to protect
            raise ValueError(
                "trace_spool requires tracing: set --trace ring (or on)"
            )
        if self.superstep_window < 1:
            raise ValueError("superstep_window must be >= 1")
        if self.aot_pool < 0:
            raise ValueError("aot_pool must be >= 0 (0 = auto)")
        if self.aot_backend not in ("thread", "process"):
            raise ValueError("aot_backend must be 'thread' or 'process'")
        if self.aot_workers < 0:
            raise ValueError("aot_workers must be >= 0 (0 = auto)")
        if self.compress_grads and self.dynamic_batch_size and not self.fused_dbs:
            raise ValueError(
                "compress_grads rides a fused path (the elastic DBS combine "
                "keeps exact f32 gradients); enable fused_dbs to combine it "
                "with the balancer"
            )
        if self.grad_accum > 1 and self.dynamic_batch_size and not self.fused_dbs:
            raise ValueError(
                "grad_accum rides a fused path; the elastic DBS path controls "
                "memory by shrinking per-worker batches instead"
            )

    def straggler_factors(self) -> List[float]:
        return [float(x) for x in self.straggler.split(",")] if self.straggler else []

    def lm_kept_layers(self) -> List[int]:
        """--lm_layers as published layer indices; empty = all."""
        return [int(i) for i in self.lm_layers.split(",")] if self.lm_layers else []

    def lm_expert_range(self) -> Optional[Tuple[int, int]]:
        """--lm_experts_held as (first, end); None = all."""
        if not self.lm_experts_held:
            return None
        first, end = (int(v) for v in self.lm_experts_held.split(":"))
        return first, end

    @property
    def num_classes(self) -> int:
        # dbs.py:333-335
        return 100 if self.dataset == "cifar100" else 10

    def worker_device_ids(self, n_devices: int) -> List[int]:
        """Resolve the worker→device map. An int (including 0, like the
        reference's `-gpu 0`) pins every worker to that device; a list is
        used verbatim; None (the default) round-robins workers over the
        available devices (one worker per chip when ws == n_devices)."""
        if isinstance(self.device, list):
            return [d % n_devices for d in self.device]
        if isinstance(self.device, int):
            return [self.device % n_devices] * self.world_size
        return [r % n_devices for r in range(self.world_size)]

    def base_filename(self) -> str:
        """Config-encoded artifact name, same fields as the reference
        (dbs.py:54-61); `{}` is the worker-rank placeholder."""
        name = (
            f"{self.model}-{self.dataset}-debug{int(self.debug)}-n{self.world_size}"
            f"-bs{self.batch_size}-lr{self.learning_rate:.4f}-ep{self.epoch_size}"
            f"-dbs{int(self.dynamic_batch_size)}-ft{int(self.fault_tolerance)}"
            f"-ftc{self.fault_tolerance_chance:f}-node{{}}"
            f"-ocp{int(self.one_cycle_policy)}"
        )
        if self.disable_enhancements:
            name = "puredbs=" + name
        if self.seq_parallel:
            name = f"sp_{self.seq_parallel}=" + name  # distinct artifact lineage
        return name

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


def get_parser() -> argparse.ArgumentParser:
    """CLI with the reference's 13 flags (same short names/defaults,
    parser.py:40-80) plus this framework's TPU knobs."""
    p = argparse.ArgumentParser(
        description="Dynamic Batch Size for Distributed DNN Training — TPU-native"
    )
    d = Config()
    p.add_argument("-d", "--debug", type=str2bool, default=d.debug,
                   help="Debug mode: small run on whatever backend is present.")
    p.add_argument("-ws", "--world_size", type=int, default=d.world_size)
    p.add_argument("-b", "--batch_size", type=int, default=d.batch_size)
    p.add_argument("-lr", "--learning_rate", type=float, default=d.learning_rate)
    p.add_argument("-e", "--epoch_size", type=int, default=d.epoch_size)
    p.add_argument("-ds", "--dataset", type=str, default=d.dataset, choices=DATASETS)
    p.add_argument("-dbs", "--dynamic_batch_size", type=str2bool, default=d.dynamic_batch_size)
    p.add_argument("-gpu", "-dev", "--device", type=device_map, default=None,
                   help="Worker→device map, e.g. '0,0,0,1', or a single ordinal "
                        "to pin all workers (reference -gpu). Default: "
                        "round-robin, one worker per device.")
    p.add_argument("-m", "--model", type=str, default=d.model, choices=MODELS)
    p.add_argument("-ft", "--fault_tolerance", type=str2bool, default=d.fault_tolerance)
    p.add_argument("-ftc", "--fault_tolerance_chance", type=float, default=d.fault_tolerance_chance)
    p.add_argument("-ocp", "--one_cycle_policy", type=str2bool, default=d.one_cycle_policy)
    p.add_argument("-de", "--disable_enhancements", type=str2bool, default=d.disable_enhancements)
    # TPU-native extras
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--n_train", type=int, default=d.n_train,
                   help="Truncate the train split to N examples (LM: tokens); 0 = full.")
    p.add_argument("--momentum", type=float, default=d.momentum)
    p.add_argument("--bucket", type=int, default=d.bucket)
    p.add_argument("--capacity_factor", type=float, default=d.capacity_factor)
    p.add_argument("--snap_to_bucket", type=str2bool, default=d.snap_to_bucket)
    p.add_argument("--remat", type=str2bool, default=d.remat,
                   help="Rematerialize activations in the backward "
                        "(jax.checkpoint; exact, saves HBM).")
    p.add_argument("--fused_dbs", type=str2bool, default=d.fused_dbs,
                   help="DBS on the fused capacity-padded SPMD scan (one "
                        "compiled step for every plan; probe-measured times).")
    p.add_argument("--grad_comm", type=str, default=d.grad_comm,
                   choices=["flat", "hier"],
                   help="Gradient combine structure: flat single psum, or "
                        "the hierarchical ICI/DCN collective (in-host "
                        "reduce-scatter, compressed cross-host hop with "
                        "error-feedback residuals, in-host all-gather).")
    p.add_argument("--grad_comm_wire", type=str, default=d.grad_comm_wire,
                   choices=["fp32", "int8", "int4"],
                   help="Wire format of the hierarchical cross-host hop: "
                        "fp32 exact, int8 stochastic-rounded (unbiased, "
                        "int16 wire sum), int4 nearest-rounded (biased, "
                        "error feedback corrects; int8 wire sum).")
    p.add_argument("--dcn_bandwidth_probe", type=str2bool,
                   default=d.dcn_bandwidth_probe,
                   help="Probe both link classes at init and fall back to "
                        "the flat combine when the hierarchical structure "
                        "does not beat one flat psum on this fabric.")
    p.add_argument("--hier_hosts", type=int, default=d.hier_hosts,
                   help="Synthetic host-axis size for single-process meshes "
                        "(CPU tiers/tests); 0 = real process topology.")
    p.add_argument("--hier_levels", type=str, default=d.hier_levels,
                   help="N-level topology declaration for the tree combine: "
                        "comma list of name:size outer levels, outermost "
                        "first (e.g. 'pod:2,host:2'); prefix 'learned' to "
                        "merge probe-indistinguishable levels; '' = the "
                        "two-level host/device split.")
    p.add_argument("--grad_comm_wires", type=str, default=d.grad_comm_wires,
                   help="Per-hop wire codecs, outermost first (e.g. "
                        "'int4,int8,fp32'; innermost must be fp32); 'auto' "
                        "= choose per hop from measured link rates; '' = "
                        "grad_comm_wire on the outermost hop only.")
    p.add_argument("--dcn_probe_gate", type=float, default=d.dcn_probe_gate,
                   help="Bandwidth-probe verdict ratio: hier wins when its "
                        "wall < gate * flat wall.")
    p.add_argument("--compress_grads", type=str, default=d.compress_grads,
                   choices=["", "int8"],
                   help="Quantized gradient collective (stochastic rounding, "
                        "int16 wire sum): half the collective bytes.")
    p.add_argument("--grad_accum", type=int, default=d.grad_accum,
                   help="Fused-path micro-batching factor (activation memory "
                        "/ N, grads summed before the collective; exact).")
    p.add_argument("--shard_update", type=str2bool, default=d.shard_update,
                   help="ZeRO-1-style sharded optimizer update, generic over "
                        "optax transforms (reduce_scatter grads / tx.update "
                        "on the 1/n chunk / all_gather delta); composes "
                        "with elastic, hier and the quantized wires.")
    p.add_argument("--stream_chunk_steps", type=int, default=d.stream_chunk_steps,
                   help="Stream the host data path in windows of N steps "
                        "(prefetch overlaps compute); 0 = materialize whole epochs.")
    p.add_argument("--time_smoothing", type=float, default=d.time_smoothing)
    p.add_argument("--probe_overhead_correction", type=str2bool,
                   default=d.probe_overhead_correction,
                   help="Subtract measured per-device dispatch overhead from "
                        "standalone probe walls (negligible on local "
                        "backends).")
    p.add_argument("--probe_mode", type=str, default=d.probe_mode,
                   choices=["adaptive", "always"],
                   help="adaptive: skip per-worker probe steps once the "
                        "cost model is anchored (re-probe on schedule/episode "
                        "change/wall deviation); always: probe every epoch.")
    p.add_argument("--probe_every", type=int, default=d.probe_every)
    p.add_argument("--probe_wall_tol", type=float, default=d.probe_wall_tol)
    p.add_argument("--fault_mode", type=str, default=d.fault_mode, choices=["virtual", "compute"])
    p.add_argument("--straggler", type=str, default=d.straggler,
                   help="Deterministic per-worker slowdown factors, e.g. '3,1,1,1' "
                        "(the reference's contended -gpu 0,0,0,1 profile); "
                        "fault_mode picks virtual vs real injected compute.")
    p.add_argument("--precision", type=str, default=d.precision, choices=["float32", "bfloat16"])
    p.add_argument("--data_dir", type=str, default=d.data_dir)
    p.add_argument("--lm_data_dir", type=str, default=d.lm_data_dir)
    p.add_argument("--log_dir", type=str, default=d.log_dir)
    p.add_argument("--stat_dir", type=str, default=d.stat_dir)
    p.add_argument("--ckpt_dir", type=str, default=d.ckpt_dir)
    p.add_argument("--bptt", type=int, default=d.bptt)
    p.add_argument("--lm_arch", type=str, default=d.lm_arch,
                   help="Language model built under -m transformer: one of "
                        f"{LM_ARCHS} (a published architecture is kept as "
                        "models/<name>.json), or the path of such a .json file.")
    p.add_argument("--lm_layers", type=str, default=d.lm_layers,
                   help="Published layers kept, e.g. 1,4,5,6,7 (empty: all).")
    p.add_argument("--lm_experts_held", type=str, default=d.lm_experts_held,
                   help="Routed experts held here, first:end (empty: all).")
    p.add_argument("--lm_dropout", type=float, default=d.lm_dropout,
                   help="Dropout of the paper's LM (0.2 in the reference).")
    p.add_argument("--seq_parallel", type=str, default=d.seq_parallel,
                   choices=["", "ring", "ulysses"],
                   help="Long-context LM mode: shard the sequence axis over "
                        "the mesh (ring ppermute pipeline or Ulysses head "
                        "all-to-all attention).")
    p.add_argument("--grad_clip", type=float, default=d.grad_clip)
    p.add_argument("--profile_dir", type=str, default=d.profile_dir)
    p.add_argument("--use_pallas", type=str2bool, default=d.use_pallas)
    p.add_argument("--warm_start", type=str2bool, default=d.warm_start)
    p.add_argument("--aot_warm", type=str2bool, default=d.aot_warm,
                   help="Warm + dispatch through the async AOT compile "
                        "service (lower(abstract).compile() on a thread "
                        "pool; zero execute-to-compile). off = legacy "
                        "execute-to-compile warm loop.")
    p.add_argument("--aot_pool", type=int, default=d.aot_pool,
                   help="AOT compile pool width (0 = auto).")
    p.add_argument("--aot_backend", type=str, default=d.aot_backend,
                   choices=["thread", "process"],
                   help="Where AOT backend compiles run: in-process threads, "
                        "or subprocess workers feeding the persistent cache "
                        "(replayed in-process as guaranteed cache hits; "
                        "scales multi-program compile throughput on "
                        "many-core hosts).")
    p.add_argument("--aot_workers", type=int, default=d.aot_workers,
                   help="Process-backend compile worker count (0 = auto).")
    p.add_argument("--release_on_close", type=str2bool, default=d.release_on_close,
                   help="Closing the AOT service also clears JAX's in-memory "
                        "caches (frees the device for what the process does next).")
    p.add_argument("--aot_speculate", type=str2bool, default=d.aot_speculate,
                   help="Background-compile adjacent ladder rungs during "
                        "epochs so mid-run rebalances never block on XLA.")
    p.add_argument("--speculate_scan", type=str2bool, default=d.speculate_scan,
                   help="Scan mode: predict the solver's next share vector "
                        "and background-compile the predicted superstep "
                        "shape-tuple keys in the untimed epoch tail.")
    p.add_argument("--device_cache", type=str, default=d.device_cache,
                   choices=["auto", "on", "off"],
                   help="Keep train arrays HBM-resident and feed epochs by "
                        "index (on-device gather): per-epoch reshard costs an "
                        "index upload instead of re-transferring the dataset.")
    p.add_argument("--device_cache_mb", type=int, default=d.device_cache_mb)
    p.add_argument("--superstep", type=str, default=d.superstep,
                   choices=["auto", "on", "off"],
                   help="Elastic-path supersteps: windowed executables (one "
                        "compiled scan per window on single-device groups) "
                        "plus the per-device double-buffered transfer "
                        "pipeline; off = legacy per-step dispatch.")
    p.add_argument("--superstep_window", type=int, default=d.superstep_window,
                   help="Max steps per compiled superstep window (scan mode "
                        "unrolls fully for bitwise parity; compile time "
                        "scales with this).")
    p.add_argument("--trace", type=str, default=d.trace,
                   choices=["on", "off", "ring"],
                   help="graftscope span tracing: on = full buffer, ring = "
                        "last trace_ring events; Chrome-trace JSON saved "
                        "under trace_dir (summarize with `graftscope`).")
    p.add_argument("--trace_ring", type=int, default=d.trace_ring)
    p.add_argument("--trace_dir", type=str, default=d.trace_dir)
    p.add_argument("--trace_annotations", type=str2bool,
                   default=d.trace_annotations,
                   help="Bridge spans into jax.profiler.TraceAnnotation so "
                        "host phases line up with device timelines in a "
                        "--profile_dir trace.")
    p.add_argument("--trace_spool", type=str, default=d.trace_spool,
                   help="Flight recorder: directory for a crash-durable "
                        "per-process trace spool (background flusher; a "
                        "SIGKILL loses at most the last flush interval). "
                        "Merge post-mortem with `graftscope postmortem`.")
    p.add_argument("--trace_spool_flush_s", type=float,
                   default=d.trace_spool_flush_s,
                   help="Spool flush cadence in seconds (also flushes at "
                        "the event watermark).")
    p.add_argument("--trace_spool_fsync", type=str2bool,
                   default=d.trace_spool_fsync,
                   help="fsync each spool flush (power-loss durability at "
                        "the cost of flush latency).")
    p.add_argument("--elastic", type=str, default=d.elastic,
                   choices=["on", "off"],
                   help="Elastic world size: survive confirmed worker loss "
                        "by re-solving the partition over survivors "
                        "(re-shard + AOT re-warm + continue from the "
                        "epoch-start snapshot); readmit recovered workers "
                        "at epoch boundaries.")
    p.add_argument("--elastic_detect_misses", type=int,
                   default=d.elastic_detect_misses,
                   help="Consecutive missed liveness checks that confirm a "
                        "worker loss.")
    p.add_argument("--elastic_latency_factor", type=float,
                   default=d.elastic_latency_factor,
                   help="Probe latency over this multiple of the fleet "
                        "median marks a worker SUSPECT.")
    p.add_argument("--elastic_readmit", type=str, default=d.elastic_readmit,
                   choices=["epoch", "off"],
                   help="Readmission policy for recovered workers: at the "
                        "next epoch boundary (probe-seeded share), or never.")
    p.add_argument("--elastic_max_recoveries", type=int,
                   default=d.elastic_max_recoveries)
    p.add_argument("--rebalance", type=str, default=d.rebalance,
                   choices=["epoch", "window"],
                   help="DBS control-loop cadence: epoch = one re-solve per "
                        "epoch (reference semantics); window = the online "
                        "hysteresis controller re-solves every "
                        "rebalance_every windows and switches plans "
                        "MID-epoch when the predicted remaining-epoch win "
                        "beats the measured switch cost.")
    p.add_argument("--rebalance_every", type=int, default=d.rebalance_every,
                   help="Window cadence: evaluate the online controller "
                        "every K dispatch windows.")
    p.add_argument("--rebalance_hysteresis", type=float,
                   default=d.rebalance_hysteresis,
                   help="Relative switch threshold: predicted win as a "
                        "fraction of predicted remaining-epoch time.")
    p.add_argument("--rebalance_margin", type=float,
                   default=d.rebalance_margin,
                   help="Absolute switch threshold: win must exceed margin "
                        "x the measured (EMA) switch cost.")
    p.add_argument("--rebalance_budget_frac", type=float,
                   default=d.rebalance_budget_frac,
                   help="Regret budget: cumulative switch spend capped at "
                        "this fraction of cumulative banked wins.")
    p.add_argument("--rebalance_rate_alpha", type=float,
                   default=d.rebalance_rate_alpha,
                   help="EMA weight on the newest per-worker rate sample.")
    p.add_argument("--fault_schedule", type=str, default=d.fault_schedule,
                   choices=["none", "sin", "ramp", "spike", "diurnal",
                            "brownout", "killstorm"],
                   help="Time-varying straggler schedule over the "
                        "--straggler factors (sin: smooth appear/disappear "
                        "per period; ramp: rise once and hold; spike: full "
                        "factor for the duty fraction of each period; "
                        "diurnal: day/night load plateau; brownout: seeded "
                        "contiguous multi-worker slowdowns per period; "
                        "killstorm: seeded random victim stalls per period).")
    p.add_argument("--fault_period", type=float, default=d.fault_period,
                   help="Schedule period in epochs.")
    p.add_argument("--packed", type=str, default=d.packed,
                   choices=["auto", "on", "off"],
                   help="Single-device packed epochs: concat all workers' "
                        "true-width batches into one compiled whole-epoch "
                        "scan when every worker shares one chip.")
    p.add_argument("--coordinator", type=str,
                   default=os.environ.get("DBS_COORDINATOR", d.coordinator),
                   help="Multi-host: coordinator host:port for "
                        "jax.distributed.initialize (the reference's "
                        "MASTER_ADDR/PORT rendezvous, dbs.py:513-515). "
                        "Empty = single-host.")
    p.add_argument("--num_processes", type=int,
                   default=_env_int("DBS_NUM_PROCESSES", d.num_processes),
                   help="Multi-host: total number of processes (0 = let JAX "
                        "autodetect, TPU pods).")
    p.add_argument("--process_id", type=int,
                   default=_env_int("DBS_PROCESS_ID", d.process_id),
                   help="Multi-host: this process's id (-1 = let JAX "
                        "autodetect, TPU pods).")
    return p


def config_from_args(argv: Optional[Sequence[str]] = None) -> Config:
    ns = get_parser().parse_args(argv)
    return Config(**vars(ns))
