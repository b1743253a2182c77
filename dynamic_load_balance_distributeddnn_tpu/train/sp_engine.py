"""Long-context LM training: the sequence axis sharded over the mesh.

The DBS trainers parallelize over DATA (workers own example/token shares;
the balancer moves the shares). This trainer parallelizes over the SEQUENCE:
one logical batch of ``--bptt``-token windows has its time axis split across
every device, attention runs ring- or Ulysses-parallel over ICI
(parallel/ring.py, parallel/ulysses.py), and loss/grads psum back to
replicated. This is the regime the reference cannot reach at all — its
sequence handling stops at bptt=35 truncation (SURVEY §5.7) because the full
[T, T] attention lives on one GPU; here T scales with the mesh.

Selected via ``--seq_parallel ring|ulysses`` on the transformer model; the
param layout matches the single-device/DBS LM, so checkpoints move freely
between trainers.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.corpus import (
    Corpus,
    batchify,
    bptt_windows,
)
from dynamic_load_balance_distributeddnn_tpu.models import build_model
from dynamic_load_balance_distributeddnn_tpu.obs import (
    MetricsRecorder,
    MetricsRegistry,
    init_logger,
)
from dynamic_load_balance_distributeddnn_tpu.obs.trace import EPOCH_CAT, get_tracer
from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import data_mesh, replicated_sharding
from dynamic_load_balance_distributeddnn_tpu.parallel.seq_parallel import (
    make_seq_parallel_apply,
    make_seq_parallel_value_and_grad,
    shard_tokens,
)
from dynamic_load_balance_distributeddnn_tpu.train.schedule import one_cycle_lr
from dynamic_load_balance_distributeddnn_tpu.train.state import create_state, make_optimizer

# reference LM dims (dbs.py:337-343) — kept so SP checkpoints interchange
# with the DBS LM trainer's
EMSIZE, NHEAD, NHID, NLAYERS = 200, 2, 200, 2


class SeqParallelLMTrainer:
    """Epoch loop for sequence-parallel LM training."""

    def __init__(self, cfg: Config, corpus: Optional[Corpus] = None,
                 log_to_file: bool = True):
        if cfg.model != "transformer":
            raise ValueError("seq_parallel training applies to the transformer LM")
        if cfg.seq_parallel not in ("ring", "ulysses"):
            raise ValueError("seq_parallel must be 'ring' or 'ulysses'")
        self.cfg = cfg
        self.logger = init_logger(cfg, rank=0, to_file=log_to_file)
        self.mesh = data_mesh()
        self.n_dev = len(self.mesh.devices.flat)
        if cfg.bptt % self.n_dev != 0:
            raise ValueError(
                f"bptt {cfg.bptt} must divide by the {self.n_dev}-device mesh"
            )
        if cfg.seq_parallel == "ulysses" and NHEAD % self.n_dev != 0:
            raise ValueError(
                f"ulysses needs num_heads ({NHEAD}) % n_devices ({self.n_dev}) == 0"
            )

        self.corpus = corpus if corpus is not None else Corpus(cfg.lm_data_dir)
        for note in getattr(self.corpus, "notes", []):
            self.logger.warning(f"corpus: {note}")
        stream = self.corpus.train
        if cfg.n_train:
            stream = stream[: cfg.n_train]
        elif cfg.debug and len(stream) > 60_000:
            stream = stream[:60_000]
        # [B, nbatch] token columns; steps consume [B, bptt] windows
        self.data = batchify(stream, max(cfg.batch_size, 1))
        self.val_data = batchify(self.corpus.valid, 10)  # eval bsz 10 (dataloader.py:109)

        dims = dict(
            ntoken=self.corpus.ntokens,
            ninp=EMSIZE, nhead=NHEAD, nhid=NHID, nlayers=NLAYERS,
            dropout=cfg.lm_dropout,
        )
        # init with the param-compatible single-device twin: the SP module's
        # collectives (axis_size/axis_index) only exist inside shard_map
        single = build_model("transformer", **dims).module
        self.module = build_model(
            "transformer", **dims, seq_axis="data", sp_mode=cfg.seq_parallel
        ).module
        self.tx = make_optimizer(cfg.learning_rate, cfg.momentum)
        self.state = create_state(
            single,
            jnp.zeros((1, cfg.bptt), jnp.int32),
            self.tx,
            seed=cfg.seed,
            sharding=replicated_sharding(self.mesh),
        )
        self._vg = make_seq_parallel_value_and_grad(
            self.mesh, self.module, train=True
        )
        self._eval_apply = make_seq_parallel_apply(self.mesh, self.module)
        clip = cfg.grad_clip if cfg.grad_clip > 0 else 0.25  # dbs.py:274

        @jax.jit
        def update(state, grads):
            if clip > 0:
                gnorm = optax.global_norm(grads)
                scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            return state.replace(
                params=params, opt_state=opt_state, step=state.step + 1
            )

        self._update = update
        self.recorder = MetricsRecorder()
        # graftscope: the engine owns the process-wide tracer config (same
        # contract as the DBS engines — unconditional, so an off run never
        # inherits an earlier traced run's enabled state) + the registry
        self._trace = get_tracer().configure(
            cfg.trace,
            ring_size=cfg.trace_ring,
            jax_annotations=cfg.trace_annotations,
        )
        self.obs = MetricsRegistry(recorder=self.recorder, tracer=self._trace)
        self.recorder.stamp_data_source(self.corpus)
        # SP walls never contained standalone probe steps (the SP engine has
        # no re-probe machinery); stamped so its artifacts carry the same
        # wall-definition schema as the vision/LM engines (ADVICE r4)
        self.recorder.meta["wall_excludes_probes"] = True
        if cfg.straggler:
            self.recorder.meta["straggler_factors"] = [
                float(f) for f in cfg.straggler_factors()
            ]
            self.recorder.meta["fault_mode"] = cfg.fault_mode
        self.total_wallclock = 0.0

    # ------------------------------------------------------------------ loop

    def _windows(self, data: np.ndarray):
        # no column padding: the SP batch is the full [bsz] column set; only
        # the tail window (short T) is masked out of the step loop
        return bptt_windows(data, self.cfg.bptt)

    def run_epoch(self, epoch: int) -> dict:
        tr = get_tracer()
        tr.set_epoch(epoch)
        try:
            with tr.span("epoch", cat=EPOCH_CAT):
                return self._run_epoch(epoch)
        finally:
            tr.set_epoch(None)

    def _run_epoch(self, epoch: int) -> dict:
        cfg = self.cfg
        tr = get_tracer()
        with tr.span("plan_solve"):
            if cfg.one_cycle_policy:
                lr = one_cycle_lr(cfg.learning_rate, epoch, cfg.epoch_size,
                                  disable=cfg.disable_enhancements)
                self.state = self.state.with_learning_rate(lr)
            xs, ys, ms = self._windows(self.data)
        with tr.span("train"):
            t0 = time.perf_counter()
            loss_sum, tok, n_done = 0.0, 0, 0
            for s in range(xs.shape[0]):
                # full-length windows only: the SP shard_map needs T % n_dev == 0
                if not ms[s].all():
                    continue
                x = shard_tokens(self.mesh, jnp.asarray(xs[s], jnp.int32))
                y = shard_tokens(self.mesh, jnp.asarray(ys[s], jnp.int32))
                loss, grads = self._vg(
                    self.state.params, x, y,
                    jax.random.fold_in(jax.random.PRNGKey(cfg.seed), epoch * 131071 + s),
                )
                self.state = self._update(self.state, grads)
                loss_sum += float(loss)
                tok += int(ms[s].sum())
                n_done += 1
            jax.block_until_ready(self.state.params)
            wall = time.perf_counter() - t0
        self.total_wallclock += wall
        train_loss = loss_sum / max(n_done, 1)
        with tr.span("validate"):
            val_loss, acc = self.validate()
        with tr.span("record"):
            tps = tok / wall if wall > 0 else 0.0
            self.logger.info(
                f"Epoch {epoch}: sp={cfg.seq_parallel} T={cfg.bptt} "
                f"train_loss {train_loss:.4f}, val_loss {val_loss:.4f}, "
                f"{tps:,.0f} tok/s, wall {wall:.3f}s"
            )
            self.recorder.record_epoch(
                epoch=epoch,
                train_loss=train_loss,
                train_time=wall,
                sync_time=0.0,
                val_loss=val_loss,
                accuracy=acc,
                partition=[1.0 / self.n_dev] * self.n_dev,
                node_time=[wall] * self.n_dev,
                wallclock_time=self.total_wallclock,
                tokens_per_s=tps,
            )
        return {"epoch_wall": wall, "loss": train_loss, "val_loss": val_loss}

    def validate(self) -> Tuple[float, float]:
        xs, ys, ms = self._windows(self.val_data)
        tot, cnt = 0.0, 0.0
        for s in range(xs.shape[0]):
            if not ms[s].all():
                continue
            logits = self._eval_apply(
                self.state.params, shard_tokens(self.mesh, jnp.asarray(xs[s], jnp.int32))
            )
            logits = np.asarray(logits, np.float32)
            logz = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + logits.max(-1)
            gold = np.take_along_axis(logits, ys[s][..., None], axis=-1)[..., 0]
            tot += float((logz - gold).sum())
            cnt += float(ys[s].size)
        val = tot / max(cnt, 1.0)
        return val, 1.0 - val  # "accuracy" = 1 - val_loss (dbs.py:180-181)

    def run(self, epochs: Optional[int] = None) -> MetricsRecorder:
        n = epochs if epochs is not None else self.cfg.epoch_size
        for e in range(n):
            self.run_epoch(e)
        self.logger.info(f"Total wallclock: {self.total_wallclock:.3f}s")
        self.recorder.save(self.cfg.stat_dir, self.cfg.base_filename())
        if self._trace.enabled:
            path = os.path.join(
                self.cfg.trace_dir,
                self.cfg.base_filename().format(0) + ".trace.json",
            )
            self._trace.save(path)
            self.logger.info(f"graftscope trace saved: {path}")
        return self.recorder
