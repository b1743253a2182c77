"""Transformer-LM trainer — the sequence workload path.

Shares the DBS controller (solver, timing, faults, recorder) with the vision
Trainer; differs in the data plane, mirroring the reference's transformer
branch (dbs.py:253-288, 397-419; dataloader.py:100-110):

- the token *stream* is split contiguously by worker share (no shuffle,
  dataloader.py:106) and each worker folds its slice into
  ``bsz_r = share_r * B`` columns (batchify),
- steps consume bptt=35-token windows with next-token targets (utils.py:7-10),
- per-worker gradients are clipped to 0.25 before combining (dbs.py:274),
- validation is bptt-windowed NLL with eval batch 10 (dataloader.py:109) and
  "accuracy" defined as ``1 - val_loss`` (dbs.py:180-181 — the reference's
  convention, kept for series parity).

Because worker slice length and column count are both proportional to the
share, every worker sweeps the same number of windows — the equal-step
invariant again, now in token space.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from dynamic_load_balance_distributeddnn_tpu.data.corpus import (
    Corpus,
    batchify,
    bptt_windows,
)
from dynamic_load_balance_distributeddnn_tpu.data.partitioner import (
    EpochPlan,
    WorkerPlan,
    partition_indices,
)
from dynamic_load_balance_distributeddnn_tpu.models import build_model
from dynamic_load_balance_distributeddnn_tpu.models.afmoe import published
from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import replicated_sharding
from dynamic_load_balance_distributeddnn_tpu.train.engine import Trainer
from dynamic_load_balance_distributeddnn_tpu.train.state import create_state, make_optimizer
from dynamic_load_balance_distributeddnn_tpu.train.steps import StepLibrary


class LMTrainer(Trainer):
    SNAP_BATCHES = False  # columns, not examples — keep the exact split

    # Reference LM hyperparameters (dbs.py:337-343). Its dropout is the flag
    # --lm_dropout (0.2 there); a DROPOUT set on the class overrides the flag
    # (tests/benchmark's fixture, which no model_config PR may edit, sets it)
    EMSIZE = 200
    NHEAD = 2
    NHID = 200
    NLAYERS = 2
    DROPOUT = None

    def _setup_data(self, bundle) -> None:
        cfg = self.cfg
        if bundle is not None:
            self.corpus = bundle  # tests may inject a Corpus directly
        else:
            self.corpus = Corpus(cfg.lm_data_dir)
        for note in getattr(self.corpus, "notes", []):
            self.logger.warning(f"corpus: {note}")
        stream = self.corpus.train
        if cfg.n_train:
            stream = stream[: cfg.n_train]
        elif cfg.debug and len(stream) > 60_000:
            stream = stream[:60_000]
        self.train_stream = stream
        self.n_train = len(stream)
        self.bundle = None

    def _setup_model(self) -> None:
        cfg = self.cfg
        from dynamic_load_balance_distributeddnn_tpu.ops.pallas import set_use_pallas

        set_use_pallas(cfg.use_pallas)
        if cfg.lm_arch != "paper":
            # a published architecture (models/<lm_arch>.json, or the file
            # --lm_arch names), cut as the command line says; its family is
            # the file's model_type; the vocabulary is the corpus's
            self.spec = build_model(
                published(cfg.lm_arch)["model_type"],
                arch=cfg.lm_arch,
                ntoken=self.corpus.ntokens,
                layers=cfg.lm_kept_layers(),
                experts_held=cfg.lm_expert_range(),
                remat=cfg.remat,
            )
        else:
            self.spec = build_model(
                "transformer",
                ntoken=self.corpus.ntokens,
                ninp=self.EMSIZE,
                nhead=self.NHEAD,
                nhid=self.NHID,
                nlayers=self.NLAYERS,
                dropout=cfg.lm_dropout if self.DROPOUT is None else self.DROPOUT,
            )
        self.tx = make_optimizer(cfg.learning_rate, cfg.momentum)
        example = jnp.zeros((1, cfg.bptt), jnp.int32)
        self.state = create_state(
            self.spec.module,
            example,
            self.tx,
            seed=cfg.seed,
            sharding=replicated_sharding(self.mesh),
        )
        self._zero1_padded = 0
        if cfg.shard_update:
            # ZeRO-1 sharded update on the LM path (ISSUE 13): identical
            # flat-chunk conversion and combine-twin dispatch as the vision
            # engine — the update shard stays uniform even though the LM's
            # column batches are not
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

            # hierarchical combine (ISSUE 12): the LM's elastic dispatch
            # rides the hier combine twins like the vision path — the
            # error-feedback residual travels in the TrainState
            self.state = attach_comm_residual(
                self.state, self.mesh,
                pad_multiple=self.n_dev if cfg.shard_update else 0,
            )
        grad_clip = cfg.grad_clip if cfg.grad_clip > 0 else 0.25  # dbs.py:274
        self.steps = StepLibrary(
            self.spec,
            self.mesh,
            self.tx,
            grad_clip=grad_clip,
            compute_dtype=jnp.bfloat16 if cfg.precision == "bfloat16" else None,
            use_pallas=cfg.use_pallas,
            shard_update=cfg.shard_update,
            grad_accum=cfg.grad_accum,
            compress_grads=cfg.compress_grads,
            remat=cfg.remat,
            grad_comm=self.grad_comm,
            grad_comm_wire=cfg.grad_comm_wire,
            grad_comm_wires=self._grad_comm_wires or None,
            zero1_padded=self._zero1_padded,
        )

    def _dummy_batch(self, b: int):
        """LM warm-up batch: ``b`` padded columns of bptt-token windows."""
        cfg = self.cfg
        return (
            np.zeros((b, cfg.bptt), dtype=np.int32),
            np.zeros((b, cfg.bptt), dtype=np.int32),
            np.zeros((b, cfg.bptt), dtype=np.float32),
        )

    # ------------------------------------------------------------- planning

    def _build_plan(self, epoch: int, batch_sizes: np.ndarray) -> EpochPlan:
        """LM plan: contiguous stream slices; a worker's "batch size" is its
        column count; steps = number of bptt windows of its folded slice."""
        cfg = self.cfg
        parts = partition_indices(self.n_train, self.shares, shuffle=False)
        workers = []
        num_steps = 0
        for rank, (token_range, cols) in enumerate(zip(parts, batch_sizes)):
            cols = int(max(cols, 1))
            nbatch = max(len(token_range) // cols, 2)
            steps = max(-(-(nbatch - 1) // cfg.bptt), 1)
            padded = -(-cols // cfg.bucket) * cfg.bucket
            workers.append(
                WorkerPlan(
                    rank=rank,
                    indices=token_range,
                    batch_size=cols,
                    padded_batch=padded,
                    steps=steps,
                )
            )
            num_steps = max(num_steps, steps)
        return EpochPlan(
            epoch=epoch,
            shares=self.shares.copy(),
            batch_sizes=np.asarray(batch_sizes, dtype=np.int64),
            workers=tuple(workers),
            num_steps=num_steps,
            global_batch=cfg.batch_size,
        )

    def _worker_inputs(
        self, plan: EpochPlan, rank: int, s0: int = 0, s1=None, *, pad_to=None,
        as_indices: bool = False
    ):
        # pad_to: the fused-DBS capacity layout — every worker presents
        # ``cap`` columns (padding masked to zero weight) so one compiled
        # scan serves every rebalanced plan, exactly as in the vision path.
        # as_indices: the vision device-cache mode — never active here (the
        # LM has no cacheable train arrays; _decide_device_cache returns
        # False), accepted for signature parity.
        assert not as_indices
        #
        # The epoch's windows are plan-deterministic, so they are built ONCE
        # per (epoch, rank, pad) and the chunked fused gather / probe calls
        # slice the cached arrays — token windows are small (the folded
        # stream), so whole-epoch residency is cheap, unlike images.
        if getattr(self, "_win_cache_epoch", None) != plan.epoch:
            self._win_cache_epoch = plan.epoch
            self._win_cache = {}
        key = (rank, pad_to)
        if key not in self._win_cache:
            # graftscope: the LM's host data plane — token-window folds are
            # built once per (epoch, rank, pad) and show as their own spans
            with self._trace.span(
                "lm_build_windows", cat="transfer", args={"rank": rank}
            ):
                self._win_cache[key] = self._build_windows(plan, rank, pad_to)
        x, y, weights = self._win_cache[key]
        if s1 is None:
            s1 = plan.num_steps
        return x[s0:s1], y[s0:s1], weights[s0:s1]

    def _build_windows(self, plan: EpochPlan, rank: int, pad_to):
        cfg = self.cfg
        w = plan.workers[rank]
        if len(w.indices):
            slice_tokens = self.train_stream[w.indices[0] : w.indices[-1] + 1]
        else:
            slice_tokens = np.zeros(0, dtype=np.int32)
        data = batchify(slice_tokens, w.batch_size)
        x, y, m = bptt_windows(
            data, cfg.bptt, pad_bsz=pad_to if pad_to is not None else w.padded_batch
        )
        # pad the step axis to the plan-wide count with fully masked windows
        if x.shape[0] < plan.num_steps:
            extra = plan.num_steps - x.shape[0]
            zpad = ((0, extra), (0, 0), (0, 0))
            x, y, m = (np.pad(a, zpad) for a in (x, y, m))
        # Per-token weights: worker weight p_r (or 1/ws under -de) spread over
        # the window's true token count — sum over all workers == 1.
        p_r = (
            1.0 / cfg.world_size
            if cfg.disable_enhancements
            else float(plan.shares[rank])
        )
        tok_counts = m.reshape(plan.num_steps, -1).sum(axis=1)
        weights = m * (
            p_r / np.maximum(tok_counts, 1.0)[:, None, None]
        ).astype(np.float32)
        return x, y, weights

    # ------------------------------------------------------------- validate

    def validate(self) -> Tuple[float, float]:
        """bptt-windowed NLL over the test stream, sharded over the mesh: the
        [windows, bsz, bptt] windows flatten to independent [rows, bptt]
        sequences (each row is one column's window — the model treats batch
        rows independently) and run through the same fused sharded eval as
        the vision path, in fixed-shape chunks."""
        cfg = self.cfg
        eval_bsz = 10  # dataloader.py:109
        stream = self.corpus.test
        if cfg.debug and len(stream) > 20_000:
            stream = stream[:20_000]
        data = batchify(stream, eval_bsz)
        x, y, m = bptt_windows(data, cfg.bptt)
        loss_sum, _, count = self._eval_sharded(
            x.reshape(-1, cfg.bptt),
            y.reshape(-1, cfg.bptt),
            mask=m.reshape(-1, cfg.bptt),
        )
        val_loss = loss_sum / max(count, 1.0)
        # "accuracy" = 1 - val_loss: the reference's LM convention
        # (dbs.py:180-181), not a real accuracy.
        return val_loss, 1.0 - val_loss
