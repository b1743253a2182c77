"""Command-line entry point.

The analogue of ``python dbs.py <flags>`` (dbs.py:527-544): parse the 13
reference flags (+ TPU extras), skip runs whose completion sentinel already
exists (idempotence probe, hardened from the reference's log-file check,
dbs.py:528-534), then run the training engine. No process
forking — the SPMD controller drives all logical workers from one process per
host (SURVEY §7.1).
"""

from __future__ import annotations

import sys
from typing import Optional, Sequence

from dynamic_load_balance_distributeddnn_tpu.compile_cache import (
    enable_compile_cache,
)
from dynamic_load_balance_distributeddnn_tpu.config import config_from_args
from dynamic_load_balance_distributeddnn_tpu.obs.logging import (
    mark_run_done,
    run_already_done,
)


def _maybe_init_distributed(cfg) -> None:
    """Multi-host rendezvous from the shipped entry point — the analogue of
    the reference's MASTER_ADDR/MASTER_PORT + init_process_group('gloo')
    (dbs.py:513-515). One process per HOST (SPMD across its chips), not one
    per worker: the rendezvous makes every host see the global device mesh,
    and the engines' collectives ride it. On TPU pods the coordinator can be
    given alone (process count/id autodetected); on the CPU tier (tests) all
    three are explicit and gloo backs the collectives."""
    if not cfg.coordinator:
        return
    from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
        initialize_multihost,
    )

    initialize_multihost(
        cfg.coordinator,
        num_processes=cfg.num_processes if cfg.num_processes > 0 else None,
        process_id=cfg.process_id if cfg.process_id >= 0 else None,
    )


def _run_already_done_global(cfg) -> bool:
    """The idempotence probe, made collective: per-process filesystems can
    disagree (non-shared log_dirs, a config completed on one host only), and
    a rank that skips while its peers train leaves the peers hung in their
    first collective. Process 0 decides; everyone follows."""
    skip = run_already_done(cfg)
    if cfg.coordinator:
        import jax
        import numpy as np
        from jax.experimental import multihost_utils

        if jax.process_count() > 1:
            skip = bool(
                multihost_utils.broadcast_one_to_all(np.asarray(skip))
            )
    return skip


def trainer_class(cfg):
    """The trainer a parsed ``cfg`` is run by: the language-model trainers
    under ``-m transformer`` (whatever ``--lm_arch`` names), ``Trainer`` for
    every other model."""
    if cfg.model == "transformer" and cfg.seq_parallel:
        from dynamic_load_balance_distributeddnn_tpu.train.sp_engine import (
            SeqParallelLMTrainer,
        )

        return SeqParallelLMTrainer
    if cfg.model == "transformer":
        from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

        return LMTrainer
    from dynamic_load_balance_distributeddnn_tpu.train.engine import Trainer

    return Trainer


def run(argv: Optional[Sequence[str]] = None):
    """Parse ``argv``, train, mark the run done. Returns the trainer — or
    None when the idempotence probe found the run already finished and
    nothing was trained (callers that must know, e.g. ``chip_smoke.py``,
    check for it; the command line just exits 0 like the reference)."""
    cfg = config_from_args(argv)
    enable_compile_cache()
    _maybe_init_distributed(cfg)
    if _run_already_done_global(cfg):
        print("\n===========================")
        print("Had finished this experiment, skipping...")
        print("===========================\n")
        return None

    trainer = trainer_class(cfg)(cfg)
    trainer.run()
    mark_run_done(cfg)
    return trainer


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
