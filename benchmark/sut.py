"""The one place where the benchmark touches the system under test.

It builds the trainer exactly as ``cli.run`` does (``config_from_args`` ->
``enable_compile_cache`` -> the trainer class of the parsed ``cfg``), feeds
it the benchmark's own rows and weights, calls ``run_epoch`` — the entry the
measured window drives — and reads back what the program exposes: its state,
its recorder's per-epoch series, its graftscope spans and its AOT service's
failure count. Nothing here measures or decides anything, and nothing here
knows what a row is: the task (``benchmark/tasks/``) makes the bundle.
"""

from __future__ import annotations

import gc
import os
from typing import Callable, List


def trainer_class(cfg):
    """The class ``cli.run`` builds for this ``cfg``: its three-way choice,
    copied (a benchmark PR edits no program file; ``tests/benchmark`` holds
    the two together for every name in ``config.MODELS``). For a later PR to
    replace by one function in ``cli.py`` that both call."""
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


class Job:
    def __init__(self, argv: List[str], make_bundle: Callable, out_dir: str, job_seed: int,
                 trace: bool):
        """``make_bundle(cfg)`` gives what the trainer is handed as its data."""
        from dynamic_load_balance_distributeddnn_tpu.compile_cache import (
            enable_compile_cache,
        )
        from dynamic_load_balance_distributeddnn_tpu.config import config_from_args

        argv = list(argv) + [
            "--seed", str(job_seed),
            "--log_dir", os.path.join(out_dir, "logs"),
            "--stat_dir", os.path.join(out_dir, "statis"),
        ]
        if trace:
            argv += ["--trace", "on", "--trace_annotations", "true",
                     "--trace_dir", os.path.join(out_dir, "traces")]
        self.cfg = config_from_args(argv)
        self.cache_dir = enable_compile_cache()
        self.trainer = trainer_class(self.cfg)(self.cfg, bundle=make_bundle(self.cfg))

    # ------------------------------------------------------------- state

    def param_shapes(self):
        """``(shapes, shardings)`` of the parameter tree."""
        import jax

        params = self.trainer.state.params
        return (
            jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
            jax.tree_util.tree_map(lambda a: a.sharding, params),
        )

    def set_weights(self, params) -> None:
        self.trainer.state = self.trainer.state.replace(params=params)

    def snapshot(self) -> dict:
        """Parameters and momentum as host arrays."""
        import jax

        state = self.trainer.state
        flat, _ = jax.tree_util.tree_flatten_with_path(state.opt_state)
        trace = [leaf for path, leaf in flat if "trace" in jax.tree_util.keystr(path)]
        treedef = jax.tree_util.tree_structure(state.params)
        if len(trace) != treedef.num_leaves:
            raise RuntimeError("the optimizer state holds no momentum tree of the parameters' shape")
        return {
            "params": jax.device_get(state.params),
            "trace": jax.device_get(jax.tree_util.tree_unflatten(treedef, trace)),
        }

    def block(self) -> None:
        import jax

        jax.block_until_ready(self.trainer.state)

    # ------------------------------------------------------------- epochs

    def run_epoch(self, epoch: int) -> dict:
        return self.trainer.run_epoch(epoch)

    def epoch_record(self, i: int = -1) -> dict:
        """What the program recorded for one epoch it ran: its steps, the
        shares of its plan and the path it took."""
        data, meta = self.trainer.recorder.data, self.trainer.recorder.meta
        paths = meta.get("exec_path") or []
        return {
            "steps": int(data["steps"][i]),
            "shares": [float(s) for s in data["partition"][i]],
            "exec_path": paths[i] if paths else None,
        }

    def aot_failed(self) -> int:
        aot = getattr(self.trainer, "_aot", None)
        return int(aot.stats().get("failed", 0)) if aot is not None else 0

    def program_memory(self) -> list:
        """``[key, temp, arguments, outputs, aliased]`` bytes, as the compiler
        assigned them, of every program the AOT service holds."""
        aot = getattr(self.trainer, "_aot", None)
        rows = []
        for key in (aot.keys() if aot is not None else []):
            exe = aot.get(key)
            ma = exe.memory_analysis() if exe is not None else None
            if ma is not None:
                rows.append([str(key[0]), int(ma.temp_size_in_bytes),
                             int(ma.argument_size_in_bytes), int(ma.output_size_in_bytes),
                             int(ma.alias_size_in_bytes)])
        return rows

    def input_path(self) -> str:
        return "device cache" if getattr(self.trainer, "_use_device_cache", False) else "host windows"

    def spans(self) -> list:
        """graftscope events as ``(name, category, start_s, duration_s)`` on
        ``time.perf_counter``'s clock; empty when tracing is off."""
        tr = self.trainer._trace
        if not getattr(tr, "enabled", False):
            return []
        base = tr._epoch_base
        return [
            (name, cat, base + ts * 1e-6, dur * 1e-6)
            for name, cat, ph, ts, dur, _tid, _args in tr.events()
            if ph == "X"
        ]

    def close(self) -> None:
        """Stop the program's threads and drop its state from the device."""
        aot = getattr(self.trainer, "_aot", None)
        if aot is not None:
            aot.close(False)
        self.trainer.close_spool()
        self.trainer = None
        gc.collect()
