#!/usr/bin/env python3
"""Readings that a cell's limits are set from, beside the program's own
(which every ``run.py`` run prints): the control and the planted faults, each
the plain reference put in the program's place at the cell's own size.

    python3 benchmark/control.py --workload <name> --seeds 11,12,13 \
        [--variants fp8,half_batch,state_unchanged] [--rehearsal] [--manifest <path>]

For each seed it follows the cell's first epoch with the reference, then with
each variant, and prints the numbers ``run.py`` compares, one JSON line per
seed and variant. ``fp8``: the same equations with every convolution's and
matrix product's inputs rounded to float8. ``half_batch``,
``state_unchanged`` and a task's further faults (``no_clip`` for tokens): see
the task's ``train_epoch`` (``tasks/<task>.py``). A variant has to come out
as not correct under the cell's limits. Not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def readings(workload: str, seed: int, variants, rehearsal: bool = False, manifest_path=None):
    """``{variant: compared numbers}`` for one seed, and the cell's verdict on each."""
    import jax

    from benchmark import harness, tasks
    from benchmark.reference import common as reference

    spec = harness.load_cell(workload, manifest_path=manifest_path)
    config, traffic = spec["config"], spec["traffic"]
    task = tasks.load(config)
    argv = harness.job_argv(config, traffic, rehearsal)
    sizes = task.job_sizes(argv)
    model = config["rehearsal_model" if rehearsal else "model"]
    rows = task.make_rows(seed, sizes, 1, model)
    shapes = reference.family(model).param_shapes(model)
    params0 = jax.device_get(harness.make_weights(shapes, None, seed, reference.init_std(model)))
    job = harness.job_definition(config, traffic, sizes, seed % harness.JOB_SEED_MOD, task)

    def follow(**kw):
        return task.train_epoch(params0, rows, model, job, **kw)

    ref = follow()
    out = {}
    for v in variants:
        got = follow(precision=v) if v in ("fp8", "bf16") else follow(fault=v)
        compared = reference.compare(got, ref, params0)
        limits = {k: x for k, x in spec["limits"].items() if k in compared}
        out[v] = {"readings": compared, **harness.decide(compared, limits)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,half_batch,state_unchanged")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        for variant, r in readings(args.workload, seed, args.variants.split(","),
                                   args.rehearsal, manifest_path=args.manifest).items():
            print(json.dumps({"workload": args.workload, "seed": seed, "variant": variant,
                              "correct": r["correct"], **r["readings"]}), flush=True)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
