#!/usr/bin/env python3
"""One cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process (it holds the chip). Builds the trainer as ``cli.run`` does,
gives it rows and weights made from the seed, follows its first epoch for
``correct``, warms up, then measures whole epochs for ``--seconds`` on its
own clock, from outside the trainer. The last line of standard output is
the result; everything else (set-up breakdown, cache, plan and path per
epoch) is on earlier lines. Without a TPU it exits non-zero and prints no
result; ``--rehearsal`` (CPU tests only) runs the configuration's
``rehearsal_argv`` instead and says which platform it ran on.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

CACHE_CAP_ENV = "JAX_COMPILATION_CACHE_MAX_SIZE"


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def _maxrss_gib() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20  # Linux: KiB


def _trim_host_memory() -> None:
    """Hand freed heap back to the system before the window: a cold DenseNet
    set-up peaks at 39 GiB of a chip host's 40 (compiles), and the profiler
    needs 6 more when it stops (my chip runs, PR 23)."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU tests only: the configuration's rehearsal_argv, any platform")
    ap.add_argument("--manifest", default=None,
                    help="tests only: a manifest other than BENCHMARK.json (its directory's "
                         "traffic/, limits/ and layer_metrics/ are looked in first)")
    return ap.parse_args(argv)


def _timed_epoch(job, epoch: int, steps_per_epoch: int, sizes: dict, task):
    """One ``run_epoch`` on this clock, with what the program recorded for it.
    An epoch that raised, lost an AOT job or returned a loss that is not
    finite trained no samples and its steps are failed."""
    t0 = time.perf_counter()
    try:
        out = job.run_epoch(epoch)
        rec = job.epoch_record()
        ok = math.isfinite(float(out["loss"])) and job.aot_failed() == 0
    except Exception:  # the run is lost; say so in the counts
        traceback.print_exc()
        return {"index": epoch, "t0": t0, "t1": time.perf_counter(), "steps": steps_per_epoch,
                "failed_steps": steps_per_epoch, "samples": 0, "raised": True}
    return {
        "index": epoch, "t0": t0, "t1": time.perf_counter(), "steps": rec["steps"],
        "failed_steps": 0 if ok else rec["steps"],
        "samples": task.epoch_samples(rec["shares"], sizes) if ok else 0,
        "batches": task.plan_batches(rec["shares"], sizes), "shares": rec["shares"],
        "loss": float(out["loss"]), "exec_path": rec["exec_path"],
    }


def measure_window(job, first_epoch: int, seconds: float, steps_per_epoch: int,
                   sizes: dict, task, counters):
    """Whole epochs from ``first_epoch`` until ``seconds`` have passed, on
    this clock, ending with the state ready: the same window in a traced run
    as in a plain one."""
    epochs = []
    c0 = counters.snapshot()["compiles"]
    t_start = time.perf_counter()
    while True:
        epochs.append(_timed_epoch(job, first_epoch + len(epochs), steps_per_epoch, sizes, task))
        if epochs[-1].get("raised") or epochs[-1]["t1"] - t_start >= seconds:
            break
    job.block()
    t_end = time.perf_counter()
    samples = sum(e["samples"] for e in epochs)
    return {
        "t0": t_start, "t1": t_end, "wall_s": t_end - t_start, "samples": samples,
        "steps": sum(e["steps"] for e in epochs),
        "failed_steps": sum(e["failed_steps"] for e in epochs),
        "samples_per_s": samples / (t_end - t_start),
        "compiles": counters.snapshot()["compiles"] - c0,
    }, epochs


def profiled_epoch(job, epoch: int, steps_per_epoch: int, sizes: dict, task, profile_dir: str):
    """One more epoch, after the window has closed, under ``jax.profiler``:
    the device's side of a traced run. It follows the window so that starting
    and stopping the profiler (seconds, and gigabytes of host memory) cost
    the window nothing and the window holds the epochs a plain run's holds
    (one epoch, not two: stopping a trace of two DenseNet epochs met the
    host's 40 GiB, my chip run, PR 23)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(profile_dir, profiler_options=opts)
    try:
        e = _timed_epoch(job, epoch, steps_per_epoch, sizes, task)
        job.block()
    finally:
        jax.profiler.stop_trace()
    return e


def main(argv=None) -> int:
    args = parse(argv)
    capped = os.environ.pop(CACHE_CAP_ENV, None)  # see PERF.md: a capped cache misses everything

    from benchmark import harness, tasks

    spec = harness.load_cell(args.workload, manifest_path=args.manifest)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    task = tasks.load(config)

    import jax

    from benchmark import trace_reduce
    from benchmark.reference import common as reference

    devices = jax.devices()
    dev0 = devices[0]
    if not args.rehearsal and dev0.platform != "tpu":
        sys.stderr.write(f"benchmark: no TPU (platform {dev0.platform!r}); no result\n")
        return 2
    if len(devices) < cell["chips"]:
        sys.stderr.write(
            f"benchmark: {cell['name']} needs {cell['chips']} chips, found {len(devices)}\n")
        return 2
    counters = harness.Counters()
    t_imports = time.perf_counter()

    argv_job = harness.job_argv(config, traffic, args.rehearsal)
    sizes = task.job_sizes(argv_job)
    model = config["rehearsal_model" if args.rehearsal else "model"]
    n_test = config["rehearsal_n_test" if args.rehearsal else "n_test"]
    job_seed = args.seed % harness.JOB_SEED_MOD
    out_dir = os.path.join(_HERE, "out", f"{args.workload}.s{args.seed}.t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    rows = task.make_rows(args.seed, sizes, n_test, model)
    t_rows = time.perf_counter()

    from benchmark.sut import Job

    job = Job(argv_job, lambda cfg: task.bundle(rows, config, cfg), out_dir, job_seed,
              trace=bool(args.trace))
    shapes, shardings = job.param_shapes()
    weights = harness.make_weights(shapes, shardings, args.seed, reference.init_std(model))
    params0 = jax.device_get(weights)
    job.set_weights(weights)
    del weights
    t_built = time.perf_counter()

    # the first epoch from the seed, through the window's own call, on the
    # object the window is handed: what `correct` compares
    first = job.run_epoch(0)
    got = {"loss": float(first["loss"]), **job.snapshot()}
    t_first = time.perf_counter()
    plans = [task.plan_batches(job.epoch_record()["shares"], sizes)]
    steps_per_epoch = job.epoch_record()["steps"]
    warm = traffic.get("rehearsal_warmup" if args.rehearsal else "", traffic["warmup"])
    epoch = 1
    while epoch < warm["max_epochs"]:
        c0 = counters.snapshot()["compiles"]
        job.run_epoch(epoch)
        plans.append(task.plan_batches(job.epoch_record()["shares"], sizes))
        quiet = counters.snapshot()["compiles"] == c0
        epoch += 1
        if epoch >= warm["min_epochs"] and plans[-1] == plans[-2] and quiet:
            break
    job.block()
    _trim_host_memory()
    setup_counts = counters.snapshot()
    t_window = time.perf_counter()
    say(setup={
        "imports_s": t_imports - _T_PROCESS, "rows_s": t_rows - t_imports,
        "build_s": t_built - t_rows, "first_epoch_s": t_first - t_built,
        "warm_epochs_s": t_window - t_first, "warm_epochs": epoch, "plans": plans,
        "cache_dir": job.cache_dir, "cache_cap_dropped": capped, **setup_counts,
        "input_path": job.input_path(), "host_maxrss_gib": _maxrss_gib(),
    })

    window, epochs = measure_window(job, epoch, args.seconds, steps_per_epoch, sizes, task,
                                    counters)
    setup_s = window["t0"] - _T_PROCESS
    profile_dir = os.path.join(out_dir, "profile")
    traced = None
    if args.trace and not epochs[-1].get("raised"):
        traced = profiled_epoch(job, epoch + len(epochs), steps_per_epoch, sizes, task,
                                profile_dir)
    peak_bytes = max(harness.peak_bytes(d.memory_stats()) for d in devices[: cell["chips"]])
    for e in epochs + ([traced] if traced else []):
        say(epoch={k: e[k] for k in e if k not in ("t0", "t1")}, seconds=e["t1"] - e["t0"],
            profiled=e is traced)
    spans = job.spans()
    say(program_memory=job.program_memory(),
        memory_stats={str(d.id): d.memory_stats() for d in devices[: cell["chips"]]})
    if spans:
        per_phase = {}
        for name, _cat, start, dur in spans:
            if name in harness.PHASES and window["t0"] <= start <= window["t1"]:
                per_phase[name] = per_phase.get(name, 0.0) + dur
        say(phase_seconds_per_epoch={k: v / len(epochs) for k, v in per_phase.items()})
    job.close()

    device = {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    metrics, breakdown = {}, None
    units = {m["name"]: m["unit"] for k in ("end_to_end", "per_layer")
             for m in spec["manifest"][k]}
    if args.trace:
        profile = None
        xplane = trace_reduce.find_xplane(profile_dir)
        if xplane:
            ops, host, layout = trace_reduce.load_xplane(xplane)
            if args.rehearsal and not ops:
                ops = trace_reduce.host_ops_as_device(xplane)
            profile = trace_reduce.reduce_profile(ops, host, harness.PHASES, unit=trace_reduce.NS)
            say(trace={"file_bytes": os.path.getsize(xplane), "layout": layout[:40]})
        if profile:
            device["busy_s"], device["window_s"] = profile["busy_s"], profile["window_s"]
            breakdown = {"device_ops": profile["device_ops"], "idle_gaps": profile["idle_gaps"]}
        ctx = {
            "cell": cell, "config": config, "traffic": traffic, "epochs": epochs,
            "profiled_epoch": traced, "window": window, "spans": spans, "setup": setup_counts,
            "profile": profile, "run_dir": out_dir,
            "peak_hbm_bytes": peak_bytes, "sizes": sizes, "model": model,
            "peak": None if args.rehearsal else harness.peak_for(dev0.device_kind),
        }
        for m in harness.cell_metrics(spec["manifest"], args.workload, "per_layer"):
            value = harness.read_layer_metric(m["name"], ctx, spec["roots"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        values = {"samples_per_s": window["samples_per_s"], "setup_s": setup_s,
                  "peak_hbm_gib": peak_bytes / 2**30}
        for m in harness.cell_metrics(spec["manifest"], args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": units[m["name"]]}
    shutil.rmtree(out_dir, ignore_errors=True)

    # the plain reference follows the same first epoch, once the window has
    # closed, the peak has been read and the program's state is gone
    t_ref = time.perf_counter()
    ref = task.train_epoch(params0, rows, model,
                           harness.job_definition(config, traffic, sizes, job_seed, task),
                           device=dev0)
    compared = reference.compare(got, ref, params0)
    compared.update(task.plan_errors(epochs, sizes))
    verdict = harness.decide(compared, spec["limits"])
    say(reference_s=time.perf_counter() - t_ref, readings=compared,
        host_maxrss_gib=_maxrss_gib(),
        loss_first_epoch={"program": got["loss"], "reference": ref["loss"]})

    counted = epochs + ([traced] if traced else [])
    failed = sum(e["failed_steps"] for e in counted)
    result = {"correct": bool(verdict["correct"] and failed == 0),
              "attempted": sum(e["steps"] for e in counted), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = verdict["compared"]
    for name, row in verdict["compared"].items():
        sys.stderr.write(f"compared {name}: {row['value']} (limit {row['limit']})\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
