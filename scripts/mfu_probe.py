#!/usr/bin/env python
"""Clean-leg MFU attribution: where does the DenseNet epoch time go?

A round-2 bench read clean_mfu_bf16_peak = 1.36% on a chip (pre-ledger record, deleted in PR 21)
without ever attributing the idle time.
This probe isolates each layer of the stack on the same clean leg
(DenseNet-121 / cifar10-shaped data / B=512 / bf16):

A. step-compute ceiling — the compiled fused step on device-resident
   data, per-call blocking, min over reps: pure device step time.
B. pipelined rate — N async dispatches, block once: what the scan can
   sustain; if B ~= A the device is saturated, dispatch is hidden.
C. epoch wall — Trainer.run_epoch on the same config: adds host feed,
   plan build, readback. C vs A*steps is the host-side overhead.
D. batch sweep — step time at several widths: fixed overhead vs MXU
   saturation knee (is the chip starved by small per-step work?).
E. matmul roofline — a big bf16 matmul timed the same way: what fraction
   of the chip's paper peak this chip actually delivers.
F. profiler trace over a few steps, parsed via tensorboard_plugin_profile
   (present in this image) -> device busy fraction + top self-time ops.

Writes statis/MFU_PROBE.json incrementally (each section lands as it
completes, so a failure mid-run still leaves the earlier sections).

Usage: python scripts/mfu_probe.py [--cpu] [--quick]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


OUT = os.path.join("statis", "MFU_PROBE.json")
RESULT: dict = {"sections": {}}


def _save() -> None:
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(RESULT, f, indent=1)
    os.replace(tmp, OUT)


def main() -> int:
    if "--parse-xplane" in sys.argv:
        path = sys.argv[sys.argv.index("--parse-xplane") + 1]
        print(json.dumps(_parse_xplane(path)))
        return 0
    force_cpu = "--cpu" in sys.argv
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
    quick = "--quick" in sys.argv
    import jax

    from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
        arm_stall_watchdog,
        heartbeat,
    )

    devs = jax.devices()
    if devs[0].platform != "tpu" and not force_cpu:
        sys.stderr.write(
            f"[mfu_probe] no TPU (platform {devs[0].platform!r}); pass --cpu "
            "to exercise the plumbing only\n"
        )
        return 2
    # Stall guard, TPU-only: CPU fused-scan compiles can out-wait any
    # reasonable stall cap without a heartbeat.
    if devs[0].platform != "cpu":
        arm_stall_watchdog(
            OUT + ".hb",
            float(os.environ.get("MFU_STALL_S", 1200)),
            extra_paths=(OUT,),
        )
    import jax.numpy as jnp
    import numpy as np

    dev = devs[0]
    RESULT["platform"] = dev.platform
    RESULT["device_kind"] = getattr(dev, "device_kind", "?")
    RESULT["n_devices"] = len(devs)
    _save()

    from dynamic_load_balance_distributeddnn_tpu.config import Config
    from dynamic_load_balance_distributeddnn_tpu.data import load_dataset
    from dynamic_load_balance_distributeddnn_tpu.obs.flops import (
        chip_peak_flops,
        compiled_flops,
    )
    from dynamic_load_balance_distributeddnn_tpu.train import Trainer

    peak = chip_peak_flops() or float("nan")
    peak_ok = peak == peak
    RESULT["bf16_peak_flops_per_dev"] = peak if peak_ok else None

    # ---- E first: matmul roofline (cheap, and meaningful even if the rest
    # of the probe dies) ----
    def timed_min(fn, *args, reps=5):
        jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        heartbeat()
        return best

    n = 4096 if not quick else 1024
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    t_mm = timed_min(mm, a, b)
    mm_flops = 2 * n**3
    RESULT["sections"]["matmul_roofline"] = {
        "n": n,
        "time_s": t_mm,
        "tflops": mm_flops / t_mm / 1e12,
        "frac_of_peak": (mm_flops / t_mm) / peak if peak_ok else None,
    }
    _save()

    # ---- Trainer on the clean leg ----
    n_train = int(os.environ.get("MFU_NTRAIN", 2048 if quick else 12800))
    model = os.environ.get("MFU_MODEL", "mnistnet" if force_cpu else "densenet")
    dataset = "mnist" if force_cpu else "cifar10"
    cfg = Config(
        debug=False,
        world_size=int(os.environ.get("MFU_WS", 4)),
        batch_size=512,
        learning_rate=0.01,
        epoch_size=2,
        dataset=dataset,
        model=model,
        dynamic_batch_size=False,
        fault_tolerance=False,
        bucket=32,
        precision="bfloat16",
    )
    bundle = load_dataset(dataset, n_train=n_train, n_test=512)
    tr = Trainer(cfg, bundle=bundle, log_to_file=False)
    RESULT["model"] = model
    RESULT["n_train"] = n_train

    # The clean leg on one chip runs the packed path: per-step global batch =
    # B + ws*bucket rows on a 1-device mesh. Build the same step shape here.
    n_dev = tr.n_dev
    h, w_, c = bundle.train_x.shape[1:]

    def step_inputs(b_total: int):
        x = jnp.asarray(np.random.RandomState(0).randint(0, 255, (b_total, h, w_, c)).astype(bundle.train_x.dtype))
        y = jnp.zeros((b_total,), jnp.int32)
        w = jnp.full((b_total,), 1.0 / b_total, jnp.float32)
        slow = jnp.zeros((n_dev,), jnp.int32)
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import batch_sharding

        x = jax.device_put(x, batch_sharding(tr.mesh, x.ndim))
        y = jax.device_put(y, batch_sharding(tr.mesh, 1))
        w = jax.device_put(w, batch_sharding(tr.mesh, 1))
        slow = jax.device_put(slow, batch_sharding(tr.mesh, 1))
        return x, y, w, slow, jnp.int32(7)

    # ---- A + B at the bench's step width ----
    b_bench = tr._cap_packed if n_dev == 1 else cfg.batch_size
    args = step_inputs(b_bench)
    state = tr.state
    probe = tr.steps.fused_step_probe
    t_block = timed_min(probe, state, *args, reps=5)
    f = compiled_flops(probe, state, *args) or float("nan")
    # pipelined: N dispatches, block once
    n_pipe = 20 if not quick else 5
    jax.block_until_ready(probe(state, *args))
    t0 = time.perf_counter()
    out = None
    for _ in range(n_pipe):
        out = probe(state, *args)
    jax.block_until_ready(out)
    t_pipe = (time.perf_counter() - t0) / n_pipe
    RESULT["sections"]["step"] = {
        "global_batch": b_bench,
        "blocking_step_s": t_block,
        "pipelined_step_s": t_pipe,
        "flops_per_step": f if f == f else None,
        "step_mfu_blocking": (f / t_block) / (peak * n_dev) if f == f and peak_ok else None,
        "step_mfu_pipelined": (f / t_pipe) / (peak * n_dev) if f == f and peak_ok else None,
        "examples_per_s_pipelined": b_bench / t_pipe,
    }
    _save()

    # ---- C: epoch wall through the Trainer (same path the bench times) ----
    walls = []
    for e in range(2):
        walls.append(tr.run_epoch(e)["epoch_wall"])
    steps_per_epoch = max(n_train // cfg.batch_size, 1)
    rec = tr.recorder.data
    RESULT["sections"]["epoch"] = {
        "walls_s": walls,
        "steps_per_epoch": steps_per_epoch,
        "device_time_est_s": t_pipe * steps_per_epoch,
        "host_overhead_s": min(walls) - t_pipe * steps_per_epoch,
        "examples_per_s": rec.get("examples_per_s", [None])[-1],
        "mfu_bf16_peak": rec.get("mfu_bf16_peak", [None])[-1],
    }
    _save()

    # ---- D: batch sweep ----
    # run_epoch donated the old state buffers (fused_epoch donate_argnums);
    # re-fetch the live state before reusing it
    state = tr.state
    args = step_inputs(b_bench)
    sweep = RESULT["sections"]["batch_sweep"] = {}
    for b_total in ([256, 512] if quick else [128, 256, 512, 1024, 2048]):
        if b_total % n_dev:
            continue
        try:
            argv = step_inputs(b_total)
            t = timed_min(probe, state, *argv, reps=3)
            fb = compiled_flops(probe, state, *argv) or float("nan")
            sweep[str(b_total)] = {
                "blocking_step_s": t,
                "examples_per_s": b_total / t,
                "step_mfu": (fb / t) / (peak * n_dev) if fb == fb and peak_ok else None,
            }
        except Exception as e:  # OOM at the top widths is a finding, not a crash
            sweep[str(b_total)] = {"error": f"{type(e).__name__}: {e}"[:300]}
        _save()

    # ---- F: profiler trace, parsed for busy fraction + top ops ----
    try:
        import glob
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="mfu_trace_")
        jax.profiler.start_trace(trace_dir)
        out = None
        for _ in range(5):
            out = probe(state, *args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        section = {"trace_dir": trace_dir}
        xspaces = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        if xspaces:
            # the plugin's protos clash with the already-imported protobuf
            # gencode; parse in a subprocess forced onto the python impl
            import subprocess

            env = dict(os.environ, PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION="python")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--parse-xplane", xspaces[0]],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
            )
            try:
                section.update(json.loads(proc.stdout))
            except Exception:
                section["parse_error"] = (proc.stderr or proc.stdout)[-500:]
        RESULT["sections"]["trace"] = section
    except Exception as e:
        RESULT["sections"]["trace"] = {"error": f"{type(e).__name__}: {e}"[:500]}
    _save()
    print(json.dumps(RESULT["sections"].get("step", {})))
    return 0


def _parse_xplane(path: str) -> dict:
    """Device busy fraction + top ops from a raw xplane proto, parsed
    directly with TF's bundled xplane proto (the tensorboard profile
    plugin in this image mismatches its TF; hand-rolling the two numbers
    we need is smaller than fixing that)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # type: ignore

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())

    out: dict = {"planes": []}
    for plane in space.planes:
        is_device = any(
            k in plane.name for k in ("TPU", "/device", "GPU")
        ) and "Host" not in plane.name
        stats = {"name": plane.name, "lines": len(plane.lines)}
        if not plane.lines:
            out["planes"].append(stats)
            continue
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        # busy time: union of event intervals across the plane's op lines;
        # top ops: summed duration by op name (self time approximated by
        # taking only the innermost "XLA Ops"-style line per plane)
        best_line = None
        for line in plane.lines:
            if best_line is None or len(line.events) > len(best_line.events):
                best_line = line
        intervals = []
        by_op: dict = {}
        for line in plane.lines:
            for ev in line.events:
                t0 = line.timestamp_ns + ev.offset_ps // 1000
                intervals.append((t0, t0 + ev.duration_ps // 1000))
        for ev in best_line.events:
            name = ev_meta.get(ev.metadata_id, str(ev.metadata_id))
            by_op[name] = by_op.get(name, 0) + ev.duration_ps / 1e12
        intervals.sort()
        busy_ns = 0
        span_lo = intervals[0][0] if intervals else 0
        span_hi = span_lo
        cur_lo, cur_hi = None, None
        for lo, hi in intervals:
            span_hi = max(span_hi, hi)
            if cur_hi is None or lo > cur_hi:
                if cur_hi is not None:
                    busy_ns += cur_hi - cur_lo
                cur_lo, cur_hi = lo, hi
            else:
                cur_hi = max(cur_hi, hi)
        if cur_hi is not None:
            busy_ns += cur_hi - cur_lo
        span_ns = max(span_hi - span_lo, 1)
        stats.update(
            {
                "span_s": span_ns / 1e9,
                "busy_s": busy_ns / 1e9,
                "busy_frac": busy_ns / span_ns,
                "is_device": is_device,
                "top_ops_s": dict(
                    sorted(by_op.items(), key=lambda kv: -kv[1])[:25]
                ),
            }
        )
        out["planes"].append(stats)
    return out


if __name__ == "__main__":
    sys.exit(main())
