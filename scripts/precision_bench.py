#!/usr/bin/env python
"""bf16-vs-f32 A/B on the clean fused leg -> artifacts/PRECISION.md.

Justifies the benchmark's default compute dtype (bench.py BENCH_PRECISION)
with measured numbers: epoch walls, examples/s, MFU, and the training-loss
trajectory delta (numerics evidence — bf16 keeps f32 master weights and f32
loss/grad accumulation, so the trajectories should stay close).

Usage: python scripts/precision_bench.py [--n_train 12800] [--epochs 3]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compile cache, placed by the one helper every entry uses
from dynamic_load_balance_distributeddnn_tpu.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


def run_leg(precision: str, n_train: int, epochs: int, model: str):
    from dynamic_load_balance_distributeddnn_tpu.config import Config
    from dynamic_load_balance_distributeddnn_tpu.data import load_dataset
    from dynamic_load_balance_distributeddnn_tpu.train import Trainer

    cfg = Config(
        debug=False,
        world_size=4,
        batch_size=512,
        learning_rate=0.01,
        epoch_size=epochs,
        dataset="cifar10",
        model=model,
        dynamic_batch_size=False,
        bucket=32,
        precision=precision,
    )
    bundle = load_dataset("cifar10", n_train=n_train, n_test=512)
    tr = Trainer(cfg, bundle=bundle, log_to_file=False)
    walls, losses = [], []
    for e in range(epochs):
        m = tr.run_epoch(e)
        walls.append(m["epoch_wall"])
        losses.append(m["loss"])
    out = {
        "precision": precision,
        "epoch_walls_s": [round(w, 4) for w in walls],
        "train_loss": [round(l, 5) for l in losses],
        "examples_per_s": tr.recorder.data.get("examples_per_s", [None])[-1],
        "mfu_bf16_peak": tr.recorder.data.get("mfu_bf16_peak", [None])[-1],
    }
    return out


def _r(v, nd=1):
    return round(v, nd) if isinstance(v, float) else v


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n_train", type=int, default=12800)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--model", default="densenet")
    ap.add_argument("--out_dir", default="artifacts")
    ns = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    platform, kind = dev.platform, getattr(dev, "device_kind", "?")
    print(f"[precision_bench] {platform}/{kind}", flush=True)

    # A runtime that stops answering hangs PJRT at 0% CPU; the engine
    # heartbeats per epoch/probe, so a stale heartbeat means a dead backend.
    # TPU-only: the XLA CPU backend's fused whole-epoch scan can legitimately
    # compile for 30+ min with no heartbeat (see gen_statis STATIS_FORCE_
    # ELASTIC note), which would false-trigger the stall check.
    if platform != "cpu":
        from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
            arm_stall_watchdog,
        )

        arm_stall_watchdog(
            os.path.join(ns.out_dir, ".precision.hb"),
            float(os.environ.get("PRECISION_STALL_S", 1200)),
        )

    results = {}
    for prec in ("float32", "bfloat16"):
        t0 = time.time()
        results[prec] = run_leg(prec, ns.n_train, ns.epochs, ns.model)
        print(f"[precision_bench] {prec}: {results[prec]} ({time.time()-t0:.0f}s)",
              flush=True)

    os.makedirs(ns.out_dir, exist_ok=True)
    payload = {"platform": platform, "device_kind": kind,
               "model": ns.model, "n_train": ns.n_train, "results": results}
    with open(os.path.join(ns.out_dir, f"precision_bench_{platform}.json"), "w") as f:
        json.dump(payload, f, indent=2)

    f32, bf16 = results["float32"], results["bfloat16"]
    # steady wall = min past the compile epoch
    w32 = min(f32["epoch_walls_s"][1:]) if len(f32["epoch_walls_s"]) > 1 else None
    w16 = min(bf16["epoch_walls_s"][1:]) if len(bf16["epoch_walls_s"]) > 1 else None
    speedup = round(w32 / w16, 3) if w32 and w16 else None
    loss_delta = max(
        abs(a - b) for a, b in zip(f32["train_loss"], bf16["train_loss"])
    )
    md = [
        f"# Precision A/B — {platform} ({kind})",
        "",
        f"{ns.model} / cifar10(synthetic-ok), B=512, ws=4, clean fused leg,",
        f"n_train={ns.n_train}. bf16 = bfloat16 compute with f32 master",
        "weights and f32 loss/grad accumulation (the MXU's native dtype).",
        "",
        "| precision | steady epoch (s) | examples/s | MFU (bf16 peak) | final train loss |",
        "|---|---|---|---|---|",
        "| float32 | {} | {} | {} | {} |".format(
            w32, _r(f32["examples_per_s"]), _r(f32["mfu_bf16_peak"], 4),
            f32["train_loss"][-1],
        ),
        "| bfloat16 | {} | {} | {} | {} |".format(
            w16, _r(bf16["examples_per_s"]), _r(bf16["mfu_bf16_peak"], 4),
            bf16["train_loss"][-1],
        ),
        "",
        f"**bf16 speedup: {speedup}x**; max per-epoch train-loss delta "
        f"{loss_delta:.4f} (same data order, same seeds).",
        "",
        "Generated by `scripts/precision_bench.py`.",
    ]
    with open(os.path.join(ns.out_dir, "PRECISION.md"), "w") as f:
        f.write("\n".join(md) + "\n")
    print(f"[precision_bench] wrote {ns.out_dir}/PRECISION.md", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
