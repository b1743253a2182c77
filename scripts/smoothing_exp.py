#!/usr/bin/env python
"""Partition-stability experiment (VERDICT r2 weak #4 / next-round #7).

Round 2's c5 artifact showed the transformer's node_time vector swinging
7.4<->30.8s epoch-to-epoch on the CPU mesh and the partition oscillating with
it. Two candidate stabilizers exist; this experiment measures both on the
c5-style config so the default is evidence-based, not vibes:

  A. probe_mode=always, time_smoothing=0    (round-2 behavior, the baseline)
  B. probe_mode=adaptive, time_smoothing=0  (round-3 default: epochs 2+ feed
     the solver noise-free MODELED times)
  C. probe_mode=always, time_smoothing=0.5  (EMA damping on measured times)

Metric per arm: partition churn = mean over epochs>=3 of max_r |share_r(e) -
share_r(e-1)| (0 = frozen), plus the share trajectory of the straggled
worker. Writes artifacts/SMOOTHING.json; runs on the CPU mesh by default
(the noise source under study IS host contention).

Usage: python scripts/smoothing_exp.py [--epochs 8] [--ntrain 60000]
"""

import argparse
import json
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU-mesh experiment by design
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamic_load_balance_distributeddnn_tpu.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import numpy as np


def churn(partitions: list) -> dict:
    p = np.asarray(partitions, dtype=np.float64)
    if len(p) < 4:
        return {"mean_step": None, "max_step": None}
    steps = np.abs(np.diff(p, axis=0)).max(axis=1)[2:]  # epochs >= 3
    return {
        "mean_step": float(steps.mean()),
        "max_step": float(steps.max()),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--ntrain", type=int, default=60_000)
    ap.add_argument("--straggler", default="3,1,1,1")
    ap.add_argument("--arms", default="", help="comma list of arm names to (re)run")
    args = ap.parse_args()

    from dynamic_load_balance_distributeddnn_tpu.config import Config
    from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

    arms = {
        "A_always_raw": dict(probe_mode="always", time_smoothing=0.0),
        "B_adaptive_raw": dict(probe_mode="adaptive", time_smoothing=0.0),
        "C_always_ema05": dict(probe_mode="always", time_smoothing=0.5),
    }
    out = {"config": vars(args), "arms": {}}
    if os.path.exists("artifacts/SMOOTHING.json"):
        try:
            with open("artifacts/SMOOTHING.json") as f:
                out["arms"] = json.load(f).get("arms", {})
        except Exception:
            pass
    if args.arms:
        selected = {a.strip() for a in args.arms.split(",") if a.strip()}
        unknown = selected - set(arms)
        if unknown:
            raise SystemExit(f"unknown arms {sorted(unknown)}; choose from {sorted(arms)}")
    else:
        selected = None
    for name, kw in arms.items():
        if selected is not None and name not in selected:
            continue
        cfg = Config(
            debug=False,
            world_size=4,
            batch_size=80,
            learning_rate=0.01,
            epoch_size=args.epochs,
            dataset="wikitext2",
            model="transformer",
            dynamic_batch_size=True,
            bucket=4,
            bptt=35,
            grad_clip=0.25,
            n_train=args.ntrain,
            straggler=args.straggler,
            fault_mode="compute",
            **kw,
        )
        tr = LMTrainer(cfg, log_to_file=False)
        parts, times = [], []
        for e in range(args.epochs):
            tr.run_epoch(e)
            parts.append(tr.shares.tolist())
            times.append([round(t, 4) for t in tr.node_times.tolist()])
        out["arms"][name] = {
            # per-arm config snapshot: merged re-runs of single arms must not
            # let stale arms masquerade as results for the current argv
            "config": vars(args),
            "partitions": [[round(x, 4) for x in p] for p in parts],
            "node_times": times,
            "churn": churn(parts),
            "straggler_share_final": round(parts[-1][0], 4),
        }
        os.makedirs("artifacts", exist_ok=True)
        with open("artifacts/SMOOTHING.json", "w") as f:
            json.dump(out, f, indent=1)
        print(name, out["arms"][name]["churn"], "w0 share", parts[-1][0], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
