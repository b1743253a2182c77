#!/usr/bin/env python
"""Generate the BASELINE.md acceptance-config statis artifacts.

Runs the 5 acceptance configs (BASELINE.md §"Acceptance configs"), each with
dbs on AND off (the A/B of the reference's run.sh:25-41), through the REAL
entry point (``cli.main`` — the analogue of ``python dbs.py <flags>``,
dbs.py:527-544), producing the 9-series ``.npy``/``.json`` recorder artifacts
per run (mirroring dbs.py:440-442) under ``--out_dir``.

Straggler profiles are induced deterministically with ``--straggler`` (the
analogue of the reference README's contended GPU map ``-gpu 0,0,0,1``,
README.md:23-28) in ``compute`` mode: real extra device FLOPs, so the
balancer reacts to genuinely measured time.

Scale knobs (env): STATIS_NTRAIN (vision examples, default 4096),
STATIS_LM_NTRAIN (LM tokens, default 120000), STATIS_EPOCHS (default 6),
STATIS_CPU=1 (force the 8-virtual-device CPU mesh — the reference's
gloo-on-localhost debug analogue), STATIS_ONLY (comma list of config names
to run, e.g. "c3_densenet"). Real data is used when present under ./data //
./rnn_data (run data/prepare.py first); otherwise the synthetic stand-ins.

Usage: python scripts/gen_statis.py [--out_dir statis/acceptance]
"""

import argparse
import json
import os
import sys
import time

if os.environ.get("STATIS_CPU") == "1":
    os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compile cache, placed by the one helper every entry uses
from dynamic_load_balance_distributeddnn_tpu.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

NTRAIN = int(os.environ.get("STATIS_NTRAIN", 4096))
LM_NTRAIN = int(os.environ.get("STATIS_LM_NTRAIN", 120_000))
EPOCHS = int(os.environ.get("STATIS_EPOCHS", 6))

# name -> cli args (without -dbs; both arms added by the driver loop below).
# ocp on for the CNN sweep legs, as run.sh:25-41 does.
CONFIGS = {
    # 1. MnistNet / FashionMNIST, 2-worker, debug-mode scale (BASELINE #1)
    "c1_mnistnet": [
        "-d", "true", "-ws", "2", "-b", "128", "-m", "mnistnet", "-ds", "mnist",
        "--straggler", "3,1",
    ],
    # 2. ResNet-18 / CIFAR-10, 4-worker, balanced workers (BASELINE #2)
    "c2_resnet18": [
        "-d", "false", "-ws", "4", "-b", "512", "-m", "resnet18", "-ds", "cifar10",
        "-ocp", "true",
    ],
    # 3. DenseNet-121 / CIFAR-10, 4-worker, 3:1 straggler — the README recipe
    #    (BASELINE #3, north star)
    "c3_densenet": [
        "-d", "false", "-ws", "4", "-b", "512", "-m", "densenet", "-ds", "cifar10",
        "-ocp", "true", "--straggler", "3,1,1,1",
    ],
    # 4. RegNet / CIFAR-10, 8-worker heterogeneous mix (BASELINE #4)
    "c4_regnet_ws8": [
        "-d", "false", "-ws", "8", "-b", "512", "-m", "regnet", "-ds", "cifar10",
        "-ocp", "true", "--straggler", "3,2,1,1,1,1,1,1",
    ],
    # 4b. GoogLeNet twin of BASELINE #4 ("RegNet / GoogLeNet on CIFAR-10,
    #     8-worker"); not in the default queue — run via STATIS_ONLY
    "c4b_googlenet_ws8": [
        "-d", "false", "-ws", "8", "-b", "512", "-m", "googlenet", "-ds", "cifar10",
        "-ocp", "true", "--straggler", "3,2,1,1,1,1,1,1",
    ],
    # 5. Transformer LM / wikitext-2, 4-worker (BASELINE #5)
    "c5_transformer": [
        "-d", "false", "-ws", "4", "-b", "80", "-m", "transformer", "-ds", "wikitext2",
        "--bptt", "35", "--grad_clip", "0.25", "--bucket", "4",
        "--straggler", "3,1,1,1",
    ],
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out_dir", default="statis/acceptance")
    ns = ap.parse_args()

    seed = os.environ.get("STATIS_SEED")
    if seed:
        # seed is NOT part of Config.base_filename(), so sentinels and
        # recorder artifacts of different seeds would collide in one
        # out_dir (first-seed sentinels silently skip the second seed's
        # runs; cleared sentinels overwrite its artifacts). Nest per seed
        # so collisions are structurally impossible.
        ns.out_dir = os.path.join(ns.out_dir, f"seed{seed}")
    if os.environ.get("STATIS_GPU_MAP"):
        # same collision hazard: the device map is not config-encoded
        ns.out_dir = os.path.join(
            ns.out_dir, "gpumap" + os.environ["STATIS_GPU_MAP"].replace(",", "")
        )

    import jax

    from dynamic_load_balance_distributeddnn_tpu import cli
    from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
        arm_stall_watchdog,
    )

    # A runtime that stops answering leaves PJRT hung in C++ (0% CPU,
    # uninterruptible); the engine heartbeats per compile/probe/epoch, so a
    # stale heartbeat means a dead backend — exit non-zero.
    if os.environ.get("STATIS_CPU") != "1":
        arm_stall_watchdog(
            os.path.join(ns.out_dir, ".hb"),
            float(os.environ.get("STATIS_STALL_S", 1200)),
        )

    stat_dir = os.path.join(ns.out_dir, "statis")
    log_dir = os.path.join(ns.out_dir, "logs")
    os.makedirs(stat_dir, exist_ok=True)

    only = os.environ.get("STATIS_ONLY")
    # opt-in extras (run via STATIS_ONLY) — a bare invocation runs exactly
    # the 5 BASELINE acceptance configs the docstring promises
    optional = {"c4b_googlenet_ws8"}
    if only:
        wanted = set(only.split(","))
        names = [n for n in CONFIGS if n in wanted]
    else:
        names = [n for n in CONFIGS if n not in optional]
    vision_b = os.environ.get("STATIS_VISION_B")  # reduced-scale CPU insurance
    # STATIS_GPU_MAP: explicit worker->device map (the reference's -gpu
    # 0,0,0,1 contention syntax). CPU-tier escape hatch: mapping all workers
    # to one device keeps per-worker executables single-device — the
    # 8-device SPMD compile of a decomposed-grouped-conv RegNet is an
    # XLA:CPU compile blowup even though the same graph compiles in ~42 s
    # per worker single-device. Applied ONLY to vision configs whose
    # world_size equals the map length (it is a per-config escape hatch,
    # not a global topology override), and the run nests into its own
    # out_dir because the device map is not part of the config-encoded
    # filenames (same collision hazard as STATIS_SEED above).
    gpu_map = os.environ.get("STATIS_GPU_MAP")  # out_dir nesting done above
    # STATIS_FORCE_ELASTIC=1: for configs that would otherwise take a
    # whole-epoch fused/packed CNN scan (no straggler -> uniform fused plan,
    # i.e. c2), map two workers per device so both arms use the elastic
    # per-worker executables — the XLA *CPU* backend compiles the fused CNN
    # scan pathologically slowly (30+ min for ResNet-18) while the elastic
    # path's small per-step graphs compile in seconds. Straggler configs
    # already run elastic (compute-mode probes force it) and keep their
    # default topology. CPU-insurance only; TPU runs skip this env var.
    force_elastic = os.environ.get("STATIS_FORCE_ELASTIC") == "1"
    platform = jax.devices()[0].platform
    device_kind = getattr(jax.devices()[0], "device_kind", "?")
    # merge with any existing manifest: this dir is filled across several
    # invocations (c1/c5 on the CPU tier, c2-c4 on chip) and each run's
    # provenance must survive them all
    mpath = os.path.join(ns.out_dir, "manifest.json")
    manifest = {}
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        pass
    manifest.update(
        {
            "platform": platform,
            "device_kind": device_kind,
            "ntrain": NTRAIN,
            "lm_ntrain": LM_NTRAIN,
            "epochs": EPOCHS,
        }
    )
    manifest.setdefault("runs", {})
    for name in names:
        base = list(CONFIGS[name])
        if vision_b and name != "c5_transformer":
            bi = base.index("-b")
            base[bi + 1] = vision_b
        if (
            gpu_map
            and "-gpu" not in base
            and name != "c5_transformer"
            and len(gpu_map.split(",")) == int(base[base.index("-ws") + 1])
        ):
            print(f"[gen_statis] {name}: applying STATIS_GPU_MAP={gpu_map}", flush=True)
            base += ["-gpu", gpu_map]
        if force_elastic and "-gpu" not in base and "--straggler" not in base:
            ws = int(base[base.index("-ws") + 1])
            if ws >= 4:  # >=2 devices, >=2 workers/device: elastic, not packed
                base += ["-gpu", ",".join(str(i // 2) for i in range(ws))]
        n_train = LM_NTRAIN if name == "c5_transformer" else NTRAIN
        # STATIS_ARM_ORDER=false_first flips the arms: running the A/B in
        # both orders exposes host-throughput drift between the two arms'
        # time windows (sequential arms on a noisy 1-core box can differ
        # several % for identical work)
        arm_order = (
            ("false", "true")
            if os.environ.get("STATIS_ARM_ORDER") == "false_first"
            else ("true", "false")
        )
        seed = os.environ.get("STATIS_SEED")  # second-seed parity pairs
        for dbs in arm_order:
            args = base + (["--seed", seed] if seed else []) + [
                "-dbs", dbs,
                "-e", str(EPOCHS),
                "--n_train", str(n_train),
                "--fault_mode", "compute",
                # warm_start pre-compiles the shape ladder — worth it on TPU
                # (cached, fast), prohibitive on the CPU mesh. The balancer's
                # signal is compile-free either way (probe warm pass).
                "--warm_start", os.environ.get("STATIS_WARM", "false"),
                "--stat_dir", stat_dir,
                "--log_dir", log_dir,
            ]
            from dynamic_load_balance_distributeddnn_tpu.config import (
                config_from_args,
            )
            from dynamic_load_balance_distributeddnn_tpu.obs.logging import (
                _done_sentinel,
                run_already_done,
            )

            cfg = config_from_args(args)
            key = f"{name}_dbs{dbs}"
            # a non-tpu (e.g. reduced-scale CPU-insurance) run must never
            # clobber a chip entry's provenance — it runs a different config
            # (different sentinel), so record it under its own key and leave
            # the tpu entry (and its sentinel) standing
            if (
                platform != "tpu"
                and (manifest["runs"].get(key) or {}).get("platform") == "tpu"
            ):
                key = f"{key}_{platform}"
            # chip runs supersede CPU-tier runs in the same out_dir (never
            # the reverse): if this arm's sentinel was written by a non-TPU
            # invocation and we are ON the chip now, clear it so the run
            # re-executes here instead of being skipped by the reference
            # idempotence probe
            if platform == "tpu":
                prev_run = manifest["runs"].get(key) or {}
                # only the PER-RUN platform is trustworthy: the top-level
                # manifest platform is whatever the last invocation ran on
                # (a CPU-tier c1 run after a TPU c3 run would misclassify the
                # TPU sentinels and re-run them).
                # Anything not positively attributed to the chip — explicit
                # cpu tier, a legacy entry with no platform field, or an
                # unattributed sentinel skip — is superseded by running here:
                # one idempotent re-run, after which the manifest records tpu
                prev_platform = prev_run.get("platform")
                if prev_platform != "tpu":
                    sentinel = _done_sentinel(cfg)
                    if os.path.isfile(sentinel):
                        os.unlink(sentinel)
                        print(
                            f"[gen_statis] {name} dbs={dbs}: clearing "
                            f"{prev_platform or 'unattributed'} sentinel, "
                            "re-running on tpu",
                            flush=True,
                        )
            skipped = run_already_done(cfg)
            t0 = time.time()
            print(f"[gen_statis] {name} dbs={dbs}: cli.main({' '.join(args)})", flush=True)
            rc = cli.main(args)
            if skipped and key in manifest["runs"]:
                # sentinel skip: the run that produced the artifacts is the
                # recorded one — keep its provenance, don't clobber wall_s
                # and platform with the skip's
                pass
            else:
                manifest["runs"][key] = {
                    "rc": rc,
                    "wall_s": round(time.time() - t0, 1),
                    # a sentinel skip executed nothing here: the artifacts
                    # came from an invocation this manifest never saw, so
                    # their platform is unknown — recording THIS invocation's
                    # would let a later TPU pass wrongly trust (or clear) them
                    "platform": "unknown" if skipped else platform,
                    "device_kind": "?" if skipped else device_kind,
                    "args": args,
                    **({"sentinel_skip": True} if skipped else {}),
                }
            with open(mpath, "w") as f:
                json.dump(manifest, f, indent=2)
            if rc != 0:
                print(f"[gen_statis] {name} dbs={dbs} FAILED rc={rc}", file=sys.stderr)
                return rc
    print("[gen_statis] all runs complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
