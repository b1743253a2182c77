#!/usr/bin/env python
"""Render recorder artifacts (./statis *.npy/json) as tables.

The reference's workflow dumps per-config numpy dicts (dbs.py:440-442) and
leaves interpretation to offline plotting; this gives the same data a quick
terminal view, and computes the dbs-on/off A/B headline when both arms of a
config are present in the directory.

Usage:
  python scripts/summarize_statis.py statis/acceptance/statis [more dirs/files]
"""

import json
import os
import sys

import numpy as np


def load(path):
    if path.endswith(".npy"):
        d = np.load(path, allow_pickle=True).item()
        # the JSON sidecar carries run-level _meta (data provenance) that the
        # reference-parity .npy payload deliberately omits
        sidecar = path[:-4] + ".json"
        if os.path.exists(sidecar):
            try:
                with open(sidecar) as f:
                    d["_meta"] = json.load(f).get("_meta", {})
            except Exception:
                pass
        return d
    with open(path) as f:
        return json.load(f)


def fmt_run(name, d):
    rows = []
    meta = d.get("_meta") or {}
    if meta.get("synthetic"):
        name += "   [SYNTHETIC DATA — accuracies not comparable to real sets]"
    n = len(d.get("epoch", []))
    for e in range(n):
        part = np.asarray(d["partition"][e], dtype=float)
        nt = np.asarray(d["node_time"][e], dtype=float)
        rows.append(
            f"  {int(d['epoch'][e]):>3}  {d['train_loss'][e]:>8.4f}  "
            f"{d['val_loss'][e]:>8.4f}  {d['accuracy'][e]:>7.2f}  "
            f"{d['train_time'][e]:>8.3f}  {d['wallclock_time'][e]:>9.3f}  "
            f"{np.array2string(np.round(part, 3), separator=',')}"
            f"  max/min nt={nt.max() / max(nt.min(), 1e-9):.2f}"
        )
    header = (
        "  ep  train_ls   val_ls      acc   t_node0   wallclock  partition"
    )
    return f"{name}\n{header}\n" + "\n".join(rows)


def main(argv):
    md_out = None
    argv = list(argv or [])
    if "--markdown" in argv:
        i = argv.index("--markdown")
        if i + 1 >= len(argv):
            print("usage: summarize_statis.py [--markdown OUT] [PATHS...]",
                  file=sys.stderr)
            return 2
        md_out = argv[i + 1]
        del argv[i : i + 2]
    paths = []
    for a in argv or ["./statis"]:
        if os.path.isdir(a):
            paths += sorted(
                os.path.join(a, f) for f in os.listdir(a) if f.endswith(".npy")
            )
        elif os.path.exists(a):
            paths.append(a)
    runs = {}
    for p in paths:
        try:
            # keyed by basename; a same-config artifact from a second dir
            # (e.g. a gpumap/seed-nested variant of one config) must not
            # silently shadow the first — disambiguate with the parent dir
            key = os.path.basename(p)
            parent = os.path.dirname(p)
            while key in runs and parent:
                key = f"{os.path.basename(parent)}/{key}"
                parent = os.path.dirname(parent)
            runs[key] = load(p)
        except Exception as e:
            print(f"skip {p}: {e}", file=sys.stderr)
    for name, d in runs.items():
        print(fmt_run(name, d))
        print()
    # A/B headline per config: pair -dbs1- with -dbs0-
    ab_rows = []
    for name, d in runs.items():
        if "-dbs1-" not in name:
            continue
        off_name = name.replace("-dbs1-", "-dbs0-")
        off = runs.get(off_name)
        if off is None:
            continue
        on_w = np.diff([0.0] + list(d["wallclock_time"]))
        off_w = np.diff([0.0] + list(off["wallclock_time"]))
        # steady state: skip the calibration epoch (and first reaction, on-arm);
        # median headline + min alongside
        on_win = on_w[2:] if len(on_w) > 2 else on_w[-1:]
        off_win = off_w[1:] if len(off_w) > 1 else off_w[-1:]
        on_med, off_med = float(np.median(on_win)), float(np.median(off_win))
        on_min, off_min = float(np.min(on_win)), float(np.min(off_win))
        # balancer-quality metric (BASELINE.md §protocol): distance of the
        # final partition from the ideal equilibrium share_i ∝ 1/f_i, when
        # the artifact records its induced straggler profile
        conv = None
        factors = (d.get("_meta") or {}).get("straggler_factors")
        if factors:
            inv = 1.0 / np.asarray(factors, dtype=float)
            ideal = inv / inv.sum()
            final = np.asarray(d["partition"][-1], dtype=float)
            conv = float(np.abs(final - ideal).max())
        ab_rows.append(
            {
                "config": name.split("-node")[0],
                "on_median_s": on_med,
                "off_median_s": off_med,
                "speedup_median": off_med / max(on_med, 1e-9),
                "speedup_min": off_min / max(on_min, 1e-9),
                "acc_on": float(d["accuracy"][-1]),
                "acc_off": float(off["accuracy"][-1]),
                "synthetic": bool((d.get("_meta") or {}).get("synthetic")),
                "partition_err": conv,
            }
        )
        print(
            f"A/B {name.split('-node')[0]}: steady epoch "
            f"on={on_med:.3f}s off={off_med:.3f}s "
            f"speedup(median)={off_med / max(on_med, 1e-9):.2f}x "
            f"speedup(min)={off_min / max(on_min, 1e-9):.2f}x "
            f"acc on/off={d['accuracy'][-1]:.2f}/{off['accuracy'][-1]:.2f}"
        )
    if md_out and ab_rows:
        lines = [
            "# Acceptance A/B table",
            "",
            "Steady-state epoch wall-clock, dbs on vs off (median over the "
            "steady window, min alongside; reference protocol BASELINE.md).",
            "",
            "| config | on median (s) | off median (s) | speedup (median) | "
            "speedup (min) | acc on/off | partition err |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in sorted(ab_rows, key=lambda r: r["config"]):
            acc = f"{r['acc_on']:.2f}/{r['acc_off']:.2f}"
            if r["synthetic"]:
                acc += " (synthetic)"
            perr = (
                f"{r['partition_err']:.3f}"
                if r["partition_err"] is not None
                else "—"
            )
            lines.append(
                f"| {r['config']} | {r['on_median_s']:.3f} | "
                f"{r['off_median_s']:.3f} | {r['speedup_median']:.2f}x | "
                f"{r['speedup_min']:.2f}x | {acc} | {perr} |"
            )
        with open(md_out, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"[summarize_statis] wrote {md_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
