"""The program's routing counts of the window's epochs, for the per-layer
readers that share them (``routed_here_pct``, ``expert_load_max_over_mean``).

With its tracer on, a routed model's trainer leaves one line an epoch in
``<trace_dir>/routing_counts.jsonl``: ``counts[step x worker][expert
layer][held + 1]``, the (token, choice) pairs that arrived at each held
expert and, last, those routed to experts held elsewhere
(``dynamic_load_balance_distributeddnn_tpu/obs/routing.py``). A program that
writes no such file (one with no routed model; the parent of the PR that
brought this reader) gives ``None``."""

import json
import os

COUNTS_FILE = "routing_counts.jsonl"


def window_rows(ctx):
    """``[rows][layer][held + 1]`` over the window's epochs, or ``None``."""
    if "routing_rows" not in ctx:
        ctx["routing_rows"] = None
        path = os.path.join(ctx.get("run_dir") or "", "traces", COUNTS_FILE)
        if os.path.isfile(path):
            wanted = {e["index"] for e in ctx["epochs"]}
            rows = []
            with open(path) as f:
                for line in f:
                    if line.strip():
                        row = json.loads(line)
                        if row["epoch"] in wanted:
                            rows.extend(row["counts"])
            ctx["routing_rows"] = rows or None
    return ctx["routing_rows"]
