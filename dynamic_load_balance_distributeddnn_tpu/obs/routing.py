"""Routing counts of a routed-expert model, beside the scope map.

A routed model's step counts, per expert layer, the (token, choice) pairs
that arrive at each expert this chip holds and, last, those routed to experts
held elsewhere (``models/afmoe.py``). The scanned superstep carries them out
with the loss (``train/steps.py``: no host read inside a step); the engine
hands each epoch's rows here, and while the tracer is on one line an epoch
goes to ``<trace_dir>/routing_counts.jsonl``::

    {"epoch": <n>, "counts": [step x worker][layer][held + 1]}

Positions the model was given are all counted, padding among them. With the
tracer off, or told no directory, nothing is written.
"""

from __future__ import annotations

import json
import logging
import os
import threading

from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer

COUNTS_FILE = "routing_counts.jsonl"
_write_lock = threading.Lock()


def record_epoch(epoch: int, rows, shape) -> None:
    """``rows``: one flat vector of counts per (step, worker), in the order
    the epoch ran them; ``shape``: ``(expert layers, held + 1)`` of each."""
    tr = get_tracer()
    if not tr.enabled or not tr.trace_dir or not len(rows):
        return
    counts = [[[int(v) for v in layer] for layer in row.reshape(shape)] for row in rows]
    line = json.dumps({"epoch": int(epoch), "counts": counts})
    try:
        with _write_lock:
            os.makedirs(tr.trace_dir, exist_ok=True)
            with open(os.path.join(tr.trace_dir, COUNTS_FILE), "a") as f:
                f.write(line + "\n")
    except OSError as e:  # the epoch trained; only its counts are lost
        logging.getLogger("graftscope").warning("routing counts of epoch %s not written: %s",
                                                epoch, e)
