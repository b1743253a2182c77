"""The gated delta rule's share of its roofline over the profiled epoch: the
least time the chip could take for the epoch's delta-rule work over the device
seconds of the program's ``delta_rule`` scope (``scope_reduce``).

The least time is the larger of operations / the peak bf16 FLOP/s and bytes /
the peak HBM bytes/s (``benchmark/peaks.json``), both from
``benchmark/flops/delta_rule.py``: the recurrence's three products a token and
head, three times over for a trained window and once for a validated one, and
q, k, v, g, beta in and o out once a pass at the pass's element size. It counts
the recurrence and not the form, so it reads on the chunked XLA form and on
the kernels alike; recomputation (the block's, under ``--remat``) is in the
seconds and not in the count. The epoch's trained windows are its samples;
its validated windows are the validation stream's tokens over the window.
Prints which bound holds. Nothing where the program has no such scope or the
model no linear layer. A rehearsal's device has no peaks (``ctx["peak"]`` is
``None``): its share is reckoned against the one chip ``peaks.json`` holds, as
a check of the count and, like everything a rehearsal prints, no device
number."""

import json
import os

from benchmark import scope_reduce
from benchmark.flops import delta_rule

ELEMENT_BYTES = {"bfloat16": 2, "float32": 4}
PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def rehearsal_peak():
    with open(PEAKS) as f:
        chips = list(json.load(f).values())
    return chips[0] if len(chips) == 1 else None


def read(ctx):
    table, epoch = scope_reduce.table(ctx), ctx.get("profiled_epoch")
    peak = ctx.get("peak") or rehearsal_peak()
    model = ctx["model"]
    if (not table or not table["seconds"] or not epoch or not peak
            or "linear_num_value_heads" not in model):
        return None
    seconds = table["seconds"].get("delta_rule", 0.0)
    argv = ctx["config"]["argv"]
    precision = argv[argv.index("--precision") + 1] if "--precision" in argv else "float32"
    if seconds <= 0 or not delta_rule.linear_layers(model) or precision not in ELEMENT_BYTES:
        return None
    validated = ctx["config"]["n_test"] // model["seq_len"]
    operations, moved = delta_rule.epoch_operations_and_bytes(
        model, epoch["samples"], validated, ELEMENT_BYTES[precision])
    by_products = operations / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    print(json.dumps({"delta_rule_roofline": {
        "scope_s": seconds, "trained_windows": epoch["samples"], "validated_windows": validated,
        "flops": operations, "bytes": moved, "least_s_by_products": by_products,
        "least_s_by_bytes": by_bytes, "bound": "bytes" if by_bytes >= by_products else "products",
    }}), flush=True)
    return 100.0 * max(by_products, by_bytes) / seconds
