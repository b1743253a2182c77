"""FLOP accounting and MFU estimation.

The reference has no profiling beyond wall-clock (SURVEY §5.1). Here the
compiled step's own XLA cost model supplies per-step FLOPs
(``lowered.compile().cost_analysis()``), giving throughput (examples/s,
tokens/s) and MFU against the chip's peak — the "fast, or just correct?"
instrumentation the TPU build needs.

MFU is reported against the chip's **bf16 systolic-array peak** regardless of
the run's compute dtype (f32 runs will show correspondingly lower MFU); the
key name says so explicitly.
"""

from __future__ import annotations

from typing import Optional

import jax

# Per-chip dense peak matmul throughput, bf16, FLOP/s. Sources: public TPU
# spec sheets (per-chip, all MXUs).
_PEAK_BF16 = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def chip_peak_flops(device=None) -> Optional[float]:
    """Peak bf16 FLOP/s for one chip, or None when unknown (e.g. CPU)."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    for name, peak in _PEAK_BF16.items():
        if kind.lower().startswith(name.lower()):
            return peak
    return None


def compiled_flops(jitted_fn, *args, compiled=None) -> Optional[float]:
    """FLOPs of one execution of ``jitted_fn(*args)`` per XLA's cost model.
    Returns None when the backend doesn't expose cost analysis.

    ``compiled``: an already-compiled executable (``jax.stages.Compiled``,
    e.g. fetched from the AOT compile service) — its cost analysis is read
    directly and NOTHING is recompiled. Without it this function lowers and
    compiles a second copy of the step just to ask for its cost, which on a
    big model is a whole duplicate XLA compile."""
    try:
        if compiled is None:
            compiled = jitted_fn.lower(*args).compile()
        val = float(compiled.cost_analysis().get("flops", 0.0))
        return val if val > 0 else None
    except Exception:
        return None


def mfu(flops_per_second: Optional[float], n_devices: int = 1, device=None) -> Optional[float]:
    """Model FLOP utilization in [0,1] vs the mesh's aggregate bf16 peak."""
    peak = chip_peak_flops(device)
    if peak is None or flops_per_second is None:
        return None
    return flops_per_second / (peak * max(n_devices, 1))
