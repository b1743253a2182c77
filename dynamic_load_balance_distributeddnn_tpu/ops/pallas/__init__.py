"""Pallas TPU kernels for the framework's hot ops.

The reference delegates all device math to cuDNN via ``model.to("cuda:N")``
(dbs.py:66-68, 363); on TPU the equivalent default is XLA codegen, and these
kernels are the "only where XLA underperforms" layer (SURVEY §2.2): fused
GroupNorm (the normalization every CNN in the zoo uses, Net/Resnet.py:11
et al.) and fused softmax cross-entropy (the CNN criterion, dbs.py:374).

Kernels run as real Mosaic kernels on TPU and in interpreter mode elsewhere
(CPU tests), selected automatically. The module-level toggle gates whether
model builders and step libraries route through them; default off so the
pure-XLA path stays the reference numerical baseline.
"""

from __future__ import annotations

import jax

_USE_PALLAS = False


def set_use_pallas(flag: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = bool(flag)


def use_pallas() -> bool:
    return _USE_PALLAS


def interpret_default() -> bool:
    """Real kernels on TPU, interpreter everywhere else."""
    return jax.default_backend() != "tpu"


from dynamic_load_balance_distributeddnn_tpu.ops.pallas.groupnorm import (  # noqa: E402
    fused_group_norm,
)
from dynamic_load_balance_distributeddnn_tpu.ops.pallas.xent import (  # noqa: E402
    fused_softmax_xent,
)

__all__ = [
    "set_use_pallas",
    "use_pallas",
    "interpret_default",
    "fused_group_norm",
    "fused_softmax_xent",
]
