"""Compile-only guard: the Pallas kernels of the main paths, at the widths
``chip_smoke.py`` runs them, through the TPU compiler for a DESCRIBED v5e
chip (no chip attached, nothing executes).

Interpret-mode tests cannot see what Mosaic refuses (a slice off the tiling,
too much fast memory); this can, at ~2 s a case and no chip time. A compile
that passes is not a chip run — ``chip_smoke.py``'s ``kernels`` phase is.

All cases live in this one file and describe the topology inside a
module-scoped fixture: only the xdist worker that is handed this file loads
the TPU library, and every worker collects the same tests.
"""

import os
import sys

import jax
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip; the persistent compile cache is
    off around these tests — an entry written for a described chip cannot be
    read back without one and would only warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the TPU library raises where it cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


@pytest.mark.parametrize("mode", ["forward", "gradient"])
@pytest.mark.parametrize(
    "kind,shape",
    [(kind, shape) for _, kind, shape in chip_smoke.KERNEL_CASES],
    ids=[case_id for case_id, _, _ in chip_smoke.KERNEL_CASES],
)
def test_kernel_compiles_for_v5e(one_chip, kind, shape, mode):
    pallas_fn, _, specs, argnums = chip_smoke.kernel_case(kind, shape)
    fn = pallas_fn if mode == "forward" else chip_smoke.grad_of(pallas_fn, argnums)
    args = [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip) for s in specs
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
