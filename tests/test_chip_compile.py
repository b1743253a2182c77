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

import functools
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


# The routed decoder's ops (PR 27, PR 28) at Trinity-Mini's widths and the
# cell's shapes. The blocked attention and the expert layer are XLA
# throughout: what is guarded is that they fit the chip (one block's scores,
# one chunk's rows), and that `lax.ragged_dot` still becomes XLA:TPU's own
# grouped matmul and not a dense product per expert. The fused attention is
# the default path's kernel: that it is taken, fits and is seen by the scopes.


def _cell_attention_operands(one_chip):
    import jax.numpy as jnp

    q = jax.ShapeDtypeStruct((2, 4096, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 4096, 4, 128), jnp.bfloat16, sharding=one_chip)
    return q, kv, kv


def _attention_gradient(fn):
    import jax.numpy as jnp

    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))


@functools.lru_cache(maxsize=None)
def _blocked_attention_gradient(one_chip, window):
    """The blocked form at the cell's shapes, compiled once for both tests
    that look at it (12 s each)."""
    from dynamic_load_balance_distributeddnn_tpu.ops import attention

    return _attention_gradient(
        lambda q, k, v: attention._blocked(q, k, v, window, 256)
    ).lower(*_cell_attention_operands(one_chip)).compile()


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_blocked_attention_gradient_fits_a_v5e(one_chip, window):
    compiled = _blocked_attention_gradient(one_chip, window)
    assert "tpu_custom_call" not in compiled.as_text()
    # all [32 heads, 4096, 4096] float32 scores of two columns would be 4.3 GB;
    # tied in sequence, a few blocks' worth are alive at once
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_fused_attention_gradient_is_taken_and_fits_a_v5e(one_chip, window):
    """The cell's attention call, compiled for the chip from a CPU process,
    is the program the chip runs: the fused kernels, forward and backward, and
    less temporary memory than the blocked form asks for."""
    from dynamic_load_balance_distributeddnn_tpu.ops import attention
    from tests.conftest import traced_instants

    with traced_instants("attention_path") as said:
        compiled = _attention_gradient(
            lambda q, k, v: attention.blocked_causal_attention(q, k, v, window)
        ).lower(*_cell_attention_operands(one_chip)).compile()
    assert said == [{"path": "fused", "why": "tpu", "window": window, "t": 4096,
                     "dtype": "bfloat16"}]
    text = compiled.as_text()
    assert "fused_attention_fwd" in text and "fused_attention_bwd" in text
    assert (compiled.memory_analysis().temp_size_in_bytes
            < _blocked_attention_gradient(one_chip, window).memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("window,scope", [(2048, "attention_window"), (None, "attention_full")])
def test_the_scopes_see_the_fused_attention_kernels(one_chip, window, scope):
    """A custom VJP's backward pass is not ``transpose(jvp(forward))`` of
    anything: its kernel has to land in the layer's scope all the same, under
    the block's rematerialisation as the model runs it, or
    ``attention_device_pct`` would fall for the wrong reason."""
    import re

    import jax.numpy as jnp

    from dynamic_load_balance_distributeddnn_tpu.obs import scopes
    from dynamic_load_balance_distributeddnn_tpu.ops.attention import blocked_causal_attention

    def layer(q, k, v):
        with jax.named_scope(scopes.FORWARD):
            with jax.named_scope(scope):
                o = blocked_causal_attention(q, k, v, window)
            return jnp.sum(o.astype(jnp.float32))

    text = jax.jit(jax.grad(jax.checkpoint(layer), argnums=(0, 1, 2))).lower(
        *_cell_attention_operands(one_chip)).compile().as_text()
    _, by_instruction = scopes.instruction_scopes(text)
    kernels = re.findall(r"^\s*(?:ROOT\s+)?%?(\S+) = .*tpu_custom_call", text, flags=re.M)
    assert {k.split(".")[0] for k in kernels} == {"fused_attention_fwd", "fused_attention_bwd"}
    assert {by_instruction[k] for k in kernels} == {scope}


# Qwen3-Next's mixers (PR 32) at its cell's shapes: the full layers' attention
# through the fused kernel at heads of 256 in groups of 8, and the chunked
# delta rule, plain XLA: that its gradient compiles for the chip and what it
# holds for all chunks at once fits beside a training job's state.


def test_fused_attention_gradient_is_taken_at_heads_of_256_on_a_v5e(one_chip):
    import jax.numpy as jnp

    from dynamic_load_balance_distributeddnn_tpu.ops import attention
    from tests.conftest import traced_instants

    q = jax.ShapeDtypeStruct((2, 4096, 16, 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 4096, 2, 256), jnp.bfloat16, sharding=one_chip)
    with traced_instants("attention_path") as said:
        compiled = _attention_gradient(
            lambda q, k, v: attention.blocked_causal_attention(q, k, v)
        ).lower(q, kv, kv).compile()
    assert said == [{"path": "fused", "why": "tpu", "window": None, "t": 4096,
                     "dtype": "bfloat16"}]
    text = compiled.as_text()
    assert "fused_attention_fwd" in text and "fused_attention_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * 2**30


def test_delta_rule_gradient_fits_a_v5e_and_lands_in_its_scope(one_chip):
    """One worker's call of the cell (2 columns of 4,096 tokens, 32 value over
    16 key heads of 128, bfloat16) takes the fused form when lowered for the
    chip, both kernels land in the ``delta_rule`` scope, and it holds less than
    the chunked XLA form does for all chunks at once."""
    import jax.numpy as jnp

    from dynamic_load_balance_distributeddnn_tpu.obs import scopes
    from dynamic_load_balance_distributeddnn_tpu.ops import linear_attention
    from tests.conftest import traced_instants

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled_gradient(rule):
        def layer(q, k, v, g, beta):
            with jax.named_scope(scopes.FORWARD):
                with jax.named_scope(scopes.LINEAR_ATTENTION):
                    with jax.named_scope(scopes.DELTA_RULE):
                        o = rule(q, k, v, g, beta)
                return jnp.sum(o.astype(jnp.float32))

        return jax.jit(jax.grad(jax.checkpoint(layer), argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()

    keys, values = (2, 4096, 16, 128), (2, 4096, 32, 128)
    args = (sds(keys), sds(keys), sds(values), sds(values[:3], jnp.float32),
            sds(values[:3], jnp.float32))
    with traced_instants("linear_attention_path") as said:
        fused = compiled_gradient(linear_attention.gated_delta_rule)
    # the checkpoint lowers the forward pass twice
    assert said and all(x == {"path": "fused", "why": "tpu", "chunk": 64, "t": 4096,
                              "dtype": "bfloat16", "heads": 32} for x in said)
    chunked = compiled_gradient(
        lambda *a: linear_attention._chunked(*a, linear_attention.CHUNK))
    # what is held for all 64 chunks of two columns at once, forward and backward
    assert chunked.memory_analysis().temp_size_in_bytes < 3 * 2**30
    assert "tpu_custom_call" not in chunked.as_text()  # plain XLA
    # the fused form: the states between chunks and the gradients of q and k a value head
    assert (fused.memory_analysis().temp_size_in_bytes
            < chunked.memory_analysis().temp_size_in_bytes)
    assert fused.memory_analysis().temp_size_in_bytes < 1 * 2**30
    text = fused.as_text()
    assert "delta_rule_fwd" in text and "delta_rule_bwd" in text
    for compiled in (fused, chunked):
        _, by_instruction = scopes.instruction_scopes(compiled.as_text())
        named = set(by_instruction.values())
        assert scopes.DELTA_RULE in named and scopes.LINEAR_ATTENTION not in named
    by_name = scopes.instruction_scopes(text)[1]
    kernels = [n for n, line in _instruction_lines(text).items() if "tpu_custom_call" in line]
    assert len(kernels) >= 2 and all(by_name[n] == scopes.DELTA_RULE for n in kernels)


def _instruction_lines(text):
    """``{instruction name: its line}`` of an HLO module's text."""
    lines = {}
    for line in text.splitlines():
        head = line.strip().removeprefix("ROOT ").split(" = ", 1)
        if len(head) == 2 and head[0].startswith("%"):
            lines[head[0].lstrip("%")] = line
    return lines


def test_expert_layer_gradient_is_a_grouped_matmul_on_a_v5e(one_chip):
    import jax.numpy as jnp

    from dynamic_load_balance_distributeddnn_tpu.ops import moe

    n, d, f, experts, held, k = 8192, 2048, 1024, 128, 8, 8

    def loss(m, w_r, w_gate, w_up, w_down):
        chosen, weights = moe.route(m, w_r, jnp.zeros((experts,)), k, True, 2.826)
        out, arrivals = moe.expert_ffn(m, chosen, weights, 0, w_gate, w_up, w_down, experts)
        return jnp.sum(out.astype(jnp.float32)), arrivals

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((n, d)), sds((d, experts), jnp.float32), sds((held, d, f)), sds((held, d, f)),
            sds((held, f, d)))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(*args).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    # one chunk of 8,192 rows at a time, not all 65,536 pairs' rows
    assert moe.chunk_rows(n * k, held, experts) == 8192
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * 2**30
