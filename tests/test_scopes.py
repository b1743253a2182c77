"""Device scopes (obs/scopes.py): the rule from ``op_name`` to scope, the
parser of a compiled program's text, the scopes each step family carries when
lowered, and the map a traced run writes."""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu.config import Config
from dynamic_load_balance_distributeddnn_tpu.data.datasets import synthetic_dataset
from dynamic_load_balance_distributeddnn_tpu.models import build_model
from dynamic_load_balance_distributeddnn_tpu.obs import scopes
from dynamic_load_balance_distributeddnn_tpu.obs.trace import configure
from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
    batch_sharding,
    data_mesh,
    replicated_sharding,
    tree_mesh,
)
from dynamic_load_balance_distributeddnn_tpu.train import Trainer
from dynamic_load_balance_distributeddnn_tpu.train.state import (
    attach_comm_residual,
    create_state,
    make_optimizer,
    shard_optimizer_state,
    zero1_padded_size,
)
from dynamic_load_balance_distributeddnn_tpu.train.steps import StepLibrary


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jvp(forward)/dot_general", "forward"),
    ("jit(f)/transpose(jvp(forward))/mul", "backward"),
    ("jit(f)/while/body/closed_call/transpose(jvp(forward))/checkpoint/forward/add", "backward"),
    ("jit(fused_epoch_idx)/while/body/closed_call/augment/vmap()/gather", "augment"),
    ("jit(f)/augment/jit(_bernoulli)/jit(_uniform)/add", "augment"),
    ("jit(f)/update/combine/all_gather", "combine"),
    ("jit(f)/update/sub", "update"),
    ("jit(update)/sub", ""),            # a jitted function's own name is no scope
    ("jit(f)/forwarder/add", ""),       # whole components only
    ("jit(f)/while/body/add", ""),
    ("jit(f)/vmap(eval)/argmax", "eval"),
    ("jit(f)/clip/div", "clip"),
    ("jit(f)/while/body/inject/dot_general", "inject"),
    ("", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8])->f32[8]}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/augment/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p), metadata={op_name="jit(step)/jvp(forward)/add"}
}

%fused_computation.1 (p.1: f32[8]) -> (f32[8], f32[8]) {
  %p.1 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%p.1), metadata={op_name="jit(step)/update/neg"}
  ROOT %tuple.9 = (f32[8]{0}, f32[8]{0}) tuple(%neg.1, %p.1)
}

%wide.body (w: (s32[], f32[8])) -> (s32[], f32[8]) {
  %w = (s32[], f32[8]{0}) parameter(0)
  %dynamic-update-slice.3 = f32[8]{0} dynamic-update-slice(%w, %w)
  %fusion.7 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%w, %dynamic-update-slice.3)
}

%wide.cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] compare(%c, %c), direction=LT
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.2 = (s32[], f32[8]{0}) while(%x), condition=%wide.cond, body=%wide.body, metadata={op_name="jit(step)/augment/vmap()/gather"}
  %fusion.8 = (f32[8]{0}, f32[8]{0}) fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/update/neg"}
  %copy.4 = f32[8]{0} copy(%x)
  ROOT %fusion.9 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/augment/mul"}
}
"""


def test_instruction_scopes_of_a_compiled_text():
    module, got = scopes.instruction_scopes(HLO)
    assert module == "jit_step"
    # a fusion takes its root's scope, not its own op_name's
    assert got["fusion.9"] == "forward"
    # a root with no op_name (a multi-output fusion's tuple) falls back on the fusion's own
    assert got["fusion.8"] == "update"
    # XLA's own loop carries no metadata: it inherits from the while that calls it
    assert got["dynamic-update-slice.3"] == "augment" and got["lt"] == "augment"
    assert got["fusion.7"] == "forward"  # its own root still wins inside the loop
    assert got["while.2"] == "augment" and got["copy.4"] == "" and got["x"] == ""
    # what lives inside a fused computation has no event of its own
    assert "mul.1" not in got and "neg.1" not in got


def test_the_cache_key_takes_op_names_and_no_file_or_line():
    """An executable compiled before a scope moved must not be served after:
    the key takes the metadata, which is cut down to the op names."""
    from jax._src import cache_key

    from dynamic_load_balance_distributeddnn_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    def step(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.tanh(x) * 2
        return jax.jit(f).lower(jnp.ones((4,)))

    text = step("forward").as_text(debug_info=True)
    assert '"jit(f)/forward/tanh"' in text and ".py" not in text

    def digest(lowered):
        h = hashlib.sha256()
        cache_key._hash_computation(h, lowered.compiler_ir("stablehlo"), cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()

    assert digest(step("forward")) == digest(step("forward"))
    assert digest(step("forward")) != digest(step("update"))


# ------------------------------------------------- the step families, lowered


def _library(mesh, **kw):
    spec = build_model("mnistnet", num_classes=10)
    tx = make_optimizer(0.05, 0.9)
    state = create_state(spec.module, jnp.zeros((1, 28, 28, 1), jnp.float32), tx, seed=3,
                         sharding=replicated_sharding(mesh))
    n = len(mesh.devices.flat)
    if kw.get("shard_update"):
        kw["zero1_padded"] = zero1_padded_size(state.params, n)
        state = shard_optimizer_state(state, mesh, tx)
    if kw.get("grad_comm") == "hier":
        state = attach_comm_residual(state, mesh, pad_multiple=n if kw.get("shard_update") else 0)
    lib = StepLibrary(spec, mesh, tx, mean=np.array([0.3]), std=np.array([0.3]), **kw)
    return lib, state


def _lowered_scopes(lowered):
    names = re.findall(r'"([^"\n]*/[^"\n]*)"', lowered.as_text(debug_info=True))
    return {scopes.scope_of(n) for n in names} - {""}


def _fused_args(mesh, state, b=8):
    n = len(mesh.devices.flat)
    bx = tuple(mesh.axis_names) if len(mesh.axis_names) > 1 else mesh.axis_names[0]
    put = lambda a: jax.device_put(a, batch_sharding(mesh, a.ndim, axis=bx))  # noqa: E731
    return (state, put(np.zeros((n * b, 28, 28, 1), np.uint8)), put(np.zeros((n * b,), np.int32)),
            put(np.full((n * b,), 1.0 / (n * b), np.float32)), put(np.zeros((n,), np.int32)),
            jnp.int32(0))


TRAIN = {"augment", "forward", "backward", "inject"}


def test_worker_step_carries_its_scopes():
    lib, state = _library(data_mesh(jax.devices()[:1]), grad_clip=1.0)
    args = (state.params, np.zeros((8, 28, 28, 1), np.uint8), np.zeros((8,), np.int32),
            np.full((8,), 0.125, np.float32), jax.random.PRNGKey(0), jnp.int32(0))
    assert _lowered_scopes(lib.worker_step_first.lower(*args)) == TRAIN | {"clip"}
    stacked = jax.tree_util.tree_map(lambda p: p[None], state.params)
    assert _lowered_scopes(lib.combine_update.lower(state, stacked)) == {"combine", "update"}


@pytest.mark.parametrize("kw,mesh_of", [
    ({}, lambda d: data_mesh(d[:4])),
    ({"shard_update": True}, lambda d: data_mesh(d[:4])),
    ({"grad_comm": "hier", "grad_comm_wire": "fp32"},
     lambda d: tree_mesh(d[:4], ("host", "device"), (2, 2))),
], ids=["flat", "shard_update", "hier"])
def test_fused_step_carries_its_scopes(kw, mesh_of):
    mesh = mesh_of(jax.devices())
    lib, state = _library(mesh, **kw)
    assert _lowered_scopes(lib.fused_step.lower(*_fused_args(mesh, state))) == \
        TRAIN | {"combine", "update"}


def test_fused_epoch_idx_and_eval_carry_their_scopes():
    mesh = data_mesh(jax.devices()[:4])
    lib, state = _library(mesh)
    rep = replicated_sharding(mesh)
    win = lambda a: jax.device_put(a, batch_sharding(mesh, a.ndim, axis="data", axis_dim=1))  # noqa: E731
    args = (state, jax.device_put(np.zeros((64, 28, 28, 1), np.uint8), rep),
            jax.device_put(np.zeros((64,), np.int32), rep), win(np.zeros((2, 32), np.int32)),
            win(np.full((2, 32), 1 / 32, np.float32)),
            jax.device_put(np.zeros((4,), np.int32), batch_sharding(mesh, 1, axis="data")),
            jnp.int32(0))
    assert _lowered_scopes(lib.fused_epoch_idx.lower(*args)) == TRAIN | {"combine", "update"}
    _, x, y, w, _, _ = _fused_args(mesh, state)
    assert _lowered_scopes(lib.fused_eval_step.lower(state.params, x, y, w)) == {"eval"}


def test_no_two_programs_of_a_library_share_a_module_name():
    lib, _ = _library(tree_mesh(jax.devices()[:4], ("host", "device"), (2, 2)),
                      grad_comm="hier", grad_comm_wire="fp32")
    programs = dict(lib.aot_lowerables(), fused_step=lib.fused_step,
                    fused_step_probe=lib.fused_step_probe, fused_step_nocomm=lib.fused_step_nocomm,
                    comm_probe=lib.comm_probe, fused_eval_step=lib.fused_eval_step)
    names = [fn.__name__ for fn in programs.values()]
    assert len(set(names)) == len(names), sorted(names)


# --------------------------------------------------------- the map of a run


def test_a_traced_run_writes_the_map_of_its_programs(tmp_path):
    cfg = Config(debug=True, world_size=4, batch_size=128, learning_rate=0.05, epoch_size=1,
                 dataset="mnist", model="mnistnet", dynamic_batch_size=True, seed=7, bucket=8,
                 device=0, trace="on", trace_dir=str(tmp_path / "traces"),
                 stat_dir=str(tmp_path / "statis"))
    try:
        tr = Trainer(cfg, bundle=synthetic_dataset("mnist", n_train=512, n_test=128),
                     log_to_file=False)
        tr.run_epoch(0)
        with open(tmp_path / "traces" / scopes.MAP_FILE) as f:
            rows = [json.loads(line) for line in f]
        held = {repr(k): tr._aot.get(k) for k in tr._aot.keys()}
        assert {r["key"] for r in rows} >= set(held)  # every program of the registry
        assert any(r["module"] == "jit_fused_eval_step" for r in rows)  # and a lazy one
        found = set()
        for row in rows:
            found |= set(row["scopes"].values())
            compiled = held.get(row["key"])
            if compiled is None:
                continue
            text = compiled.as_text()
            assert text.startswith("HloModule " + row["module"] + ",")
            named = set(re.findall(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ", text, re.M))
            assert set(row["scopes"]) <= named and row["scopes"]
        assert {"forward", "backward", "update", "eval"} <= found
        tr._aot.close(False)
    finally:
        configure("off")
    assert not os.path.exists(tmp_path / "statis" / scopes.MAP_FILE)
