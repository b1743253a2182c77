"""chip_smoke.py away from the chip: it must refuse to run, and its phase
functions must do what they say at a size the CPU tier affords.

Also the placement rule of the persistent compile cache (compile_cache.py),
which the smoke, the CLI and this suite's conftest all share.
"""

import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from dynamic_load_balance_distributeddnn_tpu import compile_cache  # noqa: E402

# MnistNet-sized stand-in for the DenseNet recipe: same flags, same straggler,
# all four workers on device 0 as on a one-chip machine
SMALL = dict(model="mnistnet", dataset="mnist", batch=128, n_train=512,
             extra=("-gpu", "0"))


@pytest.mark.parametrize("args", [[], ["--chips", "4"]], ids=["one_chip", "four_chips"])
def test_script_refuses_to_run_without_a_tpu(args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize(
    "kind,shape",
    [(kind, shape) for _, kind, shape in chip_smoke.KERNEL_CASES],
    ids=[case_id for case_id, _, _ in chip_smoke.KERNEL_CASES],
)
def test_kernel_case_and_its_reference_have_one_shape(kind, shape):
    """What ``kernels_phase`` compares on the chip is comparable: the kernel
    and its XLA reference return the same shapes, the kernel in its operands'
    dtype and a loss in float32 (nothing runs; the compile is
    ``tests/test_chip_compile.py``'s)."""
    import jax
    import jax.numpy as jnp

    kernel_fn, reference_fn, specs, _ = chip_smoke.kernel_case(kind, shape)
    got = jax.eval_shape(kernel_fn, *specs)
    assert got.shape == jax.eval_shape(reference_fn, *specs).shape
    assert got.dtype == (jnp.float32 if kind == "xent" else specs[0].dtype)


def test_cnn_phase_trains_and_reports(tmp_path):
    report = chip_smoke.cnn_main_path(str(tmp_path), **SMALL)
    assert report["epochs"] == 3 and report["steps"] == 12
    assert report["aot_stats"]["failed"] == 0
    assert report["exec_path"] == ["packed"] * 3
    assert report["final_partition"][0] < 0.25  # DBS moved the 3x straggler
    assert report["realized_injection_profile"][0] > 2.0
    assert len(report["compile_s_per_epoch"]) == 3
    assert report["compile_s_per_epoch"][0] > 0 or report["compiles_per_epoch"][0] == 0


def test_done_sentinel_is_a_failure_not_a_silent_skip(tmp_path):
    from dynamic_load_balance_distributeddnn_tpu.config import config_from_args
    from dynamic_load_balance_distributeddnn_tpu.obs.logging import mark_run_done

    mark_run_done(config_from_args(chip_smoke.cnn_argv(str(tmp_path), **SMALL)))
    with pytest.raises(chip_smoke.SmokeFailure, match="trained nothing"):
        chip_smoke.cnn_main_path(str(tmp_path), **SMALL)


def test_fresh_dirs_never_collide(tmp_path):
    a = chip_smoke._fresh_dir(str(tmp_path), "cnn")
    b = chip_smoke._fresh_dir(str(tmp_path), "cnn")
    assert a != b and os.listdir(a) == [] and os.path.dirname(a) == str(tmp_path)


# ------------------------------------------------- compile-cache placement


def test_cache_dir_from_environment_is_used_verbatim(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "relative/../cache dir")
    assert compile_cache.compile_cache_dir() == "relative/../cache dir"
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append(k))
    assert compile_cache.enable_compile_cache() == "relative/../cache dir"
    assert "jax_compilation_cache_dir" not in seen  # JAX read the env itself


def test_cache_dir_default_is_absolute_and_independent_of_cwd(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert compile_cache.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")


def test_no_entry_point_makes_a_temporary_cache(monkeypatch):
    from dynamic_load_balance_distributeddnn_tpu.runtime.compile_worker import (
        ensure_persistent_cache,
    )

    monkeypatch.setattr(
        tempfile, "mkdtemp", lambda *a, **k: pytest.fail("temporary cache dir")
    )
    got = ensure_persistent_cache()
    assert got == compile_cache.compile_cache_dir()
    assert not got.startswith(tempfile.gettempdir() + os.sep)
