"""bench.py's process contract: the parent stays off JAX, one ``--arms``
child measures, and without a chip nothing is measured and nothing printed."""

import ast
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench.py")


def _run(*argv, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_FORCE_CPU"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, BENCH, *argv],
        capture_output=True, text=True, timeout=180, env=env, cwd=ROOT,
    )


def test_bench_without_a_chip_exits_nonzero_and_prints_no_result():
    proc = _run()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line, stored or otherwise
    assert "no TPU" in proc.stderr


def test_arms_child_refuses_cpu_unless_asked(tmp_path):
    out = tmp_path / "arms.json"
    proc = _run("--arms", "--out", str(out))
    assert proc.returncode == 2
    assert not out.exists() or out.read_text() == ""


def test_parent_never_imports_jax_and_survival_layer_is_gone():
    """The parent's code path (module level + main's tail) must not import
    jax; the retry/salvage/cached-result machinery must not come back."""
    src = open(BENCH).read()
    tree = ast.parse(src)
    top_imports = {
        n.name for node in tree.body if isinstance(node, ast.Import) for n in node.names
    } | {node.module for node in tree.body if isinstance(node, ast.ImportFrom)}
    assert not any(m and m.split(".")[0] == "jax" for m in top_imports)
    defined = {n.name for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)}
    gone = {
        "run_preflight", "_install_init_watchdog", "_wait_healthy", "_try_arms",
        "_preflight_seed", "_cached_tpu_result", "_publish", "_resume_compatible",
    }
    assert not (defined & gone)
    main_src = ast.get_source_segment(
        src, next(n for n in tree.body if getattr(n, "name", "") == "main")
    )
    assert "import jax" not in main_src


def test_result_names_the_device():
    sys.path.insert(0, ROOT)
    import bench

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    partial = {
        "backend": "tpu", "device": device, "model": "densenet", "n_train": 2560,
        "world_size": 4, "straggler_factors": [3.0, 1.0, 1.0, 1.0],
        "off": [9.0, 5.0, 5.1, 5.0], "on": [9.0, 5.0, 4.0, 4.1, 4.0],
        "instr": {"off_injection_calibrated": True, "on_injection_calibrated": True},
    }
    res = bench._result_from(partial)
    assert res["device"] == device
    assert res["vs_baseline"] > 1.0
    json.dumps(res)
