"""The harness end to end on the CPU (``--rehearsal``: ResNet-18 in float32
at batch 8), one run per cell; what it must refuse; and runs with the timed
path broken underneath, each of which has to come out as not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, run  # noqa: E402

CELLS = [w["name"] for w in harness.load_manifest()["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(capsys, workload, trace, seed=2**31 + 11):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace), "--rehearsal"])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    return rc, json.loads(lines[-1]), lines


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_contracts_line(capsys, workload):
    manifest = harness.load_manifest()
    rc, result, lines = _run(capsys, workload, trace=1)
    assert rc == 0
    keys = list(result)
    assert keys[:5] == RESULT_KEYS and keys[-1] == "compared"
    assert set(keys) <= set(RESULT_KEYS) | {"breakdown", "compared"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"  # said as it is, never a device number
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    wanted = {m["name"] for m in harness.cell_metrics(manifest, workload, "per_layer")}
    needs_chip = {"train_mfu_pct"}
    assert wanted - needs_chip <= set(result["metrics"]) <= wanted
    units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    assert all(v["unit"] == units[k] for k, v in result["metrics"].items())
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    assert all(row["value"] <= row["limit"] for row in result["compared"].values())
    setup = json.loads(next(ln for ln in lines if '"setup"' in ln))["setup"]
    assert setup["warm_epochs"] >= 3 and setup["input_path"]
    # the profiler runs over one more epoch, after the window has closed
    epochs = [json.loads(ln) for ln in lines if ln.startswith('{"epoch"')]
    assert [e["profiled"] for e in epochs] == [False] * (len(epochs) - 1) + [True]
    assert [e["epoch"]["index"] for e in epochs] == list(
        range(setup["warm_epochs"], setup["warm_epochs"] + len(epochs)))
    assert result["attempted"] == sum(e["epoch"]["steps"] for e in epochs)
    assert not os.path.isdir(os.path.join(ROOT, "benchmark", "out",
                                          f"{workload}.s{2**31 + 11}.t1"))


def test_untraced_line_reports_the_end_to_end_metrics(capsys):
    manifest = harness.load_manifest()
    rc, result, lines = _run(capsys, CELLS[0], trace=0, seed=7)
    wanted = {m["name"] for m in harness.cell_metrics(manifest, CELLS[0], "end_to_end")}
    assert rc == 0 and set(result["metrics"]) == wanted and "breakdown" not in result
    assert result["metrics"]["samples_per_s"]["value"] > 0
    assert "busy_s" not in result["device"]
    assert not any('"profiled": true' in ln for ln in lines)


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    done = _cli(ROOT)
    assert done.returncode != 0
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())
    assert "no TPU" in done.stderr


def test_alone_with_its_manifest_it_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    done = _cli(str(tmp_path), "--rehearsal")
    assert done.returncode != 0
    assert not any(ln.startswith("{") and '"correct"' in ln for ln in done.stdout.splitlines())


# ---------------------------------------------------------------- faults


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    from benchmark import sut

    real = sut.Job.run_epoch

    def run_epoch(self, epoch):
        before = self.trainer.state
        self.trainer.state = __import__("jax").tree_util.tree_map(lambda a: a + 0, before)
        out = real(self, epoch)
        self.trainer.state = before
        return out

    monkeypatch.setattr(sut.Job, "run_epoch", run_epoch)


def _half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from dynamic_load_balance_distributeddnn_tpu.train import engine

    real = engine.example_weights

    def example_weights(mask, **kw):
        w = real(mask, **kw)
        w[::2] = 0.0
        return w * 2.0

    monkeypatch.setattr(engine, "example_weights", example_weights)


# the faults a one-chip training cell can have (no exchange between chips, no
# token or answer): each planted in the program, under every cell
FAULTS = [(f.__name__.strip("_"), f, c) for c in CELLS for f in (_state_unchanged, _half_batch)]


@pytest.mark.parametrize("fault,plant,workload", FAULTS, ids=[f"{f[0]}-{f[2]}" for f in FAULTS])
def test_a_broken_timed_path_comes_out_as_not_correct(capsys, monkeypatch, fault, plant,
                                                      workload):
    plant(monkeypatch)
    rc, result, _ = _run(capsys, workload, trace=0, seed=99)
    assert rc == 0 and result["correct"] is False, (fault, result["compared"])
    over = [k for k, row in result["compared"].items()
            if row["value"] is None or not row["value"] <= row["limit"]]
    assert over, fault
