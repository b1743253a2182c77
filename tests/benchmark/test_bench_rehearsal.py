"""The harness end to end on the CPU (``--rehearsal``: ResNet-18 in float32
at batch 8), one run per cell, and one of the fixture language-model cell
(``tests/benchmark/fixture``: the repo's own 2-layer LM through the ``tokens``
task, found by its own manifest and in no cell of ``BENCHMARK.json``); what
the harness must refuse; and runs with the timed path broken underneath, each
of which has to come out as not correct.

The fixture runs with ``LMTrainer.DROPOUT`` set to 0.0 by monkeypatch (a class
constant of 0.2 in the program): a plain reference cannot follow its masks."""

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
LM_MANIFEST = os.path.join(ROOT, "tests", "benchmark", "fixture", "manifest.json")
LM_CELL = "transformer_wikitext2.ws4_even_dbs"
# every cell of BENCHMARK.json, and the fixture cell with the manifest that names it
RUNS = [(c, None) for c in CELLS] + [(LM_CELL, LM_MANIFEST)]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(autouse=True)
def _no_dropout(monkeypatch):
    from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

    monkeypatch.setattr(LMTrainer, "DROPOUT", 0.0)


def _run(capsys, workload, trace, seed=2**31 + 11, manifest=None):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace), "--rehearsal"]
                  + (["--manifest", manifest] if manifest else []))
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    return rc, json.loads(lines[-1]), lines


@pytest.mark.parametrize("workload,manifest_path", RUNS, ids=[r[0] for r in RUNS])
def test_rehearsal_prints_the_contracts_line(capsys, workload, manifest_path):
    manifest = harness.load_manifest(manifest_path or harness.MANIFEST)
    rc, result, lines = _run(capsys, workload, trace=1, manifest=manifest_path)
    assert rc == 0
    keys = list(result)
    assert keys[:5] == RESULT_KEYS and keys[-1] == "compared"
    assert set(keys) <= set(RESULT_KEYS) | {"breakdown", "compared"}
    assert result["correct"] is True and result["failed"] == 0, (result["failed"],
                                                                 result["compared"])
    assert result["attempted"] > 0
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
    if manifest_path:  # the token job: its own trainer, path and unit of work
        assert {e["epoch"]["exec_path"] for e in epochs} == {"elastic:scan"}
        assert setup["plans"][0] == [2, 2, 2, 2]  # epoch 0, the one compared, is even
        # a sample is one window of --bptt target tokens in one column: 8 columns
        # x 90 targets / 35 in an epoch under the even plan (the balancer is on,
        # and on a loaded CPU its probes' noise may move two-column workers)
        even = [e["epoch"] for e in epochs if e["epoch"]["batches"] == [2, 2, 2, 2]]
        assert all(e["samples"] == pytest.approx(720 / 35) and e["steps"] == 3 for e in even)
        for e in epochs:  # any plan: fewer targets than the stream has tokens, at most -b columns
            assert 0 < e["epoch"]["samples"] < 728 / 35 and sum(e["epoch"]["batches"]) <= 8, e


@pytest.mark.parametrize("workload,manifest_path", RUNS, ids=[r[0] for r in RUNS])
def test_untraced_line_reports_the_end_to_end_metrics(capsys, workload, manifest_path):
    manifest = harness.load_manifest(manifest_path or harness.MANIFEST)
    rc, result, lines = _run(capsys, workload, trace=0, seed=7, manifest=manifest_path)
    wanted = {m["name"] for m in harness.cell_metrics(manifest, workload, "end_to_end")}
    assert rc == 0 and set(result["metrics"]) == wanted and "breakdown" not in result
    assert result["correct"] is True and result["failed"] == 0, (result["failed"],
                                                                 result["compared"])
    assert list(result)[:5] == RESULT_KEYS and list(result)[-1] == "compared"
    assert result["metrics"]["samples_per_s"]["value"] > 0
    assert "busy_s" not in result["device"]
    assert not any('"profiled": true' in ln for ln in lines)
    if manifest_path:
        window = [json.loads(ln) for ln in lines if ln.startswith('{"epoch"')]
        spent = sum(e["seconds"] for e in window)
        # samples/s = target tokens / bptt over the window's wall
        assert result["metrics"]["samples_per_s"]["value"] == pytest.approx(
            sum(e["epoch"]["samples"] for e in window) / spent, rel=0.2)


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


def _half_columns(monkeypatch):
    """The token job's half batch: every second column of every worker left
    out, the mean taken over the rest."""
    from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

    real = LMTrainer._build_windows

    def build_windows(self, plan, rank, pad_to):
        x, y, w = real(self, plan, rank, pad_to)
        w = w.copy()
        w[:, 1::2, :] = 0.0
        return x, y, w * 2.0

    monkeypatch.setattr(LMTrainer, "_build_windows", build_windows)


def _no_clip(monkeypatch):
    """The per-worker clip left out: the workers' gradients summed as they are."""
    from dynamic_load_balance_distributeddnn_tpu.train.steps import StepLibrary

    monkeypatch.setattr(StepLibrary, "_clip_local", lambda self, grads, w: grads)


# the faults a one-chip training cell can have (no exchange between chips, no
# token or answer): each planted in the program, under every cell; the token
# job has its own half batch (columns) and its per-worker clip to lose
FAULTS = [(f.__name__.strip("_"), f, c, None) for c in CELLS
          for f in (_state_unchanged, _half_batch)]
FAULTS += [(f.__name__.strip("_"), f, LM_CELL, LM_MANIFEST)
           for f in (_state_unchanged, _half_columns, _no_clip)]


@pytest.mark.parametrize("fault,plant,workload,manifest_path", FAULTS,
                         ids=[f"{f[0]}-{f[2]}" for f in FAULTS])
def test_a_broken_timed_path_comes_out_as_not_correct(capsys, monkeypatch, fault, plant,
                                                      workload, manifest_path):
    plant(monkeypatch)
    rc, result, _ = _run(capsys, workload, trace=0, seed=99, manifest=manifest_path)
    assert rc == 0 and result["correct"] is False, (fault, result["compared"])
    over = [k for k, row in result["compared"].items()
            if row["value"] is None or not row["value"] <= row["limit"]]
    assert over, fault
