"""BENCHMARK.json against the benchmark's contract, and against the files the
harness finds by the names in it."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head", "expansion")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def _metrics(m):
    return m["end_to_end"] + m["per_layer"]


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16 and 1 <= len(manifest["command"]) <= 32
    n = 24  # later PRs add cells and may not change run_seconds: it has to fit with all 24
    assert (2 + 14 * n) * (manifest["run_seconds"] + 60) + n * 180 + 1200 <= 43200


def test_command_names_a_file_under_paths(manifest):
    files = [w for w in manifest["command"] if "/" in w]
    assert files and all(
        any(f.startswith(p + "/") for p in manifest["paths"]) and os.path.isfile(os.path.join(ROOT, f))
        for f in files)
    assert not any(w.startswith("/") or ".." in w for w in manifest["command"])


def test_every_name_and_unit_is_in_the_allowed_characters(manifest):
    names = [m["name"] for m in _metrics(manifest)]
    names += [c["name"] for c in manifest["configs"]] + [w["name"] for w in manifest["workloads"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert all(UNIT.match(m["unit"]) for m in _metrics(manifest))
    assert all(m["better"] in ("lower", "higher") and m["source"] in SOURCES
               for m in _metrics(manifest))
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in manifest[group]}) == len(manifest[group])
    assert len({m["name"] for m in _metrics(manifest)}) == len(_metrics(manifest))
    lines = [e["why"] for e in manifest["configs"] + manifest["workloads"]]
    lines += [m["layer"] for m in manifest["per_layer"]] + [c["source"] for c in manifest["configs"]]
    assert all(1 <= len(s) <= 200 and "\n" not in s and "\t" not in s for s in lines)


def test_entries_have_just_the_contracts_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_setup_s_is_an_end_to_end_metric_of_every_cell(manifest):
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0] and setup[0]["bound"] <= 0.1


def test_four_chip_quota(manifest):
    cells = manifest["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)


def test_each_layer_metric_moves_a_metric_all_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:  # setup_s, one more end-to-end metric, one per-layer metric
        assert len(harness.cell_metrics(manifest, cell, "end_to_end")) >= 2
        assert len(harness.cell_metrics(manifest, cell, "per_layer")) >= 1
    assert any("mfu" in m["name"] for m in manifest["per_layer"])
    assert not any(m["name"].endswith("_roofline") and m["unit"] != "%" for m in manifest["per_layer"])


# a window in which nothing ran, was recorded or was profiled
EMPTY = {"cell": {"chips": 1}, "traffic": {"dbs": True, "world_size": 4}, "epochs": [],
         "spans": [], "profile": None, "peak": None, "model": {},
         "window": {"t0": 0.0, "t1": 0.0, "wall_s": 0.0, "samples_per_s": 0.0, "compiles": 0},
         "setup": {"compile_s": 0.0}}
COUNTERS = {"compiles_in_window", "setup_compile_s"}  # nought is a reading of theirs


@pytest.mark.parametrize("name", [m["name"] for m in harness.load_manifest()["per_layer"]])
def test_layer_metric_reader_finds_nothing_in_an_empty_window(name):
    """Every per-layer metric has its reader file, and a reader with nothing
    to read returns nothing (never 0 for a share of a peak)."""
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    value = harness.read_layer_metric(name, EMPTY)
    assert value == 0.0 if name in COUNTERS else value is None


def _configs():
    return [c["name"] for c in harness.load_manifest()["configs"]]


def _cells():
    return [w["name"] for w in harness.load_manifest()["workloads"]]


@pytest.mark.parametrize("name", _configs())
def test_configuration_file(manifest, name):
    entry = {c["name"]: c for c in manifest["configs"]}[name]
    assert any(entry["file"].startswith(p + "/") for p in manifest["paths"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == name and config["reduced"] == entry["reduced"]
    assert not any(w in k for k in entry["reduced"] for w in WIDTH_WORDS)
    assert not any(k.endswith(("_dim", "_rank")) for k in entry["reduced"])
    assert any(c["config"] == name for c in manifest["workloads"])  # used by some cell
    for key in ("model", "argv", "rehearsal_argv", "rehearsal_model", "assumed", "source"):
        assert key in config, key
    from benchmark import tasks

    sizes = tasks.load(config).job_sizes(config["argv"])
    assert sizes["n_train"] == config["n_train"]  # the key `reduced` names is the one that runs
    assert set(entry["reduced"]) <= set(config) and set(entry["reduced"]) == set(config["reduced_why"])
    assert sizes["batch"] % (4 * sizes["bucket"]) == 0 and sizes["n_train"] % sizes["batch"] == 0
    from benchmark import flops
    from benchmark.reference import common

    assert flops.train_flops_per_sample(config["model"]) > 0
    assert hasattr(common.family(config["model"]), "forward")


@pytest.mark.parametrize("name", _cells())
def test_cell_files(manifest, name):
    spec = harness.load_cell(name, manifest)
    assert spec["traffic"]["name"] == spec["cell"]["traffic"]
    assert spec["traffic"]["one_chip"] == (spec["cell"]["chips"] == 1)
    warm = spec["traffic"]["warmup"]
    assert 3 <= warm["min_epochs"] <= warm["max_epochs"]
    limits = spec["limits"]
    assert {"update_gap", "moment_gap", "update_gap_median", "moment_gap_median"} <= set(limits)
    assert limits["plan_sum_err"] == 0 and limits["steps_err"] == 0
    argv = harness.job_argv(spec["config"], spec["traffic"], rehearsal=False)
    from dynamic_load_balance_distributeddnn_tpu.config import config_from_args

    cfg = config_from_args(argv + ["--seed", "5"])  # the program accepts the job as written
    assert cfg.world_size == spec["traffic"]["world_size"]
    assert cfg.dynamic_batch_size == spec["traffic"]["dbs"] and not cfg.one_cycle_policy


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no.such_cell")


FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixture")


def test_a_manifest_of_its_own_brings_its_own_data_files_first(manifest):
    """The fixture language-model cells are in no cell of ``BENCHMARK.json``:
    their manifest names them, and its directory's ``traffic/`` and ``limits/``
    are looked in before ``benchmark/``'s (readers fall through to it)."""
    path = os.path.join(FIXTURE, "manifest.json")
    assert harness.data_roots() == [harness.HERE]
    assert harness.data_roots(path) == [FIXTURE, harness.HERE]
    own = harness.load_manifest(path)
    assert not {w["name"] for w in own["workloads"]} & {w["name"] for w in manifest["workloads"]}
    assert not {c["name"] for c in own["configs"]} & {c["name"] for c in manifest["configs"]}
    for cell in own["workloads"]:
        spec = harness.load_cell(cell["name"], manifest_path=path)
        assert spec["config"]["task"] == "tokens" and spec["traffic"]["world_size"] == 4
        assert spec["limits"]["plan_sum_err"] == 0 and spec["roots"][0] == FIXTURE
        config = spec["config"]
        from benchmark import flops, tasks
        from benchmark.reference import common
        from dynamic_load_balance_distributeddnn_tpu.config import config_from_args

        argv = harness.job_argv(config, spec["traffic"], rehearsal=False)
        sizes = tasks.load(config).job_sizes(argv)
        cfg = config_from_args(argv + ["--seed", "5"])  # the program accepts the job as written
        assert cfg.model == "transformer" and cfg.bptt == sizes["bptt"] == config["model"]["seq_len"]
        assert sizes["n_train"] == config["n_train"] and cfg.batch_size == sizes["batch"]
        assert flops.train_flops_per_sample(config["model"]) > 0
        assert hasattr(common.family(config["model"]), "forward")
    for m in own["per_layer"]:  # every reader it names is found, in benchmark/layer_metrics
        assert os.path.isfile(harness.find_file(spec["roots"], "layer_metrics", m["name"] + ".py"))
    with pytest.raises(SystemExit):  # and the repository's manifest does not know the cell
        harness.load_cell(own["workloads"][0]["name"])
