"""The control and the planted faults, at a size a test run can hold: the
plain reference put in the program's place, computed in float8 or with a
fault a program could have, has to come out as not correct under every
cell's limits. (Their readings at the cells' own sizes on the chip are in
PERF.md; the benchmark's own runs never run them.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, harness  # noqa: E402

CELLS = harness.load_manifest()["workloads"]
LM_MANIFEST = os.path.join(ROOT, "tests", "benchmark", "fixture", "manifest.json")
LM_CELL = "transformer_wikitext2.ws4_even_dbs"
# the fixture language-model cell states float32, so its control is bfloat16
# (the nearest precision below); float8 has to fail it all the more
CONTROLS = [(c["name"], None, ["fp8", "half_batch", "state_unchanged"]) for c in CELLS]
CONTROLS += [(LM_CELL, LM_MANIFEST, ["bf16", "fp8", "half_batch", "state_unchanged", "no_clip"])]


@pytest.mark.parametrize("cell,manifest_path,variants", CONTROLS, ids=[c[0] for c in CONTROLS])
def test_control_and_faults_fail_the_cells_limits(cell, manifest_path, variants):
    out = control.readings(cell, seed=2**31 + 3, variants=variants, rehearsal=True,
                           manifest_path=manifest_path)
    for variant in variants:
        over = [k for k, row in out[variant]["compared"].items()
                if not row["value"] <= row["limit"]]
        assert out[variant]["correct"] is False and over, (variant, out[variant]["readings"])


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_the_stated_precision_passes_the_cells_limits(cell):
    """The other side of the control: the same equations with every product's
    inputs rounded to the bfloat16 the configurations state stay within the
    limits, on two seeds."""
    for seed in (2**31 + 4, 17):
        out = control.readings(cell["name"], seed=seed, variants=["bf16"], rehearsal=True)
        # the loss is a mean over 16 rows here and over an epoch's 24,576 on
        # the chip, where its limit was read: held there, not here
        over = [k for k, row in out["bf16"]["compared"].items()
                if k != "loss_gap" and not row["value"] <= row["limit"]]
        assert not over, out["bf16"]["compared"]


def test_the_reference_agrees_with_itself():
    """Two follows of one seed give the same numbers to the last digit, so
    every gap a run reads is the program's."""
    cell = CELLS[0]["name"]
    spec = harness.load_cell(cell)
    model = spec["config"]["rehearsal_model"]
    import jax

    from benchmark.reference import common

    from benchmark.tasks import images

    sizes = images.job_sizes(harness.job_argv(spec["config"], spec["traffic"], True))
    rows = images.make_rows(9, sizes, 1, model)
    params0 = jax.device_get(harness.make_weights(common.family(model).param_shapes(model), None, 9))
    job = {"n_train": sizes["n_train"], "world_size": 4, "batch": sizes["batch"], "seed": 9,
           "epoch": 0, "lr": 0.01, "dataset": "cifar10"}
    a = images.train_epoch(params0, rows, model, job)
    b = images.train_epoch(params0, rows, model, job)
    got = common.compare(a, b, params0)
    assert got["loss_gap"] == 0 and got["update_gap"] == 0 and got["moment_gap"] == 0
    assert got["leaves_left_out"] == 0


def test_the_token_reference_agrees_with_itself_in_any_blocks_of_columns():
    """Two follows of one seed give the same numbers, and a worker's gradient
    taken one column at a time is the gradient taken at once (to float32
    rounding): blocks are a matter of memory, not of the result."""
    import jax

    from benchmark import tasks
    from benchmark.reference import common

    spec = harness.load_cell(LM_CELL, manifest_path=LM_MANIFEST)
    config, model = spec["config"], spec["config"]["rehearsal_model"]
    task = tasks.load(config)
    sizes = task.job_sizes(harness.job_argv(config, spec["traffic"], True))
    rows = task.make_rows(9, sizes, 1, model)
    params0 = jax.device_get(harness.make_weights(common.family(model).param_shapes(model), None,
                                                  9, common.init_std(model)))
    job = harness.job_definition(config, spec["traffic"], sizes, 9, task)
    a = task.train_epoch(params0, rows, model, job)
    b = task.train_epoch(params0, rows, model, job)
    got = common.compare(a, b, params0)
    assert got["loss_gap"] == 0 and got["update_gap"] == 0 and got["moment_gap"] == 0
    assert got["leaves_left_out"] == 2  # each layer's key bias: nought under softmax
    one = common.compare(task.train_epoch(params0, rows, model, job, block_rows=1), a, params0)
    limits = {k: v for k, v in spec["limits"].items() if k in one}
    assert harness.decide(one, limits)["correct"], one
