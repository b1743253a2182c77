"""Two cases that the files of ``tests/benchmark`` parametrise over every cell
of ``BENCHMARK.json`` and that cannot hold for a token cell as those files
stand (PERF.md section 7; a ``model_config`` PR edits none of them). Each is
expected to fail, strictly, so that the PR that repairs the harness has to
take its line out of here; ``test_bench_afmoe.py`` holds what each stands for."""

import pytest

CELL = "trinity_mini.ws4_even_dbs"
EXPECTED_TO_FAIL = {
    f"test_the_stated_precision_passes_the_cells_limits[{CELL}]":
        "the cell's limits were read on the chip at width 2,048; a leaf of the rehearsal model "
        "(width 64) holds a thousandth of the elements, so its norm averages away a thirtieth "
        "of the rounding: the harness has no limits of a rehearsal's own "
        "(test_bench_afmoe.py holds the rehearsal model to such limits)",
    f"test_a_broken_timed_path_comes_out_as_not_correct[half_batch-{CELL}]":
        "the planted fault is the images' (engine.example_weights), which a token job never "
        "calls: the harness does not choose the plant by the cell's task "
        "(test_bench_afmoe.py plants the token job's own half batch and lost clip in this cell)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = EXPECTED_TO_FAIL.get(item.name)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
