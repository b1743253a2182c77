"""``delta_rule_roofline_pct``: its operations-and-bytes count
(``benchmark/flops/delta_rule.py``) against a hand count at the cell's own
sizes, and the reader on a hand-made context."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.flops import delta_rule  # noqa: E402

NAME = "delta_rule_roofline_pct"
CELL = "qwen3_next.ws4_even_dbs"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3_next.json")) as f:
        return json.load(f)


def context(config, seconds, samples=16.0):
    return {"scope_table": {"seconds": seconds, "self_s": 3.0}, "profile": {},
            "profiled_epoch": {"samples": samples}, "peak": harness.peak_for("TPU v5 lite"),
            "model": config["model"], "config": config}


def test_one_pass_is_three_products_a_token_and_head_and_each_operand_once(config):
    model = config["model"]
    assert delta_rule.linear_layers(model) == 3  # published layers 0, 1, 2 of 0-3
    # 4,096 tokens x 32 value heads x 128 x 128, three products, 2 FLOP a multiply-add
    assert delta_rule.forward_operations_and_bytes(model, 2)[0] == 12_884_901_888
    # q and k at 16 key heads of 128 (16,777,216 B each in bfloat16), v and o at
    # 32 value heads of 128 (33,554,432 B each), g and beta 4,096 x 32 float32 each
    assert delta_rule.forward_operations_and_bytes(model, 2)[1] == (
        2 * 16_777_216 + 2 * 33_554_432 + 2 * 524_288) == 101_711_872
    assert delta_rule.forward_operations_and_bytes(model, 4)[1] == 202_375_168


def test_an_epoch_counts_trained_windows_three_times_over_and_validated_ones_once(config):
    operations, moved = delta_rule.epoch_operations_and_bytes(config["model"], 16, 10, 2)
    assert operations == 3 * (3 * 16 + 10) * 12_884_901_888 == 2_241_972_928_512
    assert moved == 3 * (2 * 16 * 101_711_872 + 10 * 202_375_168) == 15_835_594_752


def test_the_reader_takes_the_larger_bound_over_the_scopes_seconds(config, capsys):
    got = harness.read_layer_metric(NAME, context(config, {"delta_rule": 0.5, "forward": 2.5}))
    # bytes bound: 15.84 GB at 819 GB/s is 19.34 ms, the products at the peak 11.38 ms
    assert got == pytest.approx(100 * (15_835_594_752 / 819e9) / 0.5)
    assert got == pytest.approx(3.867, abs=1e-3)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["delta_rule_roofline"]
    assert said["bound"] == "bytes" and said["validated_windows"] == 10
    assert said["least_s_by_products"] == pytest.approx(2_241_972_928_512 / 197e12)
    # a scope that took the least time reads 100
    least = 15_835_594_752 / 819e9
    at_the_roofline = context(config, {"delta_rule": least})
    assert harness.read_layer_metric(NAME, at_the_roofline) == pytest.approx(100)


def test_the_reader_returns_nothing_where_there_is_nothing_to_read(config):
    # no such scope
    assert harness.read_layer_metric(NAME, context(config, {"forward": 2.5})) is None
    assert harness.read_layer_metric(NAME, dict(context(config, None), scope_table=None)) is None
    no_map = context(config, {"delta_rule": 0.5})
    no_map["scope_table"]["seconds"] = None  # a program that wrote no scope map
    assert harness.read_layer_metric(NAME, no_map) is None
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity_mini.json")) as f:
        other = json.load(f)
    assert harness.read_layer_metric(NAME, context(other, {"delta_rule": 0.5})) is None


def test_a_rehearsal_reckons_against_the_one_chip_of_the_table(config):
    """No peaks for the rehearsal's device: the count is still checked, against
    the table's one chip (the rehearsal test wants every listed metric)."""
    no_peak = dict(context(config, {"delta_rule": 0.5}), peak=None)
    assert harness.read_layer_metric(NAME, no_peak) == pytest.approx(3.867, abs=1e-3)


def test_the_manifest_lists_it_for_the_cell_alone():
    manifest = harness.load_cell(CELL)["manifest"]
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [{"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                      "layer": "step programs", "moves": "samples_per_s", "workloads": [CELL]}]
    assert manifest["per_layer"][-1]["name"] == NAME  # appended, nothing moved
