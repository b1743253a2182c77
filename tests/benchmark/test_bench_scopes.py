"""``benchmark/scope_reduce.py`` on hand-made events and a hand-made scope
map, and each per-layer reader that rests on it, or on the program's wait,
input and compile spans, on a hand-made context."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, scope_reduce  # noqa: E402

# one device line: a scan's `while` holds its body's operations, the probe's
# program follows, then a program that left no map
S = 10**9  # the profiler's clock is whole nanoseconds, and the arithmetic is done in them
LINE = [(module, name, start * S, dur * S) for module, name, start, dur in [
    ("jit_epoch", "copy.1", 0.0, 1.0),
    ("jit_epoch", "while.3", 1.0, 8.0),
    ("jit_epoch", "dynamic-update-slice.3", 1.0, 3.0),   # augment, via the loop it sits in
    ("jit_epoch", "fusion.7", 4.0, 2.0),                 # forward: starts where the last ends
    ("jit_epoch", "fusion.9", 6.0, 2.5),                 # backward
    ("jit_epoch", "fusion.11", 8.5, 0.25),               # update
    ("jit_probe", "fusion.7", 10.0, 1.0),                # the same name in another program
    ("jit_other", "fusion.1", 12.0, 0.75),               # not in the map at all
    ("jit_epoch", "fusion.404", 13.0, 0.5),              # an instruction the map lacks
]]
MAP = {
    "jit_epoch": {"copy.1": "", "while.3": "", "dynamic-update-slice.3": "augment",
                  "fusion.7": "forward", "fusion.9": "backward", "fusion.11": "update"},
    "jit_probe": {"fusion.7": "backward"},
}


def test_seconds_by_scope_add_up_to_the_busy_time():
    by_scope, by_program, ambiguous = scope_reduce.seconds_by_scope([LINE], MAP)
    assert by_scope == {"augment": 3.0, "forward": 2.0, "backward": 3.5, "update": 0.25,
                        # the copy, the while's own quarter second, the two unmapped
                        "unscoped": 1.0 + 0.25 + 0.75 + 0.5}
    assert sum(by_scope.values()) == pytest.approx(11.25)  # the while is not counted twice
    assert by_program == {"jit_epoch": 9.5, "jit_probe": 1.0, "jit_other": 0.75}
    assert ambiguous == 0.0


def test_reduce_run_shares_sum_to_100_and_steady_idle_leaves_the_probes_out():
    devices = {"/device:TPU:0": [LINE], "/device:TPU:1": [[("jit_epoch", "copy.1", 0, S // 2)]]}
    host = [("probe", 9.5, 2.0), ("train", 0.0, 13.5), ("sync_probe", 11.0, 1.0)]
    t = scope_reduce.reduce_run(devices, host, MAP)
    assert t["device"] == "/device:TPU:0" and t["busy_s"] == pytest.approx(11.25)
    assert 100.0 * sum(t["seconds"].values()) / t["self_s"] == pytest.approx(100.0)
    assert t["unmapped_programs"] == ["jit_other"]
    # idle gaps: 9-10, 11-12, 12.75-13; the probes' union 9.5-12 takes out 0.5 + 1.0
    assert t["window_s"] == pytest.approx(13.5)
    assert t["steady"] == {"idle_s": pytest.approx(0.75), "steady_s": pytest.approx(11.0),
                           "probe_s": pytest.approx(2.5)}
    # no map: the idle share still reads, the scope shares do not
    bare = scope_reduce.reduce_run(devices, host, {})
    assert bare["seconds"] is None and bare["steady"] == t["steady"]
    assert scope_reduce.reduce_run({"/device:TPU:0": [[]]}, host, MAP) is None


def test_load_map_keeps_the_last_line_of_a_key_and_marks_a_clash(tmp_path):
    rows = [
        {"module": "jit_step", "key": "('a', 1)", "scopes": {"fusion.1": "forward", "copy.2": ""}},
        {"module": "jit_step", "key": "('a', 1)", "scopes": {"fusion.1": "backward", "copy.2": ""}},
        {"module": "jit_step", "key": "('a', 2)", "scopes": {"fusion.1": "backward",
                                                               "copy.2": "update", "fusion.3": "eval"}},
    ]
    path = tmp_path / scope_reduce.MAP_FILE
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    got = scope_reduce.load_map(str(path))
    # two rungs of one program share a module's name: what they agree on is
    # kept, what they do not is unknown (and then counts as unscoped)
    assert got == {"jit_step": {"fusion.1": "backward", "copy.2": None, "fusion.3": "eval"}}
    by_scope, _, ambiguous = scope_reduce.seconds_by_scope(
        [[("jit_step", "copy.2", 0, 2 * S), ("jit_step", "fusion.3", 2 * S, S)]], got)
    assert by_scope == {"unscoped": 2.0, "eval": 1.0} and ambiguous == 2.0


def test_instruction_name_of_an_event():
    text = "%fusion.8 = bf16[4096,32,32,64]{0,2,3,1:T(8,128)(2,1)} fusion(bf16[1] %p), kind=kLoop"
    assert scope_reduce.instruction_name(text) == "fusion.8"
    assert scope_reduce.instruction_name("broadcast_add_fusion") == "broadcast_add_fusion"


# ------------------------------------------------------------- the readers

T0 = 100.0  # the window: two epochs of 10 s on the program's clock
SPANS = [
    # set-up: tracing and lowering on two threads, overlapping
    ("jax_trace", "compile", 10.0, 4.0), ("jax_trace", "compile", 11.0, 1.0),
    ("aot_lower", "compile", 12.0, 6.0), ("jax_lower", "compile", 30.0, 2.0),
    ("backend_compile", "compile", 32.0, 9.0),
    # epoch 0: a plain one
    ("epoch", "epoch", 100.0, 10.0), ("input_wait", "transfer", 100.5, 0.004),
    ("device_wait", "wait", 101.0, 8.0), ("device_wait", "wait", 109.5, 0.25),
    # epoch 1: a probe epoch with a stall on the host
    ("epoch", "epoch", 110.0, 10.0), ("input_wait", "transfer", 110.5, 0.008),
    ("device_wait", "wait", 111.0, 5.0), ("probe", "probe", 116.0, 2.0),
    ("sync_probe", "probe", 117.5, 1.0), ("jax_trace", "compile", 119.0, 0.5),
]


def _ctx(**over):
    ctx = {"cell": {"name": "no.such_cell", "chips": 1}, "epochs": [{}, {}], "spans": SPANS,
           "profile": None, "window": {"t0": T0, "t1": 120.0, "wall_s": 20.0},
           "scope_table": {"seconds": {"augment": 3.0, "forward": 2.0, "backward": 3.5,
                                       "update": 0.25, "clip": 0.25, "eval": 0.5,
                                       "unscoped": 0.5},
                           "self_s": 10.0,
                           "steady": {"idle_s": 0.25, "steady_s": 12.5, "probe_s": 2.0}}}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name,value", [
    ("augment_device_pct", 30.0),
    ("forward_device_pct", 20.0),
    ("backward_device_pct", 35.0),
    ("update_device_pct", 5.0),          # update and clip together
    ("unscoped_device_pct", 5.0),
    ("device_idle_steady_pct", 2.0),
    # waits: 8 + 0.25 in epoch 0; 5 and the probes' union 116-118.5 in epoch 1
    ("device_wait_pct", 100.0 * (8.25 + 7.5) / 20.0),
    ("host_ms_epoch_max", 2500.0),       # epoch 1 held 10 s and waited 7.5
    ("input_wait_ms_per_epoch", 6.0),
    # before the window: 10-18 (trace and the AOT lowering overlap) and 30-32
    ("setup_trace_lower_s", 10.0),
])
def test_reader_on_a_hand_made_context(name, value):
    assert harness.read_layer_metric(name, _ctx()) == pytest.approx(value)


SCOPE_READERS = ["augment_device_pct", "forward_device_pct", "backward_device_pct",
                 "update_device_pct", "unscoped_device_pct"]


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_scope_readers_return_nothing_without_a_map(name):
    """The parent of the PR that brought the scopes writes no map: the table
    has no seconds by scope, and nothing is reported (never 0)."""
    bare = _ctx(scope_table={"seconds": None, "steady": None})
    assert harness.read_layer_metric(name, bare) is None
    assert harness.read_layer_metric("device_idle_steady_pct", bare) is None


@pytest.mark.parametrize("name", ["device_wait_pct", "host_ms_epoch_max",
                                  "input_wait_ms_per_epoch", "setup_trace_lower_s"])
def test_span_readers_return_nothing_where_the_program_has_no_such_span(name):
    """A program with none of the new spans (it still has ``epoch``, ``probe``
    and ``aot_lower``) reports none of these, rather than a part of them."""
    old = [s for s in SPANS if s[0] in ("epoch", "probe", "sync_probe", "aot_lower")]
    assert harness.read_layer_metric(name, _ctx(spans=old)) is None


def test_table_of_a_run_with_no_profile_reads_no_file():
    ctx = _ctx()
    del ctx["scope_table"]
    assert scope_reduce.table(ctx) is None and ctx["scope_table"] is None


def test_table_looks_in_the_run_directory_the_harness_hands_it(tmp_path):
    """``run.py`` hands every reader ``ctx["run_dir"]``; the table is read
    from the profile and the scope map under it, not found by the cell's name.
    A directory with no profile in it gives no table."""
    ctx = _ctx(profile={"busy_s": 1.0}, run_dir=str(tmp_path))
    del ctx["scope_table"]
    assert scope_reduce.table(ctx) is None and ctx["scope_table"] is None
    assert not hasattr(scope_reduce, "run_dir")
