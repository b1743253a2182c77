"""Unit tests of the arithmetic the yardstick owns: the trace reduction on a
hand-made event list, the FLOP functions against hand counts and against the
reference's own jaxpr, the table of peaks, the plan arithmetic, and the plain
references against the layer equations the program's models compute."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness, trace_reduce  # noqa: E402
from benchmark.tasks import images  # noqa: E402

# two devices' worth of hand-made events: (name, start, duration)
OPS = [
    ("fusion.1", 0.0, 1.0),
    ("while.2", 2.0, 4.0),          # holds the next three
    ("convolution.3", 2.0, 1.5),
    ("all-reduce.4", 3.5, 0.5),
    ("fusion.1", 4.5, 1.0),
    ("copy.5", 8.0, 1.0),
]
HOST = [("plan_solve", 0.9, 1.2), ("train", 2.0, 4.1), ("validate", 6.1, 1.8), ("other", 0.0, 9.0)]


def test_union_counts_nested_and_overlapping_intervals_once():
    assert trace_reduce.union_seconds(OPS) == pytest.approx(1.0 + 4.0 + 1.0)
    assert trace_reduce.union_seconds([("a", 0, 2), ("b", 1, 2), ("c", 5, 1)]) == pytest.approx(4.0)
    assert trace_reduce.union_seconds([]) == 0.0


def test_self_seconds_add_up_to_busy_time():
    by_name = trace_reduce.self_seconds_by_name(OPS)
    assert by_name["while.2"] == pytest.approx(4.0 - 1.5 - 0.5 - 1.0)
    assert by_name["fusion.1"] == pytest.approx(2.0)
    assert by_name["convolution.3"] == pytest.approx(1.5)
    assert sum(by_name.values()) == pytest.approx(trace_reduce.union_seconds(OPS))


def test_gaps_longest_first_with_the_phase_that_covers_each():
    found = trace_reduce.gaps(OPS, 0.0, 9.0)
    assert found == [pytest.approx((6.0, 2.0)), pytest.approx((1.0, 1.0))]
    phases = [e for e in HOST if e[0] in harness.PHASES]
    assert trace_reduce.cover(phases, *found[0]) == "validate"
    assert trace_reduce.cover(phases, *found[1]) == "plan_solve"
    assert trace_reduce.cover([], 0.0, 1.0) == "(no host span)"


def test_collective_seconds_by_name():
    by_name = trace_reduce.self_seconds_by_name(OPS)
    assert trace_reduce.collective_seconds(by_name) == pytest.approx(0.5)
    assert trace_reduce.collective_seconds({"fusion": 1.0}) == 0.0


def _ns(events):
    return [(n, round(s * 1e9), round(d * 1e9)) for n, s, d in events]


# a `while` and the three windows of its body, in the profiler's whole
# nanoseconds: each window starts on the nanosecond its predecessor ends
T0, WIN = 1_700_000_000_130, 333_333
WHILE_NS = [("while.1", T0, 3 * WIN), ("fusion.a", T0, WIN), ("fusion.b", T0 + WIN, WIN),
            ("fusion.c", T0 + 2 * WIN, WIN)]


@pytest.mark.parametrize("whole_ns", [True, False], ids=["kept-in-ns", "scaled-to-seconds-first"])
def test_a_while_over_its_windows_is_counted_once(whole_ns):
    """An epoch of a language-model job is one ``while`` over its windows. The
    profiler's events abut in whole nanoseconds; scaled to seconds first, a
    window's event starts a rounding (7e-18 s early in a trace, 2e-13 s at
    these timestamps) before its predecessor ends, is taken for that one's
    child, and the ``while`` keeps its time (PERF.md, PR 24 Findings 2).
    ``load_xplane`` keeps the integers and ``reduce_profile`` scales after
    the self times are added up."""
    if whole_ns:
        r = trace_reduce.reduce_profile({"d0": WHILE_NS}, [], harness.PHASES,
                                        unit=trace_reduce.NS)
        by_name = dict((n, s) for n, s in r["device_ops"])
        assert by_name["while.1"] == 0.0 and r["busy_s"] == pytest.approx(3 * WIN * 1e-9)
        assert sum(by_name.values()) == pytest.approx(r["busy_s"], rel=1e-12)
        assert r["window_s"] == pytest.approx(3 * WIN * 1e-9) and r["collective_s"] == 0.0
    else:  # what the loader did before: the fault, shown on the same events
        scaled = [(n, s * 1e-9, d * 1e-9) for n, s, d in WHILE_NS]
        assert scaled[1][1] + scaled[1][2] > scaled[2][1]  # fusion.b starts inside fusion.a
        by_name = trace_reduce.self_seconds_by_name(scaled)
        assert by_name["while.1"] == pytest.approx(WIN * 1e-9, rel=1e-3)  # counted twice
        assert sum(by_name.values()) > trace_reduce.union_seconds(scaled) + 0.9 * WIN * 1e-9


def test_reduce_profile_in_nanoseconds_agrees_with_seconds():
    a = trace_reduce.reduce_profile({"d0": OPS, "d1": [("fusion.1", 0.0, 3.0)]}, HOST,
                                    harness.PHASES)
    b = trace_reduce.reduce_profile({"d0": _ns(OPS), "d1": _ns([("fusion.1", 0.0, 3.0)])}, HOST,
                                    harness.PHASES, unit=trace_reduce.NS)
    assert b["busiest"] == a["busiest"] and b["idle_gaps"][0][0] == a["idle_gaps"][0][0]
    for k in ("window_s", "busy_s", "busiest_busy_s", "collective_s"):
        assert b[k] == pytest.approx(a[k])
    assert [n for n, _ in b["device_ops"]] == [n for n, _ in a["device_ops"]]
    assert [s for _, s in b["device_ops"]] == pytest.approx([s for _, s in a["device_ops"]])


def test_reduce_profile_busiest_device_idle_and_breakdown():
    r = trace_reduce.reduce_profile({"d0": OPS, "d1": [("fusion.1", 0.0, 3.0)], "d2": []},
                                    HOST, harness.PHASES)
    assert r["busiest"] == "d0" and r["window_s"] == pytest.approx(9.0)
    assert r["busy_s"] == pytest.approx((6.0 + 3.0) / 2)
    assert r["idle_gaps"][0] == ["validate", pytest.approx(2.0)]
    assert r["device_ops"][0][0] == "fusion.1" and len(r["device_ops"]) <= 10
    assert trace_reduce.reduce_profile({"d0": []}, HOST, harness.PHASES) is None


# DenseNet-121 as published (k 32, blocks 6-12-24-16): the family's reference
# and FLOP count are held here until a cell brings its configuration back
DENSENET121 = {"family": "densenet", "nblocks": [6, 12, 24, 16], "growth_rate": 32,
               "reduction": 0.5, "num_classes": 10, "image": [32, 32, 3]}


# the repo's own language model (the paper's): like DenseNet's, its reference
# and FLOP count stand under unit tests until a configuration wants them
TRANSFORMER_LM = {"family": "transformer", "vocab_size": 2000, "seq_len": 35, "ninp": 200,
                  "nhead": 2, "nhid": 200, "nlayers": 2}


def _model(name):
    if name == "densenet121":
        return dict(DENSENET121)
    if name == "transformer_lm":
        return dict(TRANSFORMER_LM)
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_resnet18_first_stage_flops_by_hand():
    # stem 3x3 3->64 at 32x32, then 2 blocks x 2 convs 3x3 64->64 at 32x32
    stem = 2 * 32 * 32 * 9 * 3 * 64
    stage1 = 4 * (2 * 32 * 32 * 9 * 64 * 64)
    model = dict(_model("resnet18_cifar10"), widths=[64], num_blocks=[2])
    head = 2 * 64 * 8 * 8 * 10  # 4x4 pool of a 32x32 map leaves 8x8
    assert flops.forward_flops_per_sample(model) == stem + stage1 + head


def test_densenet_dense_layer_flops_by_hand():
    # one bottleneck layer at 64 input channels, 32x32: 1x1 64->128, 3x3 128->32
    layer = 2 * 32 * 32 * (64 * 128 + 9 * 128 * 32)
    one = dict(_model("densenet121"), nblocks=[1])
    none = dict(one, nblocks=[0])
    head = lambda c: 2 * c * 8 * 8 * 10  # noqa: E731
    got = flops.forward_flops_per_sample(one) - flops.forward_flops_per_sample(none)
    assert got == layer + head(96) - head(64)


def test_transformer_window_flops_by_hand():
    # one layer, window of 35 tokens, width 200, feed-forward 200, 2000 words:
    # four 200x200 projections, scores and mix over the 35x35 square summed over
    # both heads (2 x 35 x 35 x 100 each, twice), two 200x200 feed-forward
    # products, and the decoder
    t, d, v = 35, 200, 2000
    layer = 4 * (2 * t * d * d) + 2 * (2 * 2 * t * t * 100) + 2 * (2 * t * d * d)
    one = dict(TRANSFORMER_LM, nlayers=1)
    assert flops.forward_flops_per_sample(one) == layer + 2 * t * d * v
    assert flops.forward_flops_per_sample(TRANSFORMER_LM) == 2 * layer + 2 * t * d * v
    # a window twice as long: the projections double, the square quadruples
    long = dict(one, seq_len=70)
    assert flops.forward_flops_per_sample(long) - 2 * flops.forward_flops_per_sample(one) == \
        2 * (2 * 2 * t * t * 100) * 2


@pytest.mark.parametrize("config", ["densenet121", "resnet18_cifar10", "transformer_lm"])
def test_flops_agree_with_the_references_own_jaxpr(config):
    """A second, independent count: 2 x MACs of every convolution and matrix
    product in the plain reference's forward pass at batch 1 (one window in
    one column for a token family)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common

    model = _model(config)
    fam = common.family(model)
    one = jax.ShapeDtypeStruct((1, model["seq_len"]), jnp.int32) if "seq_len" in model \
        else jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: fam.forward(p, x, model))(fam.param_shapes(model), one)
    total = 0
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            kh, kw, cin, cout = eqn.invars[1].aval.shape
            _, h, w, _ = eqn.outvars[0].aval.shape
            total += 2 * h * w * kh * kw * cin * cout
        elif eqn.primitive.name == "dot_general":
            contract = eqn.params["dimension_numbers"][0][0]
            summed = int(np.prod([eqn.invars[0].aval.shape[i] for i in contract]))
            total += 2 * int(np.prod(eqn.outvars[0].aval.shape)) * summed
    assert total == flops.forward_flops_per_sample(model)
    assert flops.train_flops_per_sample(model) == 3 * total


def test_peaks_known_device_and_unknown_device_raises():
    assert harness.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="peaks.json"):
        harness.peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.peak_for("cpu")


def test_plan_arithmetic():
    shares = [0.09375, 0.28125, 0.3125, 0.3125]
    assert images.plan_batches(shares, {"batch": 4096}) == [384, 1152, 1280, 1280]
    assert images.epoch_samples(shares, {"n_train": 32768}) == 32768
    assert images.epoch_samples([1 / 3] * 3, {"n_train": 100}) == 99  # the truncating split


def test_decide_fails_on_missing_nonfinite_and_over_limit():
    limits = {"a": 0.1, "b": 0}
    assert harness.decide({"a": 0.05, "b": 0.0}, limits)["correct"]
    assert not harness.decide({"a": 0.2, "b": 0.0}, limits)["correct"]
    assert not harness.decide({"a": float("nan"), "b": 0.0}, limits)["correct"]
    assert not harness.decide({"a": 0.05}, limits)["correct"]
    assert list(harness.decide({"a": 0.05, "b": 0.0}, limits)["compared"]) == ["a", "b"]


def test_epoch_rows_place_every_row_once():
    steps = images.epoch_rows(n_train=256, world_size=4, batch=64, seed=7, epoch=3)
    assert len(steps) == 4 and all(len(w) == 16 for s in steps for w in s)
    seen = np.concatenate([w for s in steps for w in s])
    assert sorted(seen.tolist()) == list(range(256))
    again = images.epoch_rows(256, 4, 64, 7, 4)
    assert not np.array_equal(again[0][0], steps[0][0])  # a new visit order each epoch
    owner = lambda st: [set(np.concatenate([s[r] for s in st]).tolist()) for r in range(4)]  # noqa: E731
    assert owner(again) == owner(steps)  # but the same shard per worker


def test_rows_and_weights_follow_the_seed():
    model = {"image": (32, 32, 3), "num_classes": 10}
    a = images.make_rows(2**31 + 5, {"n_train": 64}, 8, model)
    b = images.make_rows(2**31 + 5, {"n_train": 64}, 8, model)
    c = images.make_rows(2**31 + 6, {"n_train": 64}, 8, model)
    assert np.array_equal(a["train_x"], b["train_x"]) and not np.array_equal(a["train_x"], c["train_x"])
    assert len({r.tobytes() for r in a["train_x"]}) == 64  # rows all differ


@pytest.mark.parametrize("name,config", [("densenet", "densenet121"),
                                         ("resnet18", "resnet18_cifar10")])
def test_reference_computes_the_programs_model(name, config):
    """The plain reference and the program's flax module give the same logits
    from the same weights (float32, highest precision), and the reference's
    parameter shapes are the program's. DenseNet's logits are compared at
    blocks 2-2-2-2 (same layer equations, a tenth of the CPU time); its shapes
    at the full 6-12-24-16."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common
    from dynamic_load_balance_distributeddnn_tpu.models import build_model
    from dynamic_load_balance_distributeddnn_tpu.models.densenet import DenseNet

    model = _model(config)
    fam = common.family(model)
    module = build_model(name).module
    theirs = jax.eval_shape(
        lambda k: module.init({"params": k, "dropout": k}, jnp.zeros((1, 32, 32, 3)), train=False),
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, theirs) == jax.tree_util.tree_map(
        lambda a: a.shape, fam.param_shapes(model))
    if name == "densenet":
        model = dict(model, nblocks=[2, 2, 2, 2])
        module = DenseNet(tuple(model["nblocks"]), growth_rate=model["growth_rate"])
    params = harness.make_weights(fam.param_shapes(model), None, 11)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: module.apply(p, x, train=True))(params, x)
    got = jax.jit(lambda p, x: fam.forward(p, x, model))(params, x)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_plan_errors_are_exact_checks():
    sizes = {"batch": 64, "n_train": 256, "bucket": 4}
    good = [{"steps": 4, "batches": [4, 20, 20, 20]}, {"steps": 4, "batches": [16] * 4}]
    assert images.plan_errors(good, sizes) == {"plan_sum_err": 0.0, "steps_err": 0.0}
    assert images.plan_errors(good + [{"steps": 4, "batches": [16, 16, 16, 12]}], sizes)[
        "plan_sum_err"] == 4.0
    assert images.plan_errors([{"steps": 3, "batches": [16] * 4}], sizes)["steps_err"] == 1.0
    lost = images.plan_errors([{"steps": 4, "raised": True}], sizes)
    assert lost["plan_sum_err"] > 0 and lost["steps_err"] > 0
    assert images.plan_errors([], sizes)["plan_sum_err"] > 0


def test_window_readers_take_the_whole_window():
    """``train_mfu_pct`` and ``train_phase_pct`` are the window's: all its
    samples and all its ``train`` spans over all its wall, a span outside it
    (the profiled epoch's) left out."""
    model = _model("resnet18_cifar10")
    ctx = {"window": {"t0": 10.0, "t1": 20.0, "wall_s": 10.0, "samples_per_s": 1000.0},
           "spans": [("train", "phase", 10.5, 4.0), ("train", "phase", 15.0, 4.0),
                     ("train", "phase", 20.5, 4.0), ("validate", "phase", 14.5, 0.5)],
           "cell": {"chips": 1}, "peak": {"bf16_flops_per_s": 197e12}, "model": model}
    assert harness.read_layer_metric("train_phase_pct", ctx) == pytest.approx(80.0)
    want = 100.0 * 1000.0 * flops.train_flops_per_sample(model) / 197e12
    assert harness.read_layer_metric("train_mfu_pct", ctx) == pytest.approx(want)
    assert harness.read_layer_metric("train_mfu_pct", dict(ctx, peak=None)) is None
    assert harness.read_layer_metric("train_phase_pct", dict(ctx, spans=[])) is None


def test_a_mix_across_chips_is_refused_until_it_brings_its_draw():
    traffic = {"name": "dp4", "world_size": 4, "one_chip": False}
    with pytest.raises(SystemExit, match="one chip"):
        harness.job_definition({"lr": 0.01, "dataset": "cifar10"}, traffic,
                               {"n_train": 16, "batch": 8}, 3, images)
