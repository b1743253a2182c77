"""The AFMoE family (Trinity-Mini): the program's model against the plain
reference on seeded weights, the expert layer's share arithmetic, the window
mask, the FLOP count, and the published keys in their three places. CPU,
test widths (``tests/fixtures/afmoe_tiny.json``), float32."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import flops, harness  # noqa: E402
from benchmark.flops import afmoe as afmoe_flops  # noqa: E402
from benchmark.reference import afmoe as ref  # noqa: E402
from dynamic_load_balance_distributeddnn_tpu.models import afmoe  # noqa: E402
from dynamic_load_balance_distributeddnn_tpu.ops import moe  # noqa: E402
from dynamic_load_balance_distributeddnn_tpu.ops.attention import (  # noqa: E402
    blocked_causal_attention,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = os.path.join(ROOT, "tests", "fixtures", "afmoe_tiny.json")  # the keys at test widths
LAYERS = [1, 4, 5, 6, 7]  # a dense window layer, then window, window, window, full


def tiny_model(held=(0, 4), layers=LAYERS, seq_len=24, vocab=64):
    """The reference's ``model`` group of a cut of the test architecture."""
    pub = afmoe.published(TINY)
    return dict(pub, family="afmoe", layers=list(layers), num_experts=held[1] - held[0],
                first_expert=held[0], published_num_experts=pub["num_experts"],
                vocab_size=vocab, seq_len=seq_len)


def module_of(model):
    pub = afmoe.published(TINY)
    first = model["first_expert"]
    return afmoe.AFMoELM(afmoe.cut_config(
        pub, model["vocab_size"], model["layers"], (first, first + model["num_experts"])))


def weights(model, seed=3):
    return harness.make_weights(ref.param_shapes(model), None, seed, ref.init_std)


def tokens(model, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, model["vocab_size"], (rows, model["seq_len"] + 1)).astype(np.int32)
    return x[:, :-1], x[:, 1:]


# ------------------------------------------------- program against reference


def test_the_programs_tree_is_the_references():
    model = tiny_model()
    theirs = jax.eval_shape(
        lambda k: module_of(model).init({"params": k}, jnp.zeros((1, 24), jnp.int32), train=False),
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, theirs) == jax.tree_util.tree_map(
        lambda a: a.shape, ref.param_shapes(model))


def test_logits_loss_and_every_leafs_gradient_agree():
    """A 5-layer cut with both layer kinds and both FFN kinds, at a length
    three times the window, holding half the experts."""
    model = tiny_model()
    module, params = module_of(model), weights(model)
    x, y = tokens(model)
    w = jnp.full(x.shape, 1.0 / x.size, jnp.float32)

    def program(p):
        logits, arrivals = module.apply(p, x, train=True)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * w), (logits, arrivals)

    with jax.default_matmul_precision("highest"):
        (loss_p, (logits_p, arrivals)), grad_p = jax.jit(
            jax.value_and_grad(program, has_aux=True))(params)
        eval_logits = jax.jit(lambda p: module.apply(p, x, train=False))(params)
    (loss_r, _), grad_r = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, x, y, w, model), has_aux=True))(params)
    logits_r = jax.jit(lambda p: ref.forward(p, x, model))(params)

    assert float(jnp.abs(logits_p - logits_r).max()) < 1e-4 * float(jnp.abs(logits_r).max())
    assert np.allclose(np.asarray(eval_logits), np.asarray(logits_p), rtol=1e-4, atol=1e-6)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    flat_p = jax.tree_util.tree_leaves_with_path(grad_p)
    flat_r = jax.tree_util.tree_leaves(grad_r)
    assert len(flat_p) == len(flat_r)
    for (path, got), want in zip(flat_p, flat_r):
        scale = float(jnp.abs(want).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(got - want).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
    # arrivals: 4 expert layers, 4 held + elsewhere; every (token, choice) pair counted
    assert arrivals.shape == (4, 5)
    assert np.all(np.asarray(arrivals).sum(axis=1) == x.size * model["num_experts_per_tok"])


# ------------------------------------------------------------ the share test


def _layer(seed=5, n=48, d=32, f=16, experts=8, k=3):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_r = jnp.asarray(rng.normal(size=(d, experts)) * 0.3, jnp.float32)
    w_gate = jnp.asarray(rng.normal(size=(experts, d, f)) * 0.2, jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(experts, d, f)) * 0.2, jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(experts, f, d)) * 0.2, jnp.float32)
    shared = [jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32) for s in ((d, f), (d, f), (f, d))]
    return m, w_r, w_gate, w_up, w_down, shared, k


def _uncut_reference(m, w_r, w_gate, w_up, w_down, shared, k):
    model = {"num_experts_per_tok": k, "route_norm": True, "route_scale": 2.826,
             "num_experts": w_gate.shape[0], "first_expert": 0}
    p = {"router_kernel": w_r, "shared_gate_kernel": shared[0], "shared_up_kernel": shared[1],
         "shared_down_kernel": shared[2], "experts_gate_kernel": w_gate,
         "experts_up_kernel": w_up, "experts_down_kernel": w_down}
    return ref.expert_ffn(m, p, model, "f32")


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_all_shares_and_the_shared_expert_once_are_the_uncut_layer(shares):
    """Over all shares of a small layer, the routed parts summed and the
    shared expert counted once equal the uncut reference."""
    m, w_r, w_gate, w_up, w_down, shared, k = _layer()
    experts = w_gate.shape[0]
    held = experts // shares
    with jax.default_matmul_precision("highest"):
        chosen, weights_ = moe.route(m, w_r, jnp.zeros((experts,)), k, True, 2.826)
        total = afmoe.gated_mlp(m, *shared)
        arrived = 0
        for s in range(shares):
            lo = s * held
            part, arrivals = moe.expert_ffn(m, chosen, weights_, lo, w_gate[lo:lo + held],
                                            w_up[lo:lo + held], w_down[lo:lo + held], experts)
            total = total + part
            arrived += float(arrivals[:held].sum())
            assert float(arrivals.sum()) == m.shape[0] * k
    assert arrived == m.shape[0] * k  # every pair lands on exactly one share
    want = _uncut_reference(m, w_r, w_gate, w_up, w_down, shared, k)
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("where", ["held", "elsewhere"])
def test_no_token_is_dropped_when_every_token_takes_one_expert(where):
    """A routing forced onto one expert: every token's first choice is expert
    2. A share that holds it computes all of them (the arrivals pass the small
    chunk, so every chunk runs); one that does not adds nothing."""
    m, _, w_gate, w_up, w_down, _, _ = _layer(n=2048, experts=16)
    n, experts, k = m.shape[0], 16, 2
    chosen = jnp.stack([jnp.full((n,), 2), 8 + jnp.arange(n) % 8], axis=1).astype(jnp.int32)
    weights_ = jnp.stack([jnp.full((n,), 0.7), jnp.full((n,), 0.3)], axis=1)
    lo = 2 if where == "held" else 4
    assert moe.chunk_rows(n * k, 2, experts) < n  # one chunk could not hold them
    with jax.default_matmul_precision("highest"):
        out, arrivals = jax.jit(lambda mm: moe.expert_ffn(
            mm, chosen, weights_, lo, w_gate[lo:lo + 2], w_up[lo:lo + 2], w_down[lo:lo + 2],
            experts))(m)
        want = 0.7 * afmoe.gated_mlp(m, w_gate[2], w_up[2], w_down[2])
    if where == "held":
        assert list(np.asarray(arrivals)) == [n, 0, n]
        assert float(jnp.abs(out - want).max()) < 1e-5 * float(jnp.abs(want).max())
    else:
        assert list(np.asarray(arrivals)) == [0, 0, 2 * n]
        assert float(jnp.abs(out).max()) == 0.0


# -------------------------------------------------------------- the window


@pytest.mark.parametrize("window", [None, 8, 200])
def test_blocked_attention_is_dense_attention_under_the_mask(window):
    """At a length over the window and over the block: the blocked form with
    its static key slices against scores of the whole square under a dense
    mask."""
    rng = np.random.default_rng(1)
    b, t, h, hkv, d = 2, 300, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = blocked_causal_attention(q, k, v, window, block_q=128)
        kk, vv = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(d)
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        mask = (i >= j) if window is None else (i >= j) & (i - j < window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vv)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert np.array_equal(np.asarray(ref.visible(t, 0, t, window)), mask)


# ----------------------------------------------------------------- the count


def test_forward_flops_by_hand():
    """One window layer with experts and the head, at 24 tokens and window 8:
    projections, 4 x 164 unmasked pairs x 64, the shared expert, the router
    over 8, 24 x 2 x 4/8 expected arrivals, and the head."""
    model = tiny_model(layers=[4])
    t, d, hq, hkv, fe, v = 24, 64, 64, 32, 32, 64
    pairs = sum(min(i + 1, 8) for i in range(t))
    assert pairs == 36 + 16 * 8 == afmoe_flops.attention_pairs(t, 8)
    assert afmoe_flops.attention_pairs(t) == t * (t + 1) // 2
    layer = (2 * t * d * (3 * hq + 2 * hkv) + 4 * pairs * hq + 6 * t * d * fe + 2 * t * d * 8
             + 6 * (t * 2 * 4 / 8) * d * fe)
    assert flops.forward_flops_per_sample(model) == layer + 2 * t * d * v
    dense = dict(model, layers=[1])
    assert flops.forward_flops_per_sample(dense) == (
        2 * t * d * (3 * hq + 2 * hkv) + 4 * pairs * hq + 6 * t * d * 96 + 2 * t * d * v)


def _product_flops(jaxpr) -> int:
    """2 x MACs of every ``dot_general`` in ``jaxpr``, those inside a
    ``lax.map`` (a scan) once per trip."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            contract = eqn.params["dimension_numbers"][0][0]
            summed = int(np.prod([eqn.invars[0].aval.shape[i] for i in contract]))
            total += 2 * int(np.prod(eqn.outvars[0].aval.shape)) * summed
        trips = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                total += trips * _product_flops(inner)
    return total


def test_the_plain_count_is_the_references_jaxpr():
    """Every product in the reference's forward pass of one column: every key
    for every query, every held expert for every token."""
    model = tiny_model()
    one = jax.ShapeDtypeStruct((1, model["seq_len"]), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, x: ref.forward(p, x, model))(ref.param_shapes(model), one)
    total = _product_flops(jaxpr.jaxpr)
    assert total == afmoe_flops.forward_flops(model, as_computed_plainly=True)
    assert afmoe_flops.forward_flops(model) < total


def test_the_cells_sample_is_2_71_tflop():
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity_mini.json")) as f:
        model = json.load(f)["model"]
    # dense layer 635.67 G, three window expert layers of 405.89 G, the full one 440.27 G,
    # the head 419.83 G (at 16 held: 2,816.5 G; each expert layer's routed part halves)
    assert model["num_experts"] == 8
    assert flops.forward_flops_per_sample(model) == 2_713_446_252_544
    assert flops.forward_flops_per_sample(dict(model, num_experts=16)) == 2_816_525_467_648


# ------------------------------------------ the cell's rehearsal and its faults
# (tests/benchmark/conftest.py: what the harness's own files cannot hold here)

CELL = "trinity_mini.ws4_even_dbs"
# The rehearsal model's own limits. The cell's were read on the chip at width
# 2,048; a leaf here (width 64) holds a thousandth of the elements and its
# norm averages away a thirtieth of the rounding. Read on the CPU over seeds
# 2**31 + 4 and 17: bfloat16 medians 0.9e-3-1.4e-3 and worst leaves 0.007-0.018,
# float8 medians 5.5e-3-7.7e-3 and worst leaves 0.040-0.091.
REHEARSAL_LIMITS = {"update_gap_median": 3e-3, "moment_gap_median": 3e-3,
                    "update_gap": 0.03, "moment_gap": 0.03}


def test_the_cells_limits_lie_between_the_chips_readings():
    """``limits/<cell>.readings.json`` keeps what the chip read (PERF.md
    section 2). Every run of the program is correct under the limits, every
    control is not, and each limit that has an upper reading leaves at least
    1.5 times of room to it and twice that to the largest the program read."""
    spec = harness.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".readings.json")) as f:
        chip = json.load(f)
    limits = {k: v for k, v in spec["limits"].items() if k.endswith("_gap") or "_gap_" in k}
    program = list(chip["program"]["by_seed"].values())
    assert len(program) >= 5
    assert all(harness.decide(r, limits)["correct"] for r in program)
    for name, control in chip["control"].items():
        for reading in control["by_seed"].values():
            held = {k: v for k, v in limits.items() if k in reading}
            assert not harness.decide(reading, held)["correct"], name
    fp8 = list(chip["control"]["fp8"]["by_seed"].values())
    for key, limit in limits.items():
        lower = max(r[key] for r in program)
        upper = min(r[key] for r in fp8)
        assert limit >= 2.0 * lower, key
        if upper >= 3 * lower:  # else float8 gives this number no upper reading
            assert limit * 1.5 <= upper, key


@pytest.mark.parametrize("seed", [2**31 + 4, 17])
def test_at_test_widths_bfloat16_passes_and_float8_fails_limits_of_the_rehearsals_own(seed):
    from benchmark import control

    out = control.readings(CELL, seed=seed, variants=["bf16", "fp8"], rehearsal=True)
    over = {v: [k for k, limit in REHEARSAL_LIMITS.items() if not out[v]["readings"][k] <= limit]
            for v in out}
    assert not over["bf16"] and over["fp8"], {v: out[v]["readings"] for v in out}


def _half_columns(monkeypatch):
    """Every second column of every worker left out, the mean taken over the rest."""
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


@pytest.mark.parametrize("plant", [_half_columns, _no_clip], ids=["half_columns", "no_clip"])
def test_the_token_jobs_own_faults_come_out_as_not_correct_in_the_cell(capsys, monkeypatch, plant):
    """The faults ``test_bench_rehearsal.py`` plants under its fixture cell,
    planted in the program under this one."""
    from benchmark import run

    plant(monkeypatch)
    rc = run.main(["--workload", CELL, "--seed", "99", "--seconds", "1", "--trace", "0",
                   "--rehearsal"])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is False, result["compared"]
    assert [k for k, row in result["compared"].items() if not row["value"] <= row["limit"]]


# ------------------------------------------------------- the published keys


def test_published_keys_in_package_configuration_and_catalog_agree():
    pub = afmoe.published("trinity_mini")
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity_mini.json")) as f:
        config = json.load(f)
    changed = set(config["reduced"]) - {"n_train", "n_test"}
    assert changed == {"layers", "num_experts", "vocab_size"}  # `layers`: the depth's cut
    for key, value in pub.items():
        if key not in changed:
            assert config["model"][key] == value and config[key] == value, key
        else:
            assert config["model"][key] == config[key] != value, key
    assert config["model"]["published_num_experts"] == pub["num_experts"]
    assert config["model"]["layers"] == config["layers"] == [1, 4, 5, 6, 7]
    assert all(0 <= i < pub["num_hidden_layers"] for i in config["layers"])
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert pub == row["config"]
    assert config["source"] == row["source_url"]
