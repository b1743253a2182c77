"""The Qwen3-Next family: the program's model against the plain reference on
seeded weights, the expert layer's share arithmetic under its softmax router
and gated shared expert, the FLOP count, the published keys in their three
places, the cell's limits against the chip's readings, and the cell's
rehearsal with faults planted in the program. CPU, test widths
(``tests/fixtures/qwen3_next_tiny.json``), float32."""

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
from benchmark.flops import qwen3_next as family_flops  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402
from dynamic_load_balance_distributeddnn_tpu.models import afmoe, qwen3_next  # noqa: E402
from dynamic_load_balance_distributeddnn_tpu.ops import linear_attention, moe  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = os.path.join(ROOT, "tests", "fixtures", "qwen3_next_tiny.json")  # the keys at test widths
LAYERS = [0, 1, 2, 3]  # one period: linear, linear, linear, full
CELL = "qwen3_next.ws4_even_dbs"


def tiny_model(held=(0, 4), layers=LAYERS, seq_len=128, vocab=64):
    """The reference's ``model`` group of a cut of the test architecture."""
    pub = afmoe.published(TINY)
    return dict(pub, family="qwen3_next", layers=list(layers), num_experts=held[1] - held[0],
                first_expert=held[0], published_num_experts=pub["num_experts"],
                vocab_size=vocab, seq_len=seq_len)


def module_of(model):
    pub = afmoe.published(TINY)
    first = model["first_expert"]
    return qwen3_next.Qwen3NextLM(qwen3_next.cut_config(
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
        lambda k: module_of(model).init({"params": k}, jnp.zeros((1, 128), jnp.int32),
                                        train=False),
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, theirs) == jax.tree_util.tree_map(
        lambda a: a.shape, ref.param_shapes(model))


def test_the_seeds_draws_spread_the_decays_and_centre_the_norms():
    params = weights(tiny_model())["params"]
    linear = params["layer_0"]["linear_attn"]
    assert float(jnp.std(jnp.stack([params[f"layer_{i}"]["linear_attn"]["A_log"]
                                    for i in range(3)]))) > 0.3  # rates over a wide range
    assert abs(float(jnp.mean(linear["out_norm_scale"])) - 1.0) < 0.05  # a plain weight: around 1
    assert abs(float(jnp.mean(params["norm_weight"]))) < 0.02  # zero-centred: around 0
    assert abs(float(jnp.mean(params["layer_3"]["attn"]["q_norm_weight"]))) < 0.02


def test_logits_loss_and_every_leafs_gradient_agree():
    """One period (three linear layers and the full one) over two chunks of the
    delta rule, holding half the experts: the chunked rule, the convolution,
    partial rotary positions, both gates and the softmax router against the
    reference's token-by-token recurrence and dense forms."""
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
    # arrivals: 4 layers, 4 held + elsewhere; every (token, choice) pair counted
    assert arrivals.shape == (4, 5)
    assert np.all(np.asarray(arrivals).sum(axis=1) == x.size * model["num_experts_per_tok"])


def test_the_references_delta_rule_is_a_recurrence_and_the_programs_is_not():
    """They must not share their algebra: the reference scans tokens (a scan
    of 64 inside a scan of blocks, no triangular solve), the program scans
    chunks after a triangular solve."""
    model = tiny_model(layers=[0])
    one = jax.ShapeDtypeStruct((1, model["seq_len"]), jnp.int32)
    theirs = str(jax.make_jaxpr(lambda p, x: ref.forward(p, x, model))(
        ref.param_shapes(model), one))
    ours = str(jax.make_jaxpr(lambda p, x: module_of(model).apply(p, x))(
        ref.param_shapes(model), one))
    assert "triangular_solve" in ours and "triangular_solve" not in theirs
    assert f"length={ref.TOKEN_BLOCK}" in theirs  # the inner scan: a token a trip
    assert f"length={model['seq_len'] // linear_attention.CHUNK}" in ours  # a chunk a trip


# ------------------------------------------------------------ the share test


def _layer(seed=5, n=48, d=32, f=16, experts=8, k=3):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=0.2):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    return {"m": draw(n, d, scale=1.0), "router_kernel": draw(d, experts, scale=0.3),
            "experts_gate_kernel": draw(experts, d, f), "experts_up_kernel": draw(experts, d, f),
            "experts_down_kernel": draw(experts, f, d), "shared_gate_kernel": draw(d, f),
            "shared_up_kernel": draw(d, f), "shared_down_kernel": draw(f, d),
            "shared_out_gate_kernel": draw(d, 1, scale=0.5)}, k


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_all_shares_and_the_gated_shared_expert_once_are_the_uncut_layer(shares):
    """Over all shares of a small layer (one chip holding all 8 experts, or 2,
    4, 8 chips holding ranges of them), the routed parts summed and the gated
    shared expert counted once equal the uncut reference's layer."""
    p, k = _layer()
    m, experts = p["m"], p["experts_gate_kernel"].shape[0]
    held = experts // shares
    with jax.default_matmul_precision("highest"):
        chosen, weights_ = moe.route(m, p["router_kernel"], None, k, True, 1.0,
                                     score_func="softmax")
        total = qwen3_next.sigmoid_gated(
            afmoe.gated_mlp(m, p["shared_gate_kernel"], p["shared_up_kernel"],
                            p["shared_down_kernel"]), m @ p["shared_out_gate_kernel"])
        arrived = 0
        for s in range(shares):
            lo = s * held
            part, arrivals = moe.expert_ffn(
                m, chosen, weights_, lo, p["experts_gate_kernel"][lo:lo + held],
                p["experts_up_kernel"][lo:lo + held], p["experts_down_kernel"][lo:lo + held],
                experts)
            total = total + part
            arrived += float(arrivals[:held].sum())
            assert float(arrivals.sum()) == m.shape[0] * k
    assert arrived == m.shape[0] * k  # every pair lands on exactly one share
    model = {"num_experts_per_tok": k, "norm_topk_prob": True, "num_experts": experts,
             "first_expert": 0}
    want = ref.expert_ffn(m, p, model, "f32")
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(weights_.sum(-1) - 1).max()) < 1e-6  # renormalised over the chosen


# ----------------------------------------------------------------- the count


def test_forward_flops_by_hand():
    """A linear layer and the full one with the head, at 128 tokens."""
    t, d, v = 128, 64, 64
    key_w, value_w, hv = 2 * 16, 4 * 8, 4
    expert_part = 6 * t * d * 32 + 2 * t * d + 2 * t * d * 8 + 6 * (t * 3 * 4 / 8) * d * 32
    linear = (2 * t * d * (2 * key_w + 2 * value_w) + 2 * t * d * 2 * hv + 2 * t * value_w * d
              + 2 * t * 4 * (2 * key_w + value_w) + 3 * 2 * t * hv * 16 * 8)
    assert flops.forward_flops_per_sample(tiny_model(layers=[0])) == (
        linear + expert_part + 2 * t * d * v)
    hq, hkv = 4 * 16, 2 * 16
    full = 2 * t * d * (2 * hq + 2 * hkv) + 2 * t * hq * d + 4 * (t * (t + 1) // 2) * hq
    assert flops.forward_flops_per_sample(tiny_model(layers=[3])) == (
        full + expert_part + 2 * t * d * v)
    parts = family_flops.layer_flops(tiny_model(), 1)
    assert parts["delta_rule"] == 3 * 2 * t * hv * 16 * 8  # three products of Dk x Dv


def _product_flops(jaxpr) -> int:
    """2 x MACs of every ``dot_general`` in ``jaxpr``, those inside a scan
    once per trip."""
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
    for every query, every held expert for every token, three state products
    a token. The convolution is four multiply-adds, not a product, and is
    counted by hand."""
    model = tiny_model()
    one = jax.ShapeDtypeStruct((1, model["seq_len"]), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, x: ref.forward(p, x, model))(ref.param_shapes(model), one)
    taps = 3 * family_flops.layer_flops(model, 0)["convolution"]
    assert _product_flops(jaxpr.jaxpr) == family_flops.forward_flops(
        model, as_computed_plainly=True) - taps
    assert family_flops.forward_flops(model) < family_flops.forward_flops(
        model, as_computed_plainly=True)


def test_the_cells_sample_is_1_72_tflop():
    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3_next.json")) as f:
        model = json.load(f)["model"]
    t = 4096
    assert model["num_experts"] == 16 and model["seq_len"] == t
    linear = family_flops.layer_flops(model, 0)
    full = family_flops.layer_flops(model, 3)
    assert linear["delta_rule"] == t * 3_145_728  # 3.1 MFLOP a token and layer
    assert linear["projections"] == 2 * t * (25_165_824 + 131_072 + 8_388_608)
    assert full["projections"] == 2 * t * (16_777_216 + 2 * 1_048_576 + 8_388_608)
    assert full["experts"] == 6 * (t * 10 * 16 / 512) * 2048 * 512
    assert flops.forward_flops_per_sample(model) == 1_716_476_968_960


# ------------------------------------------ the cell's limits and its rehearsal


def test_the_cells_limits_lie_over_the_chips_readings_and_under_the_controls():
    """``limits/<cell>.readings.json`` keeps what the chip read (PERF.md
    section 2): at least 8 seeds of the program, every one correct with every
    limit at least 2.5 times its largest reading; every control and planted
    fault not correct; and at least one compared number that puts every
    float8 reading outside its limit (the file says which)."""
    spec = harness.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".readings.json")) as f:
        chip = json.load(f)
    limits = {k: v for k, v in spec["limits"].items() if k.endswith("_gap") or "_gap_" in k}
    program = list(chip["program"]["by_seed"].values())
    assert len(program) >= 8
    assert all(harness.decide(r, limits)["correct"] for r in program)
    for key, limit in limits.items():
        assert limit >= 2.5 * max(r[key] for r in program), key
    for name, control in chip["control"].items():
        for reading in control["by_seed"].values():
            held = {k: v for k, v in limits.items() if k in reading}
            assert not harness.decide(reading, held)["correct"], name
    fp8 = list(chip["control"]["fp8"]["by_seed"].values())
    assert len(fp8) >= 2
    carries = chip["control"]["fp8"]["outside"]
    assert carries and all(r[key] > limits[key] for r in fp8 for key in carries)


@pytest.fixture(scope="module")
def first_epoch(tmp_path_factory):
    """``run.py``'s steps 1, 2 and 5 for the cell's rehearsal job, without the
    warm-up and the window between them: rows and weights from the seed, the
    trainer ``cli.run`` would build, ``run_epoch(0)`` through ``sut.Job``, the
    plain reference over the same epoch, and the cell's committed limits on
    the five numbers ``correct`` rests on. The rows, weights and the
    reference's epoch are made once; ``program()`` runs the program's epoch
    and decides (a whole rehearsal of this cell through ``run.main``, with its
    window, is ``test_bench_rehearsal.py``'s, under every cell)."""
    from benchmark import tasks
    from benchmark.reference import common as reference
    from benchmark.sut import Job

    seed = 99
    spec = harness.load_cell(CELL)
    config, traffic = spec["config"], spec["traffic"]
    task = tasks.load(config)
    argv = harness.job_argv(config, traffic, rehearsal=True)
    sizes, model = task.job_sizes(argv), config["rehearsal_model"]
    rows = task.make_rows(seed, sizes, config["rehearsal_n_test"], model)
    made = {}

    def program():
        out_dir = str(tmp_path_factory.mktemp("job"))
        job = Job(argv, lambda cfg: task.bundle(rows, config, cfg), out_dir, seed, trace=False)
        shapes, shardings = job.param_shapes()
        weights_ = harness.make_weights(shapes, shardings, seed, reference.init_std(model))
        params0 = jax.device_get(weights_)
        job.set_weights(weights_)
        got = {"loss": float(job.run_epoch(0)["loss"]), **job.snapshot()}
        job.close()
        if "ref" not in made:
            made["ref"] = task.train_epoch(
                params0, rows, model, harness.job_definition(config, traffic, sizes, seed, task))
        compared = reference.compare(got, made["ref"], params0)
        return harness.decide(compared, {k: v for k, v in spec["limits"].items()
                                         if k in compared})

    return program


def test_the_programs_first_epoch_is_correct_under_the_cells_limits(first_epoch):
    verdict = first_epoch()
    assert verdict["correct"], verdict["compared"]


def _no_decay(monkeypatch):
    """g = 0: the state never fades."""
    real = linear_attention.gated_delta_rule
    monkeypatch.setattr(linear_attention, "gated_delta_rule",
                        lambda q, k, v, g, beta: real(q, k, v, jnp.zeros_like(g), beta))


def _beta_one(monkeypatch):
    """beta = 1: every token overwrites what its key held."""
    real = linear_attention.gated_delta_rule
    monkeypatch.setattr(linear_attention, "gated_delta_rule",
                        lambda q, k, v, g, beta: real(q, k, v, g, jnp.ones_like(beta)))


def _no_l2_norm(monkeypatch):
    """q and k go into the rule as the convolution left them."""
    monkeypatch.setattr(qwen3_next, "unit", lambda x: x.astype(jnp.float32))


def _no_output_gate(monkeypatch):
    """Full attention's result goes to its output projection ungated."""
    real = qwen3_next.sigmoid_gated
    monkeypatch.setattr(qwen3_next, "sigmoid_gated",
                        lambda x, gate: real(x, gate) if gate.shape[-1] == 1 else x)


def _no_shared_gate(monkeypatch):
    """The shared expert is added whole, without its one-output gate."""
    real = qwen3_next.sigmoid_gated
    monkeypatch.setattr(qwen3_next, "sigmoid_gated",
                        lambda x, gate: x if gate.shape[-1] == 1 else real(x, gate))


def _not_renormalised(monkeypatch):
    """The chosen experts keep their softmax probabilities as weights."""
    real = moe.route
    monkeypatch.setattr(
        moe, "route", lambda m, w, bias, k, route_norm, scale, **kw: real(
            m, w, bias, k, False, scale, **kw))


PLANTS = [_no_decay, _beta_one, _no_l2_norm, _no_output_gate, _no_shared_gate, _not_renormalised]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__.strip("_") for p in PLANTS])
def test_a_fault_planted_in_the_program_comes_out_as_not_correct_in_the_cell(
        first_epoch, monkeypatch, plant):
    """Each piece of the family's mathematics left out of the program, under
    the cell's committed limits. (The token job's own faults, half the columns
    and the lost clip, are planted under Trinity-Mini's cell and the fixture's:
    they are the trainer's, which the two cells share.)"""
    plant(monkeypatch)
    verdict = first_epoch()
    assert verdict["correct"] is False, verdict["compared"]
    assert [k for k, row in verdict["compared"].items() if not row["value"] <= row["limit"]]


# ------------------------------------------------------- the published keys


def test_published_keys_in_package_configuration_and_catalog_agree():
    pub = afmoe.published("qwen3_next")
    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3_next.json")) as f:
        config = json.load(f)
    changed = set(config["reduced"]) - {"n_train", "n_test"}
    assert changed == {"layers", "num_experts", "vocab_size"}  # `layers`: the depth's cut
    for key, value in pub.items():
        if key not in changed:
            assert config["model"][key] == value and config[key] == value, key
        else:
            assert config["model"][key] == config[key] != value, key
    assert config["model"]["published_num_experts"] == pub["num_experts"] == 512
    assert config["model"]["published_vocab_size"] == pub["vocab_size"] == 151936
    assert config["model"]["vocab_size"] * 8 == pub["vocab_size"]
    assert config["model"]["layers"] == config["layers"] == [0, 1, 2, 3]
    assert config["model"]["num_hidden_layers"] == 48
    held = config["argv"][config["argv"].index("--lm_experts_held") + 1]
    assert held == f"0:{config['model']['num_experts']}"
    assert config["argv"][config["argv"].index("--bptt") + 1] == str(config["model"]["seq_len"])
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert pub == row["config"]
    assert config["source"] == row["source_url"]
