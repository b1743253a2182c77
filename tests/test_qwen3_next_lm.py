"""The Qwen3-Next language model on the normal path: ``cli.run`` ->
``LMTrainer`` -> ``run_epoch`` at test widths
(``tests/fixtures/qwen3_next_tiny.json``, given to ``--lm_arch`` by its path),
the flags that choose and cut it, what the step's cast leaves in float32, the
routing counts' way out of the scanned superstep; and its own operations: the
chunked gated delta rule against the token-by-token recurrence, the causal
convolution, the softmax router."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu import cli
from dynamic_load_balance_distributeddnn_tpu.config import LM_ARCHS, config_from_args
from dynamic_load_balance_distributeddnn_tpu.models import afmoe, build_model, qwen3_next
from dynamic_load_balance_distributeddnn_tpu.obs import routing, scopes
from dynamic_load_balance_distributeddnn_tpu.ops import linear_attention, moe
from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer
from tests.conftest import traced_instants

# Qwen3-Next's keys at test widths: nobody's model, so a file of the tests'
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "qwen3_next_tiny.json")
# published layers 2 and 3: one linear layer and the full one (XLA:CPU takes a
# minute to compile a superstep of four)
ARGV = ["-d", "false", "-m", "transformer", "--lm_arch", TINY, "--lm_layers", "2,3",
        "--lm_experts_held", "0:4", "-ws", "4", "-gpu", "0", "-dbs", "true", "-b", "8",
        "--bucket", "2", "--bptt", "64", "--n_train", "1032", "--remat", "true", "-lr", "1.0",
        "-e", "2"]


@pytest.fixture(scope="module")
def tiny_corpus(tmp_path_factory):
    from tests.conftest import make_tiny_corpus

    return make_tiny_corpus(tmp_path_factory.mktemp("corpus"))


# ------------------------------------------------------------ the delta rule


def recurrence(q, k, v, g, beta):
    """The rule a token at a time: ``S <- exp(g) S; u = beta (v - S^T k);
    S <- S + k u^T; o = S^T q``."""
    b, _, h, dk = q.shape

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def rule_operands(decay, b=2, t=128, h=3, dk=16, dv=8, seed=0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(b, t, h, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(b, t, h, dk)))
    v = rng.normal(size=(b, t, h, dv))
    g = -decay * rng.uniform(size=(b, t, h))
    beta = rng.uniform(size=(b, t, h))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


# decay 0.1: states that last for chunks. Decay 30: the running sum of g passes
# -1,000 inside a chunk of 64, where exp() is 0 in float32 and a ratio of two
# exponentials would be 0/0
@pytest.mark.parametrize("decay", [0.1, 30.0], ids=["slow_decay", "decay_past_underflow"])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_delta_rule_is_the_recurrence_forward_and_gradients(chunk, decay):
    args = rule_operands(decay)
    assert decay < 1 or float(jnp.cumsum(args[3][:, :64], axis=1).min()) < -800

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    with jax.default_matmul_precision("highest"):
        got = linear_attention.gated_delta_rule(*args, chunk)
        want = recurrence(*args)
        grads = jax.grad(scalar(lambda *a: linear_attention.gated_delta_rule(*a, chunk)),
                         argnums=(0, 1, 2, 3, 4))(*args)
        wants = jax.grad(scalar(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    for name, g_, w_ in zip(("q", "k", "v", "g", "beta"), grads, wants):
        assert bool(jnp.isfinite(g_).all()), name
        assert float(jnp.abs(g_ - w_).max()) < 1e-4 * float(jnp.abs(w_).max()), name


def test_delta_rule_takes_many_columns_a_group_at_a_time_and_refuses_a_ragged_window():
    """Ten columns (an evaluation batch) go two at a time and three one at a
    time; the result is the same rule."""
    for b in (10, 3):
        args = rule_operands(0.5, b=b, t=64, seed=b)
        with jax.default_matmul_precision("highest"):
            got, want = linear_attention.gated_delta_rule(*args, 32), recurrence(*args)
        assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    with pytest.raises(ValueError, match="must divide by the chunk"):
        linear_attention.gated_delta_rule(*rule_operands(0.5, t=48), 32)


def test_each_lowered_delta_rule_says_so_once():
    args = tuple(x.astype(jnp.bfloat16) for x in rule_operands(0.5, t=64))
    with traced_instants("linear_attention_path") as said:
        jax.jit(lambda *a: linear_attention.gated_delta_rule(*a, 32)).lower(*args)
    assert said == [{"path": "chunked", "why": "head_dim", "chunk": 32, "t": 64,
                     "dtype": "bfloat16", "heads": 3}]


def test_causal_conv_is_numpys_convolution_per_channel_and_sees_nothing_before_the_window():
    rng = np.random.default_rng(2)
    x, w = rng.normal(size=(2, 20, 5)), rng.normal(size=(4, 5))
    got = np.asarray(linear_attention.causal_conv(jnp.asarray(x, jnp.float32),
                                                  jnp.asarray(w, jnp.float32)))
    for b in range(2):
        for c in range(5):
            # y_t = sum_i w_i x_(t - 3 + i): numpy's convolution with the taps reversed
            want = np.convolve(x[b, :, c], w[::-1, c])[:20]
            np.testing.assert_allclose(got[b, :, c], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0] * w[3], rtol=1e-5)  # the first token: itself alone
    later = x.copy()
    later[:, 10:] += 1.0  # causal: what comes later changes nothing before it
    again = np.asarray(linear_attention.causal_conv(jnp.asarray(later, jnp.float32),
                                                    jnp.asarray(w, jnp.float32)))
    np.testing.assert_array_equal(again[:, :10], got[:, :10])


@pytest.mark.parametrize("score_func", ["sigmoid", "softmax"])
def test_route_scores_by_the_models_function_with_or_without_a_bias(score_func):
    rng = np.random.default_rng(4)
    m = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    chosen, weights = moe.route(m, w, None, 3, True, 1.0, score_func=score_func)
    logits = np.asarray(m, np.float64) @ np.asarray(w, np.float64)
    scores = (1 / (1 + np.exp(-logits)) if score_func == "sigmoid"
              else np.exp(logits) / np.exp(logits).sum(-1, keepdims=True))
    top = np.argsort(-scores, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(top, -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    same, _ = moe.route(m, w, jnp.zeros((16,)), 3, True, 1.0, score_func=score_func)
    assert np.array_equal(np.asarray(same), np.asarray(chosen))  # a zero bias chooses alike


# ------------------------------------------------------------- flags and cut


def test_the_name_and_the_files_model_type_choose_the_family():
    assert "qwen3_next" in LM_ARCHS and "trinity_mini" in LM_ARCHS
    cfg = config_from_args(ARGV)
    assert cli.trainer_class(cfg) is LMTrainer
    assert (cfg.lm_kept_layers(), cfg.lm_expert_range()) == ([2, 3], (0, 4))
    assert afmoe.published(TINY)["model_type"] == afmoe.published("qwen3_next")["model_type"]
    # a family is built only from a file of its own model_type
    with pytest.raises(ValueError, match="model_type"):
        build_model("afmoe", arch=TINY, ntoken=64)
    with pytest.raises(ValueError, match="model_type"):
        build_model("qwen3_next", arch="trinity_mini", ntoken=64)


@pytest.mark.parametrize("layers,held", [([8], (0, 4)), ([1], (4, 3)), ([1], (0, 9))])
def test_a_cut_outside_the_published_model_is_refused(layers, held):
    with pytest.raises(ValueError, match="outside the published model"):
        build_model("qwen3_next", arch=TINY, ntoken=64, layers=layers, experts_held=held)


def test_the_cut_keeps_every_published_width():
    pub = afmoe.published("qwen3_next")
    spec = build_model("qwen3_next", arch="qwen3_next", ntoken=18992, layers=[0, 1, 2, 3],
                       experts_held=(0, 16))
    c = spec.module.cfg
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        2048, 16, 2, 256)
    assert (c.linear_num_key_heads, c.linear_key_head_dim, c.linear_num_value_heads,
            c.linear_value_head_dim, c.linear_conv_kernel_dim) == (16, 128, 32, 128, 4)
    assert (c.moe_intermediate_size, c.shared_expert_intermediate_size, c.num_experts,
            c.num_experts_per_tok, c.partial_rotary_factor) == (512, 512, 512, 10, 0.25)
    assert c.layer_full == (False, False, False, True)  # one whole period
    assert (c.first_expert, c.experts_held, c.vocab_size) == (0, 16, 18992)
    assert pub["num_hidden_layers"] == 48 and pub["full_attention_interval"] == 4
    assert spec.aux_shape == (4, 17) and spec.own_remat and spec.serial_workers
    assert set(spec.f32_leaves) == {"router", "A_log", "dt_bias"}
    shapes = jax.eval_shape(lambda k: spec.module.init(
        {"params": k}, jnp.zeros((1, 64), jnp.int32)), jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    # the issue's 424.3 M, with the nine norms of 2,048 it leaves out
    assert count == 3 * 33_718_464 + 27_263_488 + 4 * 54_528_000 + 2 * 18992 * 2048 + 9 * 2048


def test_a_window_the_chunk_does_not_divide_is_refused_by_name(tiny_corpus, tmp_path):
    cfg = config_from_args([a if a != "64" else "48" for a in ARGV]
                           + ["--stat_dir", str(tmp_path)])
    with pytest.raises(ValueError, match="--bptt 48 must divide by 64"):
        LMTrainer(cfg, bundle=tiny_corpus, log_to_file=False)


def test_the_cast_leaves_router_and_decay_leaves_in_float32(tiny_corpus, tmp_path):
    cfg = config_from_args(ARGV + ["--precision", "bfloat16", "--stat_dir", str(tmp_path)])
    tr = LMTrainer(cfg, bundle=tiny_corpus, log_to_file=False)
    cast = tr.steps._cast_compute(tr.state.params)
    kinds = {jax.tree_util.keystr(p): a.dtype
             for p, a in jax.tree_util.tree_leaves_with_path(cast)}
    kept = [k for k in kinds if any(s in k for s in qwen3_next.F32_LEAVES)]
    assert len(kept) == 2 + 1 + 1 and all(kinds[k] == jnp.float32 for k in kept)  # routers, A_log, dt_bias
    assert all(v == jnp.bfloat16 for k, v in kinds.items() if k not in kept)
    # the program's own draws of the two decay leaves
    linear = tr.state.params["params"]["layer_0"]["linear_attn"]
    assert np.all(np.asarray(linear["dt_bias"]) == 1.0)
    a = np.exp(np.asarray(linear["A_log"]))
    assert np.all((a > 1e-3) & (a < 16.0))


def test_two_epochs_through_cli_run_and_the_counts_leave_the_scan(tiny_corpus, tmp_path,
                                                                  monkeypatch):
    """The normal path with tracing on: every epoch takes the scanned
    superstep, the loss falls, each epoch leaves its arrivals beside the scope
    map (steps x workers rows of 2 layers x (4 held + elsewhere)), every mixer
    that was lowered said so, and the new scopes are in the programs."""
    from dynamic_load_balance_distributeddnn_tpu.data import corpus as corpus_mod

    monkeypatch.setattr(corpus_mod, "Corpus", lambda *a, **k: tiny_corpus)
    monkeypatch.setattr(
        "dynamic_load_balance_distributeddnn_tpu.train.lm_engine.Corpus",
        lambda *a, **k: tiny_corpus)
    traces = tmp_path / "traces"
    trainer = cli.run(ARGV + ["--trace", "on", "--trace_dir", str(traces),
                              "--log_dir", str(tmp_path / "logs"),
                              "--stat_dir", str(tmp_path / "statis")])
    rec = trainer.recorder
    assert set(rec.meta["exec_path"]) == {"elastic:scan"}
    losses = rec.data["train_loss"]
    assert np.isfinite(losses).all() and losses[1] < losses[0]
    with open(traces / routing.COUNTS_FILE) as f:
        lines = [json.loads(line) for line in f]
    assert [row["epoch"] for row in lines] == [0, 1]
    counts = np.asarray(lines[0]["counts"])
    steps = int(rec.data["steps"][0])
    assert counts.shape == (steps * 4, 2, 5)
    assert np.all(counts.sum(axis=2) == 2 * 64 * 3)  # 2 columns x 64 tokens x 3 choices
    with open(traces / scopes.MAP_FILE) as f:
        named = {s for line in f for s in json.loads(line)["scopes"].values()}
    assert {scopes.LINEAR_ATTENTION, scopes.DELTA_RULE, scopes.ATTENTION_FULL, scopes.ROUTER,
            scopes.EXPERTS, scopes.SHARED_EXPERT, scopes.LM_HEAD} <= named
    said = [e[6] for e in trainer._trace.events() if e[0] == "linear_attention_path"]
    assert said and all(a["chunk"] == 64 and a["t"] == 64 and a["heads"] == 4 for a in said)
