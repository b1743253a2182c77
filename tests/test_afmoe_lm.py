"""The AFMoE language model on the normal path: ``cli.run`` -> ``LMTrainer``
-> ``run_epoch`` at test widths (``tests/fixtures/afmoe_tiny.json``, given to
``--lm_arch`` by its path), the flags that
choose and cut it, what the step's cast leaves in float32, and the routing
counts' way out of the scanned superstep."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamic_load_balance_distributeddnn_tpu import cli
from dynamic_load_balance_distributeddnn_tpu.config import Config, config_from_args
from dynamic_load_balance_distributeddnn_tpu.models import afmoe, build_model
from dynamic_load_balance_distributeddnn_tpu.obs import routing, scopes
from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

# Trinity-Mini's keys at test widths: nobody's model, so a file of the tests'
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "afmoe_tiny.json")
ARGV = ["-d", "false", "-m", "transformer", "--lm_arch", TINY, "--lm_layers", "1,4,5,6,7",
        "--lm_experts_held", "0:4", "-ws", "4", "-gpu", "0", "-dbs", "true", "-b", "8",
        "--bucket", "2", "--bptt", "24", "--n_train", "392", "--remat", "true", "-lr", "1.0",
        "-e", "2"]


@pytest.fixture(scope="module")
def tiny_corpus(tmp_path_factory):
    from tests.conftest import make_tiny_corpus

    return make_tiny_corpus(tmp_path_factory.mktemp("corpus"))


def test_the_flags_default_to_the_papers_model():
    cfg = config_from_args(["-m", "transformer"])
    assert (cfg.lm_arch, cfg.lm_layers, cfg.lm_experts_held, cfg.lm_dropout) == ("paper", "", "", 0.2)
    assert LMTrainer.DROPOUT is None  # the flag decides unless a test sets the class's
    cfg = config_from_args(ARGV)
    assert cli.trainer_class(cfg) is LMTrainer
    assert (cfg.lm_kept_layers(), cfg.lm_expert_range()) == ([1, 4, 5, 6, 7], (0, 4))
    assert (Config().lm_kept_layers(), Config().lm_expert_range()) == ([], None)
    with pytest.raises(ValueError, match="invalid lm_arch"):
        config_from_args(["-m", "transformer", "--lm_arch", "no_such_model"])


@pytest.mark.parametrize("layers,held", [([9], (0, 4)), ([1], (4, 3)), ([1], (0, 9))])
def test_a_cut_outside_the_published_model_is_refused(layers, held):
    with pytest.raises(ValueError, match="outside the published model"):
        build_model("afmoe", arch=TINY, ntoken=64, layers=layers, experts_held=held)


def test_the_cut_keeps_every_published_width():
    pub = afmoe.published("trinity_mini")
    c = build_model("afmoe", arch="trinity_mini", ntoken=25024, layers=[1, 4, 5, 6, 7],
                    experts_held=(0, 16)).module.cfg
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        2048, 32, 4, 128)
    assert (c.intermediate_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, c.sliding_window) == (6144, 1024, 128, 8, 2048)
    assert c.layer_types == ("sliding_attention",) * 4 + ("full_attention",)
    assert c.layer_dense == (True, False, False, False, False)
    assert (c.first_expert, c.experts_held, c.vocab_size) == (0, 16, 25024)
    assert pub["num_hidden_layers"] == 32 and len(pub["layer_types"]) == 32


def test_the_cast_leaves_the_router_in_float32(tiny_corpus, tmp_path):
    cfg = config_from_args(ARGV + ["--precision", "bfloat16", "--stat_dir", str(tmp_path)])
    tr = LMTrainer(cfg, bundle=tiny_corpus, log_to_file=False)
    cast = tr.steps._cast_compute(tr.state.params)
    kinds = {jax.tree_util.keystr(p): a.dtype
             for p, a in jax.tree_util.tree_leaves_with_path(cast)}
    routers = [k for k in kinds if "router" in k]
    assert len(routers) == 4 and all(kinds[k] == jnp.float32 for k in routers)
    assert all(v == jnp.bfloat16 for k, v in kinds.items() if "router" not in k)


def test_two_epochs_through_cli_run_and_the_counts_leave_the_scan(tiny_corpus, tmp_path,
                                                                  monkeypatch):
    """The normal path with tracing on: every epoch takes the scanned
    superstep, the loss falls, and each epoch leaves its arrivals beside the
    scope map: steps x workers rows of 4 expert layers x (4 held + elsewhere),
    every (token, choice) pair of a worker's step counted in each layer."""
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
    assert counts.shape == (steps * 4, 4, 5)
    assert np.all(counts.sum(axis=2) == 2 * 24 * 2)  # 2 columns x 24 tokens x 2 choices
    with open(traces / scopes.MAP_FILE) as f:
        named = {s for line in f for s in json.loads(line)["scopes"].values()}
    assert {scopes.ATTENTION_WINDOW, scopes.ATTENTION_FULL, scopes.ROUTER, scopes.EXPERTS,
            scopes.SHARED_EXPERT, scopes.LM_HEAD} <= named


def test_counts_are_not_written_with_the_tracer_off(tmp_path):
    from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer

    get_tracer().configure("off", trace_dir=str(tmp_path))
    routing.record_epoch(0, [np.arange(10.0)], (2, 5))
    assert not os.path.exists(tmp_path / routing.COUNTS_FILE)


# sha256 of the lowered one-step superstep (four workers of two columns x 24
# tokens, the cut of ARGV) as commit 43d4027 lowers it under jax 0.9.0. A
# second decoder family shares `ops/moe.py`, `ops/attention.py`, the step
# library and `ModelSpec` with this one: an edit there that moves this
# family's program moves Trinity-Mini's, its bytes and its warm cache. After a
# deliberate change (or a new jax), lower the parent's and this tree's and
# commit the new value only if the two agree.
SUPERSTEP_SHA256 = {
    "bfloat16": "aacfe3584889aaeef78fedadd11ebd2b8e06143aa42976607b155c5a4c03191a",
    "float32": "0d270149666c4796f524105bf88f1387f57d69f35b60255ed7faf773cc2f20a3",
}


@pytest.mark.parametrize("precision", sorted(SUPERSTEP_SHA256))
def test_the_lowered_superstep_is_the_parents_to_the_letter(precision):
    import hashlib

    from jax.sharding import Mesh

    from dynamic_load_balance_distributeddnn_tpu.train.state import TrainState, make_optimizer
    from dynamic_load_balance_distributeddnn_tpu.train.steps import StepLibrary

    spec = build_model("afmoe", arch=TINY, ntoken=64, layers=[1, 4, 5, 6, 7],
                       experts_held=(0, 4), remat=True)
    tx = make_optimizer(0.05, 0.9)

    def init_fn(key):
        params = spec.module.init({"params": key}, jnp.zeros((1, 24), jnp.int32), train=False)
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    lib = StepLibrary(spec, Mesh(np.array(jax.devices()[:1]), ("data",)), tx, grad_clip=0.25,
                      compute_dtype=jnp.bfloat16 if precision == "bfloat16" else None, remat=True)
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), 1))
    tokens = tuple(sds((1, 2, 24), jnp.int32) for _ in range(4))
    weights = tuple(sds((1, 2, 24), jnp.float32) for _ in range(4))
    keys = tuple(sds(key.shape, key.dtype) for _ in range(4))
    slows = tuple(sds((), jnp.int32) for _ in range(4))
    text = lib.group_superstep.lower(state, tokens, tokens, weights, keys, slows).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == SUPERSTEP_SHA256[precision]
