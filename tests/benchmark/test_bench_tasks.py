"""The task seam (``benchmark/tasks/``): that moving the images' code changed
no byte of its rows and weights, the tokens' rows and recipe against the
program's own partition and windows, the language model's plain reference
against the program's module, and the trainer class the harness builds
against the one ``cli.run`` builds."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, sut, tasks  # noqa: E402
from benchmark.tasks import images, tokens  # noqa: E402

FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixture")
LM = {"family": "transformer", "vocab_size": 2000, "seq_len": 35, "ninp": 200, "nhead": 2,
      "nhid": 200, "nlayers": 2}

# sha256 over rows (train_x, train_y, test_x, test_y) and over weights (path
# and bytes of every leaf) at the rehearsal's shapes, taken on the tree before
# the move (commit a95587a: harness.make_rows, harness.make_weights)
GOLDEN = {
    ("rows", 7): "3f03e51bfc65e382ed6069edf41b1e48eb0108baadde53f19d8739b8b78c3f73",
    ("weights", 7): "1bce1c4abd92104a4c240bc66faf293f284e7b0872d6fbbb109b511d274d9cb0",
    ("rows", 2**31 + 11): "de463ada1d2bebcbcfb3a7fb503d3e6b55f715347083161b0d00029c23401f5d",
    ("weights", 2**31 + 11): "deb197b657f27fdfa236470853fd354f53acc42a709cfe1f8d26ae1c8d4b1dd0",
}


def _resnet_rehearsal():
    with open(os.path.join(ROOT, "benchmark", "configs", "resnet18_cifar10.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("what,seed", sorted(GOLDEN))
def test_the_move_changed_no_byte_of_the_images_inputs(what, seed):
    import jax

    from benchmark.reference import common

    config = _resnet_rehearsal()
    model = config["rehearsal_model"]
    h = hashlib.sha256()
    if what == "rows":
        rows = images.make_rows(seed, {"n_train": 16}, 8, model)
        for k in ("train_x", "train_y", "test_x", "test_y"):
            h.update(np.ascontiguousarray(rows[k]).tobytes())
    else:
        shapes = common.family(model).param_shapes(model)
        assert common.init_std(model) is None  # resnet keeps the default draw
        w = jax.device_get(harness.make_weights(shapes, None, seed, common.init_std(model)))
        for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
            h.update(jax.tree_util.keystr(path).encode())
            h.update(np.ascontiguousarray(leaf).tobytes())
    assert h.hexdigest() == GOLDEN[(what, seed)]


def test_a_configuration_without_a_task_is_an_images_job():
    assert tasks.load(_resnet_rehearsal()) is images
    assert tasks.load({"task": "tokens"}) is tokens
    with pytest.raises(ImportError):
        tasks.load({"task": "no_such_task"})


# ----------------------------------------------------------- tokens: rows


def test_token_streams_follow_the_seed_and_can_be_learned():
    sizes = {"n_train": 20000}
    a = tokens.make_rows(2**31 + 5, sizes, 500, LM)
    b = tokens.make_rows(2**31 + 5, sizes, 500, LM)
    c = tokens.make_rows(2**31 + 6, sizes, 500, LM)
    assert np.array_equal(a["train"], b["train"]) and not np.array_equal(a["train"], c["train"])
    assert a["train"].dtype == np.int32 and len(a["train"]) == 20000 and len(a["test"]) == 500
    assert 0 <= a["train"].min() and a["train"].max() < LM["vocab_size"]
    assert not np.array_equal(a["valid"], a["test"])
    # a first-order chain: one successor follows each id about half the time
    # (a fresh draw hits it once in 2000), the same successor in every stream
    nxt = {}
    for x, y in zip(a["train"][:-1], a["train"][1:]):
        nxt.setdefault(int(x), []).append(int(y))
    top = {x: max(set(ys), key=ys.count) for x, ys in nxt.items() if len(ys) >= 8}
    share = np.mean([ys.count(top[x]) / len(ys) for x, ys in nxt.items() if x in top])
    assert 0.4 < share < 0.6
    follows = np.mean([top.get(int(x)) == int(y) for x, y in zip(a["test"][:-1], a["test"][1:])])
    assert 0.35 < follows < 0.65
    small = tokens.make_rows(3, {"n_train": 64}, 8, dict(LM, vocab_size=16))
    assert small["train"].max() < 16  # a sliced vocabulary: ids come from the slice


def test_token_bundle_is_what_the_lm_trainer_reads_of_a_corpus():
    rows = tokens.make_rows(3, {"n_train": 64}, 8, LM)
    corpus = tokens.bundle(rows, {}, None)
    assert corpus.ntokens == 2000 and corpus.train is rows["train"] and len(corpus.valid) == 8
    assert corpus.test is rows["test"] and isinstance(corpus.notes, list)


def test_token_sizes_are_read_from_the_argv():
    argv = ["-b", "8", "--bucket", "2", "--bptt", "35", "--n_train", "728"]
    assert tokens.job_sizes(argv) == {"batch": 8, "n_train": 728, "bucket": 2, "bptt": 35,
                                      "grad_clip": 0.0}
    assert tokens.job_sizes(argv + ["--grad_clip", "0.5"])["grad_clip"] == 0.5
    with pytest.raises(ValueError):
        tokens.job_sizes(["-b", "8", "--bucket", "2", "--n_train", "728"])  # no window


# --------------------------------------------------------- tokens: recipe

UNEVEN = [0.1, 0.3, 0.35, 0.25]


@pytest.mark.parametrize("shares,batch,n_train", [([0.25] * 4, 8, 728), (UNEVEN, 20, 5003),
                                                  ([0.07, 0.31, 0.31, 0.31], 16, 3000)])
def test_token_recipe_is_the_programs(shares, batch, n_train):
    """The reference's own cut, fold, windows and weights against the
    program's ``partition_indices`` / ``integer_batch_split`` / ``batchify`` /
    ``bptt_windows`` on a seeded stream: the same tokens in the same step, and
    weights that sum to 1 in every step all workers still take part in."""
    from dynamic_load_balance_distributeddnn_tpu.balance.solver import integer_batch_split
    from dynamic_load_balance_distributeddnn_tpu.data.corpus import batchify, bptt_windows
    from dynamic_load_balance_distributeddnn_tpu.data.partitioner import partition_indices

    bptt = 7
    sizes = {"n_train": n_train, "batch": batch, "bptt": bptt}
    stream = tokens.make_rows(11, sizes, 8, LM)["train"]
    cols = integer_batch_split(np.asarray(shares), batch)
    assert tokens.plan_batches(shares, sizes) == cols.tolist()
    parts = partition_indices(n_train, shares, shuffle=False)
    theirs = []
    for part, c in zip(parts, cols):
        x, y, m = bptt_windows(batchify(stream[part[0]:part[-1] + 1], int(c)), bptt)
        theirs.append((x, y, m))
    steps = tokens.epoch_windows(stream, shares, sizes)
    assert len(steps) == max(t[0].shape[0] for t in theirs)
    assert len(steps) == tokens.epoch_steps(tokens.epoch_plan(shares, sizes))
    targets = 0.0
    for s, step in enumerate(steps):
        total = 0.0
        for r, (x, y, w) in enumerate(step):
            tx, ty, tm = theirs[r]
            if s >= tx.shape[0]:
                assert not w.any()
                continue
            assert np.array_equal(x, tx[s]) and np.array_equal(y, ty[s])
            assert np.array_equal(w > 0, tm[s] > 0)
            # the program's weights: share over the window's true tokens
            assert np.allclose(w, tm[s] * shares[r] / tm[s].sum(), rtol=1e-6)
            total += float(w.sum())
            targets += float(tm[s].sum())
        if all(s < t[0].shape[0] for t in theirs):
            assert total == pytest.approx(1.0, abs=1e-5)
    assert tokens.epoch_samples(shares, sizes) == pytest.approx(targets / bptt)


def test_token_plan_errors_are_exact_checks():
    sizes = {"batch": 8, "n_train": 728, "bucket": 2, "bptt": 35, "grad_clip": 0.0}
    even = [0.25] * 4
    good = [{"steps": 3, "batches": [2, 2, 2, 2], "shares": even}]
    assert tokens.plan_errors(good, sizes) == {"plan_sum_err": 0.0, "steps_err": 0.0}
    assert tokens.epoch_samples(even, sizes) == pytest.approx(720 / 35)
    assert tokens.plan_errors([dict(good[0], steps=2)], sizes)["steps_err"] == 1.0
    # the paper's rule gives a remainder under a half no column: a moved plan
    # may run a column short of -b (seen on a loaded CPU), and that is no error
    moved = [0.36, 0.17, 0.17, 0.30]
    assert tokens.plan_batches(moved, sizes) == [3, 1, 1, 2]
    short = {"steps": tokens.epoch_steps(tokens.epoch_plan(moved, sizes)),
             "batches": [3, 1, 1, 2], "shares": moved}
    assert tokens.plan_errors([short], sizes) == {"plan_sum_err": 0.0, "steps_err": 0.0}
    # shares that do not cover the batch, or more columns than -b, are
    assert tokens.plan_errors([dict(good[0], shares=[0.25, 0.25, 0.25, 0.1])], sizes)[
        "plan_sum_err"] == 1.0
    assert tokens.plan_errors([dict(good[0], batches=[3, 2, 2, 2])], sizes)["plan_sum_err"] == 1.0
    lost = tokens.plan_errors([{"steps": 3, "raised": True}], sizes)
    assert lost["plan_sum_err"] > 0 and lost["steps_err"] > 0
    assert tokens.plan_errors([], sizes)["plan_sum_err"] > 0


def test_half_batch_leaves_out_every_second_column_and_takes_the_mean_over_the_rest():
    sizes = {"n_train": 728, "batch": 8, "bptt": 35}
    stream = tokens.make_rows(5, sizes, 8, LM)["train"]
    whole = tokens.epoch_windows(stream, [0.25] * 4, sizes)
    half = tokens.epoch_windows(stream, [0.25] * 4, sizes, half=True)
    for a, b in zip(whole, half):
        for (x, _, w), (hx, _, hw) in zip(a, b):
            assert np.array_equal(hx, x[::2]) and hw.sum() == pytest.approx(w.sum())


# ------------------------------------------- the language model's reference


def test_transformer_reference_computes_the_programs_model():
    """The plain reference and the program's flax module (dropout 0) give the
    same logits from the same seeded weights (float32, highest precision), and
    the reference's parameter shapes are the program's."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common
    from dynamic_load_balance_distributeddnn_tpu.models import build_model

    fam = common.family(LM)
    spec = build_model("transformer", ntoken=LM["vocab_size"], ninp=LM["ninp"],
                       nhead=LM["nhead"], nhid=LM["nhid"], nlayers=LM["nlayers"], dropout=0.0)
    theirs = jax.eval_shape(
        lambda k: spec.module.init({"params": k, "dropout": k}, jnp.zeros((1, 35), jnp.int32),
                                   train=False), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, theirs) == jax.tree_util.tree_map(
        lambda a: a.shape, fam.param_shapes(LM))
    params = harness.make_weights(fam.param_shapes(LM), None, 11, common.init_std(LM))
    x = jax.random.randint(jax.random.PRNGKey(1), (3, 35), 0, LM["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: spec.module.apply(
            p, x, train=True, rngs={"dropout": jax.random.PRNGKey(2)}))(params, x)
    got = jax.jit(lambda p, x: fam.forward(p, x, LM))(params, x)
    assert got.shape == (3, 35, LM["vocab_size"]) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())
    # causal: a later token moves no earlier logit
    moved = jax.jit(lambda p, x: fam.forward(p, x, LM))(params, x.at[:, 20].add(1) % 2000)
    assert float(jnp.abs(moved[:, :20] - got[:, :20]).max()) == 0.0
    assert float(jnp.abs(moved[:, 20:] - got[:, 20:]).max()) > 0.0


def test_a_familys_init_std_draws_its_leaves_and_the_loss_starts_near_ln_v():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common

    fam = common.family(LM)
    params = jax.device_get(harness.make_weights(fam.param_shapes(LM), None, 3, fam.init_std))
    p = params["params"]
    assert np.std(p["Embed_0"]["embedding"]) == pytest.approx(0.1 / 3 ** 0.5, rel=0.05)
    assert np.std(p["EncoderLayer_0"]["Dense_0"]["kernel"]) == pytest.approx(200 ** -0.5, rel=0.05)
    assert np.std(p["EncoderLayer_1"]["attn"]["out"]["kernel"]) == pytest.approx(200 ** -0.5, rel=0.05)
    assert np.std(p["Dense_0"]["kernel"]) == pytest.approx(0.1 * 200 ** -0.5, rel=0.05)
    assert np.mean(p["EncoderLayer_0"]["LayerNorm_0"]["scale"]) == pytest.approx(1.0, abs=0.05)
    x = jnp.asarray(tokens.make_rows(3, {"n_train": 4 * 36}, 8, LM)["train"].reshape(4, 36))
    losses = common.cross_entropy(fam.forward(params, x[:, :-1], LM), x[:, 1:])
    assert float(losses.mean()) == pytest.approx(np.log(2000), rel=0.03)


def test_a_familys_loss_hook_takes_the_place_of_forward_and_cross_entropy(monkeypatch):
    """A family whose training loss has terms beside the next-token loss
    brings ``loss``: the reference differentiates what it returns first and
    reports the mean of what it returns second."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common, transformer

    small = dict(LM, vocab_size=50, ninp=8, nhead=2, nhid=8, nlayers=1, seq_len=5)
    sizes = {"n_train": 8 * 11, "batch": 8, "bptt": 5}
    rows = tokens.make_rows(1, sizes, 8, small)
    params0 = jax.device_get(harness.make_weights(transformer.param_shapes(small), None, 1,
                                                  transformer.init_std))
    job = {"world_size": 4, "seed": 1, "epoch": 0, "lr": 0.5, "grad_clip": 0.0, **sizes}
    plain = tokens.train_epoch(params0, rows, small, job)

    def loss(params, x, y, weights, model, precision):
        losses = common.cross_entropy(transformer.forward(params, x, model, precision), y)
        pull = 1e-2 * jnp.sum(weights) * jnp.sum(jnp.square(params["params"]["Dense_0"]["bias"]))
        return jnp.sum(losses * weights) + pull, losses

    monkeypatch.setattr(transformer, "loss", loss, raising=False)
    hooked = tokens.train_epoch(params0, rows, small, job)
    assert common.compare(hooked, plain, params0)["update_gap"] > 0  # the extra term moved it
    assert hooked["loss"] != plain["loss"]  # ... and with it the later windows' losses
    monkeypatch.delattr(transformer, "loss")
    again = tokens.train_epoch(params0, rows, small, job)
    assert common.compare(plain, again, params0)["update_gap"] == 0


def test_the_per_worker_clip_comes_before_the_sum():
    """With a clip so small that it always bites, every worker's share of the
    first gradient has norm ``share x clip``: the sum's norm is at most the
    clip, and leaving the clip out gives another gradient."""
    import jax

    from benchmark.reference import common, transformer

    small = dict(LM, vocab_size=50, ninp=8, nhead=2, nhid=8, nlayers=1, seq_len=5)
    sizes = {"n_train": 8 * 11, "batch": 8, "bptt": 5}
    rows = tokens.make_rows(1, sizes, 8, small)
    params0 = jax.device_get(harness.make_weights(transformer.param_shapes(small), None, 1,
                                                  transformer.init_std))
    job = {"world_size": 4, "seed": 1, "epoch": 0, "lr": 0.5, "grad_clip": 1e-3, **sizes}
    norm = lambda t: float(np.sqrt(sum(np.sum(np.square(a))  # noqa: E731
                                       for a in jax.tree_util.tree_leaves(t))))
    clipped = tokens.train_epoch(params0, rows, small, job)
    bare = tokens.train_epoch(params0, rows, small, job, fault="no_clip")
    assert norm(clipped["first_grad"]) <= 1e-3 * (1 + 1e-5) < norm(bare["first_grad"])
    one = tokens.train_epoch(params0, rows, small, dict(job, shares=[1.0], world_size=1))
    assert norm(one["first_grad"]) == pytest.approx(1e-3, rel=1e-4)
    with pytest.raises(ValueError, match="unknown fault"):
        tokens.train_epoch(params0, rows, small, job, fault="no_such_fault")
    assert common.compare(clipped, clipped, params0)["update_gap"] == 0


# ------------------------------------------------- which trainer is built


def _argv(name, extra=()):
    dataset = "wikitext2" if name == "transformer" else "mnist" if name == "mnistnet" else "cifar10"
    return ["-m", name, "-ds", dataset, "-ws", "4", "-b", "8", *extra]


def _cases():
    from dynamic_load_balance_distributeddnn_tpu.config import MODELS

    cases = [(name, ()) for name in MODELS]
    return cases + [("transformer", ("--seq_parallel", "ring")),
                    ("transformer", ("--seq_parallel", "ulysses"))]


@pytest.mark.parametrize("name,extra", _cases(), ids=lambda v: v if isinstance(v, str) else "-".join(v[1:]))
def test_the_harness_builds_the_class_cli_run_builds(monkeypatch, tmp_path, name, extra):
    """``sut.trainer_class`` is a copy of ``cli.run``'s three-way choice (a
    benchmark PR edits no program file): held together here, for every model
    name the program knows and for the sequence-parallel switch."""
    from dynamic_load_balance_distributeddnn_tpu import cli
    from dynamic_load_balance_distributeddnn_tpu.config import config_from_args
    from dynamic_load_balance_distributeddnn_tpu.train import engine, lm_engine, sp_engine

    built = []
    for cls in (engine.Trainer, lm_engine.LMTrainer, sp_engine.SeqParallelLMTrainer):
        monkeypatch.setattr(cls, "__init__", lambda self, cfg, **kw: built.append(type(self)))
        monkeypatch.setattr(cls, "run", lambda self, *a, **kw: None)
    monkeypatch.setattr(cli, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(cli, "mark_run_done", lambda cfg: None)
    monkeypatch.setattr(cli, "_run_already_done_global", lambda cfg: False)
    argv = _argv(name, extra) + ["--log_dir", str(tmp_path / "logs"),
                                 "--stat_dir", str(tmp_path / "statis")]
    trainer = cli.run(argv)
    assert built == [type(trainer)]
    assert sut.trainer_class(config_from_args(argv)) is type(trainer)
