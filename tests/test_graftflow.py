"""graftflow (whole-program dataflow) tests: every flow rule must trip on
its seeded fixture — including minimized reproductions of the PR-6
donated-restore use-after-free and the PR-5 compile-pool drain race — the
clean twins must stay quiet, the engine's interprocedural machinery
(summaries, call graph, lock environments, thread inventory) must hold its
contracts, and the CLI satellites (--select/--ignore, --format json|sarif,
baseline files, parallel + cached runs) must work end to end.
"""

import json
import pathlib
import time

import pytest

from dynamic_load_balance_distributeddnn_tpu.analysis.cli import main as cli_main
from dynamic_load_balance_distributeddnn_tpu.analysis.flow import (
    CallGraph,
    Project,
    analyze_paths,
    analyze_source,
    summarize_source,
)
from dynamic_load_balance_distributeddnn_tpu.analysis.flow.baseline import (
    filter_baselined,
    load_baseline,
    write_baseline,
)
from dynamic_load_balance_distributeddnn_tpu.analysis.linter import (
    lint_file,
    lint_paths,
)

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "graftflow"
REPO = pathlib.Path(__file__).resolve().parents[1]
PKG = REPO / "dynamic_load_balance_distributeddnn_tpu"


def codes(findings):
    return {f.code for f in findings}


# ------------------------------------------------------------ seeded fixtures


@pytest.mark.parametrize(
    "fixture,expected_code,min_findings",
    [
        # foreign-alias donation + cross-function read + surviving alias
        ("g011_violation.py", "G011", 3),
        # unguarded pool handle + unguarded shutdown flag
        ("g012_violation.py", "G012", 2),
        # stale local capture + never-invalidated derived attr
        ("g013_violation.py", "G013", 2),
        # alias + donation in the SAME If arm (branch-aware groups still fire)
        ("g011_branch_violation.py", "G011", 1),
        # donation through **kwargs forwarding + tree_map lambda dispatch
        ("g011_forward_violation.py", "G011", 2),
    ],
)
def test_flow_rule_trips_on_seeded_fixture(fixture, expected_code, min_findings):
    findings = analyze_paths([str(FIXTURES / fixture)])
    hits = [f for f in findings if f.code == expected_code]
    assert len(hits) >= min_findings, (fixture, findings)
    # a seeded fixture must not also trip unrelated flow rules (noise)
    assert codes(findings) == {expected_code}, findings
    # nor any single-file rule — each corpus file isolates ONE bug class
    assert lint_file(str(FIXTURES / fixture)) == []


@pytest.mark.parametrize(
    "fixture",
    [
        "g011_clean.py",
        "g012_clean.py",
        "g013_clean.py",
        # the recorded branch-sensitivity false positive, now closed
        "g011_branch_clean.py",
    ],
)
def test_clean_fixture_is_quiet(fixture):
    path = str(FIXTURES / fixture)
    assert analyze_paths([path]) == []
    assert lint_file(path) == []


def test_g011_flags_the_pre_pr6_donated_restore_shape():
    """ISSUE contract: the restore_checkpoint -> device_put zero-copy alias
    donated by the caller must be flagged AT the donating dispatch, naming
    the external ownership."""
    findings = analyze_paths([str(FIXTURES / "g011_violation.py")])
    foreign = [
        f
        for f in findings
        if "externally-owned" in f.message and "restore" in f.message
    ]
    assert foreign, findings
    assert foreign[0].symbol.endswith("resume_and_step")


def test_g012_flags_the_pre_pr5_drain_race_shape():
    """ISSUE contract: close() mutating the pool handle/shutdown flag with
    no lock while the feeder thread reads them must be flagged."""
    findings = analyze_paths([str(FIXTURES / "g012_violation.py")])
    attrs = {f.message.split("`")[1] for f in findings}
    assert "self._pool" in attrs, findings
    assert "self._stopped" in attrs, findings


def test_g013_flags_the_restore_onto_old_mesh_shape():
    findings = analyze_paths([str(FIXTURES / "g013_violation.py")])
    local = [f for f in findings if "STALE" in f.message or "stale" in f.message]
    assert any("device_put" in f.message for f in local), findings


# --------------------------------------------------------- engine unit tests


def test_interprocedural_donation_summary():
    src = (
        "import jax\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def inner(a, b):\n"
        "    return f(a, b)\n"
        "def mid(x, y):\n"
        "    return inner(x, y)\n"
    )
    proj = Project.from_summaries([summarize_source(src, "m.py")])
    graph = CallGraph(proj)
    # donation propagates two levels: inner donates param 0, so does mid
    assert 0 in graph.donated_params["m::inner"]
    assert 0 in graph.donated_params["m::mid"]


def test_lock_env_propagates_through_call_sites():
    """The _ensure_pool_locked idiom: a callee whose every call site holds
    the lock is proven guarded (the g012_clean fixture depends on it)."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "    def _ensure(self):\n"
        "        self._x = 1\n"
        "    def _run(self):\n"
        "        with self._lock:\n"
        "            self._ensure()\n"
        "    def poke(self):\n"
        "        with self._lock:\n"
        "            self._ensure()\n"
        "            self._x = 2\n"
    )
    proj = Project.from_summaries([summarize_source(src, "s.py")])
    graph = CallGraph(proj)
    assert "_lock" in graph.lock_env["s::S._ensure"]
    assert analyze_source(src) == []


def test_lock_env_propagates_through_recursion_cycles():
    """PR-12 satellite (carried since PR 10): a recursive callee whose every
    EXTERNAL call site holds the lock is proven guarded — the in-cycle
    caller starts unknown (⊤) and must act as intersection identity, not
    pin the whole cycle at 'no locks'. Both a self-recursive method and a
    two-function cycle."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "    def _drain(self, n):\n"
        "        self._x = n\n"
        "        if n:\n"
        "            self._drain(n - 1)\n"
        "    def _ping(self, n):\n"
        "        self._x = n\n"
        "        self._pong(n)\n"
        "    def _pong(self, n):\n"
        "        if n:\n"
        "            self._ping(n - 1)\n"
        "    def _run(self):\n"
        "        with self._lock:\n"
        "            self._drain(3)\n"
        "    def poke(self):\n"
        "        with self._lock:\n"
        "            self._drain(2)\n"
        "            self._ping(2)\n"
        "            self._x = 9\n"
    )
    proj = Project.from_summaries([summarize_source(src, "s.py")])
    graph = CallGraph(proj)
    assert "_lock" in graph.lock_env["s::S._drain"]
    assert "_lock" in graph.lock_env["s::S._ping"]
    assert "_lock" in graph.lock_env["s::S._pong"]
    # and the guarded-everywhere verdict silences G012 on self._x
    assert analyze_source(src) == []


def test_lock_env_recursion_requires_external_guard():
    """The cycle inherits only what EVERY external entry holds: an unlocked
    entry into the cycle strips the env (soundness of the greatest
    fixpoint — optimism must not invent locks)."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def _drain(self, n):\n"
        "        if n:\n"
        "            self._drain(n - 1)\n"
        "    def locked(self):\n"
        "        with self._lock:\n"
        "            self._drain(2)\n"
        "    def bare(self):\n"
        "        self._drain(1)\n"
    )
    proj = Project.from_summaries([summarize_source(src, "s.py")])
    graph = CallGraph(proj)
    assert graph.lock_env["s::S._drain"] == frozenset()


def test_spawn_edge_does_not_propagate_locks():
    """Thread(target=...) started under a lock does NOT hold it."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def start(self):\n"
        "        with self._lock:\n"
        "            t = threading.Thread(target=self._run)\n"
        "            t.start()\n"
        "    def _run(self):\n"
        "        self._n = 1\n"
    )
    proj = Project.from_summaries([summarize_source(src, "s.py")])
    graph = CallGraph(proj)
    assert graph.lock_env["s::S._run"] == frozenset()


def test_thread_inventory_sees_nested_closure_targets():
    """The heartbeat/watchdog idiom: the spawned target is a closure
    defined inside a method."""
    src = (
        "import threading\n"
        "class Beacon:\n"
        "    def start(self):\n"
        "        def _beat():\n"
        "            self._beats = self._beats + 1\n"
        "        t = threading.Thread(target=_beat)\n"
        "        t.start()\n"
        "    def read(self):\n"
        "        self._beats = 0\n"
    )
    proj = Project.from_summaries([summarize_source(src, "b.py")])
    graph = CallGraph(proj)
    thread_side, _main = graph.thread_sides()
    assert "b::Beacon.start._beat" in thread_side
    assert codes(analyze_source(src)) == {"G012"}


def test_lock_order_cycle_detected():
    src = (
        "import threading\n"
        "class Pair:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "    def _loop(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def poke(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )
    findings = analyze_source(src)
    assert any("lock-order cycle" in f.message for f in findings), findings


def test_inline_suppression_silences_flow_findings():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def inner(a, b):\n"
        "    return f(a, b)\n"
        "def outer(x, y):\n"
        "    z = inner(x, y)\n"
        "    return jnp.sum(x)  # graftlint: disable=G011\n"
    )
    assert analyze_source(src) == []
    # and without the pragma it fires
    assert codes(analyze_source(src.replace("  # graftlint: disable=G011", ""))) == {
        "G011"
    }


def test_unique_tail_resolution_is_gated():
    """`obj.lower(...)` / `d.update(...)` must not resolve to unrelated
    project functions (the jax/stdlib collision trap)."""
    src_a = "class T:\n    def lower(self):\n        self._x = 1\n"
    src_b = (
        "def use(fn):\n"
        "    lowered = fn.lower()\n"  # jax API, NOT T.lower
        "    return lowered\n"
    )
    proj = Project.from_summaries(
        [summarize_source(src_a, "a.py"), summarize_source(src_b, "b.py")]
    )
    graph = CallGraph(proj)
    assert graph.edges["b::use"] == []


def test_g012_guarded_writer_bare_reader_still_fires():
    """The discipline covers READS too: a writer under the lock with a bare
    reader on the other thread is still the PR-5 race shape."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "    def _run(self):\n"
        "        while self._flag:\n"  # bare cross-thread read
        "            pass\n"
        "    def stop(self):\n"
        "        with self._lock:\n"
        "            self._flag = False\n"  # guarded write
    )
    findings = analyze_source(src)
    assert any("_flag" in f.message for f in findings), findings


def test_thread_target_defined_under_compound_statement():
    """A closure spawned from inside an if/try is still inventoried."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def start(self, fancy):\n"
        "        if fancy:\n"
        "            def _drain():\n"
        "                self._count = 1\n"
        "            threading.Thread(target=_drain).start()\n"
        "    def read(self):\n"
        "        self._count = 0\n"
    )
    proj = Project.from_summaries([summarize_source(src, "s.py")])
    assert "S.start._drain" in proj.modules["s.py"].functions
    assert codes(analyze_source(src)) == {"G012"}


def test_donation_summary_survives_later_rebind():
    """Facts are read at the site they hold: an unrelated later rebind of
    the donated token must not erase the callee's donation summary."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def helper(state, batch):\n"
        "    out = f(state, batch)\n"
        "    state = 0\n"
        "    return out\n"
        "def caller(state, batch):\n"
        "    new = helper(state, batch)\n"
        "    return new, jnp.sum(state)\n"  # donated in helper, read here
    )
    proj = Project.from_summaries([summarize_source(src, "m.py")])
    graph = CallGraph(proj)
    assert 0 in graph.donated_params["m::helper"]
    assert codes(analyze_source(src)) == {"G011"}


def test_g012_disjoint_locks_still_race():
    """Two sides each under a DIFFERENT lock share nothing: still a race."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock_a = threading.Lock()\n"
        "        self._lock_b = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "    def _run(self):\n"
        "        with self._lock_a:\n"
        "            self._count = 1\n"
        "    def read(self):\n"
        "        with self._lock_b:\n"
        "            self._count = 0\n"
    )
    findings = analyze_source(src)
    assert any(
        "_count" in f.message and "does not share" in f.message
        for f in findings
    ), findings


def test_lock_cycle_found_past_a_cycle_free_prefix():
    """A DFS from an acyclic start must not mark the b<->c cycle's edges
    visited and hide it from later starts."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "        self._c = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._f)\n"
        "    def _f(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                with self._c:\n"
        "                    pass\n"
        "    def g(self):\n"
        "        with self._a:\n"
        "            with self._c:\n"
        "                with self._b:\n"
        "                    pass\n"
    )
    findings = analyze_source(src)
    assert any("lock-order cycle" in f.message for f in findings), findings


def test_g011_chained_assignment_aliases_every_target():
    """`snap = keep = state` leaves ALL targets aliased to the buffer."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def window(state, g):\n"
        "    snap = keep = state\n"
        "    state = f(state, g)\n"
        "    return state, jnp.sum(snap)\n"
    )
    assert codes(analyze_source(src)) == {"G011"}


def test_branch_exclusive_alias_does_not_survive_into_other_arm():
    """ROADMAP gap closed: `snap = state` in the fast arm must not make the
    slow arm's donation kill `snap` — the two never coexist on any path."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def window(state, g, flag):\n"
        "    if flag:\n"
        "        snap = state\n"
        "        out = jnp.sum(snap)\n"
        "    else:\n"
        "        snap = jnp.zeros(())\n"
        "        out = f(state, g)\n"
        "    return out, jnp.sum(snap)\n"
    )
    assert analyze_source(src) == []
    # the positive control: same-arm alias + donation still fires
    same_arm = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def window(state, g, flag):\n"
        "    if flag:\n"
        "        snap = state\n"
        "        out = f(state, g)\n"
        "        return out, jnp.sum(snap)\n"
        "    return state, jnp.zeros(())\n"
    )
    assert codes(analyze_source(same_arm)) == {"G011"}


def test_unconditional_alias_survives_exclusive_arm_rebind():
    """A token ALSO bound unconditionally still aliases on the donation
    path — only tokens whose every bind is exclusive with the donation arm
    are branch-filtered (last-write-wins would un-catch the incident)."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def window(state, g, flag):\n"
        "    snap = state\n"
        "    if flag:\n"
        "        snap = state\n"
        "        out = jnp.sum(snap)\n"
        "    else:\n"
        "        out = f(state, g)\n"
        "    return out, jnp.sum(snap)\n"
    )
    assert codes(analyze_source(src)) == {"G011"}


def test_donation_propagates_through_kwargs_forwarding():
    """ROADMAP gap closed: ``outer(**kw)`` forwarding to a donor means
    outer's callers see their explicit keyword arguments die."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def inner(state, batch):\n"
        "    return f(state, batch)\n"
        "def outer(**kw):\n"
        "    return inner(**kw)\n"
        "def top(state, batch):\n"
        "    out = outer(state=state, batch=batch)\n"
        "    return out, jnp.sum(state)\n"
    )
    proj = Project.from_summaries([summarize_source(src, "m.py")])
    graph = CallGraph(proj)
    assert graph.donated_kwnames["m::outer"] == {"state": 7}
    assert 0 in graph.donated_params["m::top"]
    assert codes(analyze_source(src)) == {"G011"}


def test_kwargs_forwarding_skips_own_shadowing_param():
    """An own named param of the forwarder CAPTURES the keyword — the
    caller's ``state=...`` binds it and never reaches **kw, so the caller's
    value is not donated (the copy breaks the chain)."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def inner(state, batch):\n"
        "    return f(state, batch)\n"
        "def outer(state, **kw):\n"
        "    return inner(jnp.array(state, copy=True), **kw)\n"
        "def top(s, batch):\n"
        "    out = outer(state=s, batch=batch)\n"
        "    return out, jnp.sum(s)\n"
    )
    proj = Project.from_summaries([summarize_source(src, "m.py")])
    graph = CallGraph(proj)
    assert "state" not in graph.donated_kwnames["m::outer"]
    assert analyze_source(src) == []


def test_donation_propagates_through_tree_map_lambda():
    """ROADMAP gap closed: a donor dispatched per-leaf from a tree_map
    lambda donates the mapped trees."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "f = jax.jit(lambda s, g: s - g, donate_argnums=(0,))\n"
        "def leaf(s, g):\n"
        "    return f(s, g)\n"
        "def window(state, grads):\n"
        "    snap = state\n"
        "    new = jax.tree_util.tree_map(lambda s, g: leaf(s, g), state, grads)\n"
        "    return new, jnp.sum(snap)\n"
    )
    findings = analyze_source(src)
    assert codes(findings) == {"G011"}, findings


def test_g012_inventories_partial_bound_thread_targets():
    """ROADMAP gap closed: Thread(target=functools.partial(self._run, x))
    and pool.submit(functools.partial(f, a)) resolve their spawn edges."""
    src = (
        "import threading\n"
        "import functools\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._t = threading.Thread(\n"
        "            target=functools.partial(self._run, 3))\n"
        "    def _run(self, n):\n"
        "        self._count = n\n"
        "    def read(self):\n"
        "        self._count = 0\n"
    )
    proj = Project.from_summaries([summarize_source(src, "s.py")])
    graph = CallGraph(proj)
    thread_side, _main = graph.thread_sides()
    assert "s::S._run" in thread_side
    assert codes(analyze_source(src)) == {"G012"}


def test_baseline_keys_agree_across_path_spellings(tmp_path):
    """Absolute and relative invocations of the same file must baseline-
    match (CI writes relative, editors pass absolute)."""
    rel = "tests/fixtures/graftflow/g012_violation.py"
    findings_abs = analyze_paths([str(REPO / rel)])
    findings_rel = analyze_paths([rel])
    assert findings_abs and findings_rel
    path = tmp_path / "b.json"
    write_baseline(str(path), findings_abs)
    assert filter_baselined(findings_rel, load_baseline(str(path))) == []


# ------------------------------------------------------------- CLI satellites


def run_cli(capsys, *argv):
    rc = cli_main(list(argv))
    out = capsys.readouterr().out
    return rc, out


def test_cli_flow_mode_and_select(capsys):
    target = str(FIXTURES / "g012_violation.py")
    rc, out = run_cli(capsys, "--flow", "--no-cache", target)
    assert rc == 1 and "G012" in out
    # --select of a flow code implies flow mode
    rc, out = run_cli(capsys, "--select", "G012", "--no-cache", target)
    assert rc == 1 and "G012" in out
    # selecting an unrelated rule: quiet
    rc, out = run_cli(capsys, "--select", "G001", "--no-cache", target)
    assert rc == 0


def test_cli_ignore(capsys):
    target = str(FIXTURES / "g012_violation.py")
    rc, out = run_cli(capsys, "--flow", "--ignore", "G012", "--no-cache", target)
    assert rc == 0, out
    rc, _ = run_cli(capsys, "--flow", "--ignore", "G999", "--no-cache", target)
    assert rc == 2


def test_cli_json_format(capsys):
    target = str(FIXTURES / "g011_violation.py")
    rc, out = run_cli(capsys, "--flow", "--format", "json", "--no-cache", target)
    assert rc == 1
    data = json.loads(out)
    assert data["count"] == len(data["findings"]) >= 3
    f0 = data["findings"][0]
    assert {"code", "path", "line", "col", "message", "fix_hint", "symbol"} <= set(
        f0
    )


def test_cli_sarif_format(capsys):
    target = str(FIXTURES / "g013_violation.py")
    rc, out = run_cli(capsys, "--flow", "--format", "sarif", "--no-cache", target)
    assert rc == 1
    sarif = json.loads(out)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    results = run["results"]
    assert results and all(r["ruleId"] == "G013" for r in results)
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] >= 1
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "G013" in rule_ids


def test_cli_baseline_roundtrip(tmp_path, capsys):
    target = str(FIXTURES / "g012_violation.py")
    base = str(tmp_path / "baseline.json")
    rc, out = run_cli(
        capsys, "--flow", "--no-cache", "--write-baseline", base, target
    )
    assert rc == 0 and "wrote" in out
    # with the baseline applied the same tree is clean
    rc, out = run_cli(capsys, "--flow", "--no-cache", "--baseline", base, target)
    assert rc == 0, out
    # a NEW finding (different fixture) still fires through the baseline
    other = str(FIXTURES / "g013_violation.py")
    rc, out = run_cli(
        capsys, "--flow", "--no-cache", "--baseline", base, target, other
    )
    assert rc == 1 and "G013" in out and "G012" not in out


def test_baseline_library_roundtrip(tmp_path):
    findings = analyze_paths([str(FIXTURES / "g011_violation.py")])
    path = tmp_path / "b.json"
    write_baseline(str(path), findings)
    keys = load_baseline(str(path))
    assert filter_baselined(findings, keys) == []


# ------------------------------------------------- parallel + cache + budget


def test_parallel_and_cached_runs_agree(tmp_path):
    paths = [str(FIXTURES)]
    cache = str(tmp_path / "cache")
    serial = lint_paths(paths, jobs=1, cache_dir=None, flow=True)
    cold = lint_paths(paths, jobs=2, cache_dir=cache, flow=True)
    warm = lint_paths(paths, jobs=2, cache_dir=cache, flow=True)
    key = lambda fs: [(f.code, f.path, f.line, f.col, f.message) for f in fs]
    assert key(serial) == key(cold) == key(warm)
    # the cache actually materialized summaries + findings
    cached = list(pathlib.Path(cache).iterdir())
    assert any(p.name.endswith(".sum") for p in cached)
    assert any(p.name.endswith(".lint") for p in cached)


def test_flow_self_runtime_budget(tmp_path):
    """ISSUE acceptance: a full-repo `graftlint --flow` must stay cheap
    enough for a tier-1 gate. Cold budget is generous for CI tier noise;
    the warm (cached) run must be decisively faster than the bound."""
    cache = str(tmp_path / "cache")
    t0 = time.perf_counter()
    cold = lint_paths(
        [str(PKG)], jobs=0, cache_dir=cache, flow=True
    )
    cold_s = time.perf_counter() - t0
    assert cold_s < 120.0, f"cold full-repo --flow took {cold_s:.1f}s"
    t0 = time.perf_counter()
    warm = lint_paths(
        [str(PKG)], jobs=0, cache_dir=cache, flow=True
    )
    warm_s = time.perf_counter() - t0
    assert warm_s < 60.0, f"warm full-repo --flow took {warm_s:.1f}s"
    key = lambda fs: [(f.code, f.path, f.line, f.message) for f in fs]
    assert key(cold) == key(warm)
