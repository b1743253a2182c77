"""graftmesh (whole-program sharding/collective semantics) tests: the G014-
G016 rule families must trip on their seeded fixtures — including minimized
reproductions of BOTH motivating incidents (PR 6's restore-onto-the-old-mesh
placement, caught one function boundary deeper than G013 sees, and the
fused-AOT lowering-spec vs dispatch-seed placement mismatch) — the clean
twins must stay quiet, the MeshModel engine (axis universe, mesh-environment
lattice, required-axes fixpoint, spec identities) must hold its contracts,
and the pass must stay inside graftflow's runtime budget.
"""

import pathlib
import time

import pytest

from dynamic_load_balance_distributeddnn_tpu.analysis.flow import (
    CallGraph,
    Project,
    analyze_paths,
    analyze_source,
    summarize_source,
)
from dynamic_load_balance_distributeddnn_tpu.analysis.flow.mesh import (
    MeshModel,
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


def model_of(src: str, path: str = "m.py") -> MeshModel:
    proj = Project.from_summaries([summarize_source(src, path)])
    return MeshModel(proj, CallGraph(proj))


# ------------------------------------------------------------ seeded fixtures


@pytest.mark.parametrize(
    "fixture,expected_code,min_findings",
    [
        # unknown axis + shard_map supply/demand + elastic cfg size
        ("g014_violation.py", "G014", 4),
        # cross-boundary stale spec + lowering-vs-dispatch mismatch
        ("g015_violation.py", "G015", 2),
        # local unequal-shard sink + interprocedural param sink
        ("g016_violation.py", "G016", 3),
        # plan taint through self-attrs + container-element mutation
        # (ISSUE 11 satellite: the window controller stores plan-derived
        # sizes on `self` and packs columns into lists)
        ("g016_attr_violation.py", "G016", 3),
        # axis-param override channel must EXTEND the universe, not disarm
        # the rule (PR-12 satellite fixture pair)
        ("g014_override_violation.py", "G014", 1),
        # per-executable-key registered-lowering matching: a spec
        # registered for executable B must not sanction a mismatched
        # placement dispatched to executable A (PR-12 satellite)
        ("g015_key_violation.py", "G015", 1),
        # axis-tuple VARIABLES in collective axis args resolve through the
        # local bind — the hier combine's self._axis_arg class of
        # spellings no longer errs quiet (PR-13 satellite)
        ("g014_tuplevar_violation.py", "G014", 2),
        # plan taint through dict-VALUE iteration (.values() / .items()
        # tuple targets) — the last recorded modeling gap (PR-13 satellite)
        ("g016_dictval_violation.py", "G016", 2),
        # ATTRIBUTE-valued axis spellings (ISSUE 14 satellite): an opaque
        # self._axis_arg property is an explicit "unresolved axis
        # expression" finding, a literal-returning property feeds the
        # ordinary unknown-axis check, and an UNRELATED axis_names read in
        # the body must not silence an opaque return (review hardening)
        ("g014_attrprop_violation.py", "G014", 3),
        # N-tuple collective axes (ISSUE 17): the tree combine's 3- and
        # 4-member axis tuples resolve member-by-member — a typo'd middle
        # member, a stale sub-tuple bind, and an undeclared-level
        # axis_index all trip
        ("g014_ntuple_violation.py", "G014", 3),
    ],
)
def test_mesh_rule_trips_on_seeded_fixture(fixture, expected_code, min_findings):
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
        "g014_clean.py",
        "g015_clean.py",
        "g016_clean.py",
        "g016_attr_clean.py",
        "g014_override_clean.py",
        "g015_key_clean.py",
        "g014_tuplevar_clean.py",
        "g016_dictval_clean.py",
        "g014_attrprop_clean.py",
        "g014_ntuple_clean.py",
    ],
)
def test_clean_fixture_is_quiet(fixture):
    path = str(FIXTURES / fixture)
    assert analyze_paths([path]) == []
    assert lint_file(path) == []


def test_axis_param_override_extends_universe_and_value_env():
    """PR-12 satellite: a call-site literal override of a DEFAULTED axis
    param must enter the axis universe AND the bound mesh's value
    environment — previously invisible, so every collective over the
    override axis was a false G014."""
    src = (
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def build(devices, axis='data'):\n"
        "    return Mesh(np.array(devices), (axis,))\n"
        "def use(devices):\n"
        "    mesh = build(devices, axis='model')\n"
        "    return mesh\n"
    )
    model = model_of(src)
    assert model.axis_universe == {"data", "model"}
    assert model.axis_universe_complete
    fn = model.project.functions["m::use"]
    assert model.mesh_axes_of_token(fn, "mesh") == {"model"}
    # the callee's own default-resolved return is unchanged
    assert model.mesh_returns["m::build"] == frozenset({"data"})


def test_axis_tuple_variable_resolves_through_local_bind():
    """PR-13 satellite: a collective whose axis argument is a VARIABLE
    bound to a tuple (or string) literal resolves through the local bind —
    constants in the tuple resolve too; attribute-valued binds and later
    opaque rebinds stay unresolved (errs quiet)."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "H = 'host'\n"
        "def make(devices):\n"
        "    return Mesh(np.array(devices), (H, 'device'))\n"
        "def combine(x):\n"
        "    axes = (H, 'device')\n"
        "    return jax.lax.psum(x, axes)\n"
        "def strvar(x):\n"
        "    ax = 'host'\n"
        "    return jax.lax.axis_index(ax) + x\n"
        "def opaque(obj, x):\n"
        "    axes = obj.batch_axes\n"
        "    return jax.lax.psum(x, axes)\n"
        "def rebound(obj, x):\n"
        "    axes = (H,)\n"
        "    axes = obj.batch_axes\n"
        "    return jax.lax.psum(x, axes)\n"
    )
    model = model_of(src)
    assert model.required_axes["m::combine"] == {"host", "device"}
    assert model.required_axes["m::strvar"] == {"host"}
    assert model.required_axes["m::opaque"] == set()
    assert model.required_axes["m::rebound"] == set()  # rebind forgets


def test_attr_axis_property_resolution_channels():
    """ISSUE 14 satellite: ``self.<attr>`` collective-axis spellings
    resolve through simple property returns — a literal joins the demand,
    a chained property resolves through its target, a live-mesh
    ``axis_names`` derivation contributes no demand (consistent by
    construction), and an opaque property lands in
    ``unresolved_axis_sites`` instead of erring quiet."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        "    return Mesh(np.array(devices), ('data',))\n"
        "def batch_axes(mesh):\n"
        "    names = tuple(mesh.axis_names)\n"
        "    return names[0] if len(names) == 1 else names\n"
        "class Steps:\n"
        "    def __init__(self, mesh):\n"
        "        self.mesh = mesh\n"
        "    @property\n"
        "    def lit(self):\n"
        "        return 'data'\n"
        "    @property\n"
        "    def chained(self):\n"
        "        return self.lit\n"
        "    @property\n"
        "    def derived(self):\n"
        "        return batch_axes(self.mesh)\n"
        "    @property\n"
        "    def opaque(self):\n"
        "        return ''.join(['da', 'ta'])\n"
        "    def c_lit(self, x):\n"
        "        return jax.lax.psum(x, self.lit)\n"
        "    def c_chained(self, x):\n"
        "        return jax.lax.psum(x, self.chained)\n"
        "    def c_derived(self, x):\n"
        "        return jax.lax.psum(x, self.derived)\n"
        "    def c_opaque(self, x):\n"
        "        return jax.lax.psum(x, self.opaque)\n"
    )
    model = model_of(src)
    assert model.required_axes["m::Steps.c_lit"] == {"data"}
    assert model.required_axes["m::Steps.c_chained"] == {"data"}
    assert model.required_axes["m::Steps.c_derived"] == set()
    assert model.required_axes["m::Steps.c_opaque"] == set()
    sites = [
        (fqn, tok) for fqn, _l, _c, _t, tok in model.unresolved_axis_sites
    ]
    assert sites == [("m::Steps.c_opaque", "self.opaque")]


def test_two_level_axis_universe_and_tuple_collectives():
    """ISSUE 12: the (host, device) factorization is modeled — the hier
    mesh helper's constants enter the universe, and a tuple-literal
    collective axis (``psum(x, ("host", "device"))``, the two-level
    combine's spelling) demands BOTH member axes."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "HOST_AXIS = 'host'\n"
        "DEVICE_AXIS = 'device'\n"
        "def hier_mesh(devices, hosts, host_axis=HOST_AXIS,"
        " device_axis=DEVICE_AXIS):\n"
        "    arr = np.array(devices)\n"
        "    return Mesh(arr, (host_axis, device_axis))\n"
        "def combine(tree):\n"
        "    return jax.lax.psum(tree, ('host', 'device'))\n"
        "def hop(v):\n"
        "    return jax.lax.psum(v, 'host')\n"
    )
    model = model_of(src)
    assert {"host", "device"} <= model.axis_universe
    assert model.required_axes["m::combine"] == {"host", "device"}
    assert model.required_axes["m::hop"] == {"host"}


def test_g015_key_scoping_narrows_but_falls_back_class_wide():
    """Per-executable-key matching: key literals are harvested only from
    registry-call tuple arguments, a keyed dispatch checks against its own
    key's scopes, and a key-less dispatch keeps the class-wide union."""
    from dynamic_load_balance_distributeddnn_tpu.analysis.flow.mesh import (
        RuleG015,
    )

    viol = (FIXTURES / "g015_key_violation.py").read_text()
    clean = (FIXTURES / "g015_key_clean.py").read_text()
    proj = Project.from_summaries([summarize_source(viol, "v.py")])
    lits = RuleG015._key_literals(
        [proj.functions["v::Engine._submit_fused"]]
    )
    assert lits == {"fused"}
    assert RuleG015._key_literals(
        [proj.functions["v::Engine._dispatch_fused"]]
    ) == {"fused"}
    assert [f.code for f in analyze_source(viol)] == ["G015"]
    assert analyze_source(clean) == []


def test_g015_flags_restore_onto_old_mesh_across_boundary():
    """ISSUE acceptance (a): the PR-6 restore-onto-the-old-mesh placement,
    minimized with the spec obtained THROUGH a helper so G013's local
    mesh-capture rule is blind — exactly one of G014-G016 must flag it."""
    findings = analyze_paths([str(FIXTURES / "g015_violation.py")])
    stale = [f for f in findings if "STALE" in f.message]
    assert stale, findings
    assert stale[0].symbol.endswith("Engine.resume")
    assert codes(findings) == {"G015"}


def test_g015_flags_lowering_vs_dispatch_mismatch():
    """ISSUE acceptance (b): the fused-AOT lowering-spec vs dispatch-seed
    placement mismatch — the dispatch placement's spec identity is not in
    the class's registered lowering set."""
    findings = analyze_paths([str(FIXTURES / "g015_violation.py")])
    mism = [f for f in findings if "registered" in f.message]
    assert mism, findings
    assert mism[0].symbol.endswith("Engine")


# ----------------------------------------------------------- MeshModel units


def test_axis_universe_resolves_constants_and_param_defaults():
    src = (
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        'DATA_AXIS = "data"\n'
        "def data_mesh(devices, axis=DATA_AXIS):\n"
        "    return Mesh(np.array(devices), (axis,))\n"
        "def build(devices):\n"
        "    return data_mesh(devices)\n"
    )
    model = model_of(src)
    assert model.axis_universe == {"data"}
    # the helper's defaulted axis resolves through the constant table
    assert model.helper_axis_default["data_mesh"] == "data"


def test_unknown_collective_axis_fires_and_known_is_quiet():
    base = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def combine(tree):\n"
        '    return jax.lax.psum(tree, "{axis}")\n'
    )
    bad = analyze_source(base.format(axis="dat"))
    assert codes(bad) == {"G014"}, bad
    assert analyze_source(base.format(axis="data")) == []


def test_one_finding_per_typoed_spec():
    """The same bad construction surfaces through bind.spec, its CallFact,
    the nested P call, and spec_args — exactly ONE finding must emerge."""
    src = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def build(mesh):\n"
        '    s = NamedSharding(mesh, P("dat"))\n'
        "    return s\n"
    )
    findings = analyze_source(src)
    assert [f.code for f in findings] == ["G014"], findings


def test_incomplete_axis_universe_stays_quiet():
    """A mesh construction with dynamic (unresolvable) axes marks the
    universe incomplete: membership checks must not guess — the dropped
    mesh may define any axis (the errs-quiet contract)."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def make_dyn(devices, names):\n"
        "    return Mesh(np.array(devices), names)\n"
        "def combine(tree):\n"
        '    return jax.lax.psum(tree, "model")\n'
    )
    assert analyze_source(src) == []


def test_mesh_param_lattice_joins_over_call_sites():
    """A mesh-typed parameter's axes are the union of every mesh its
    resolved callers pass — the mesh-environment lattice join."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def use(mesh):\n"
        "    return mesh\n"
        "def a(devices):\n"
        '    m = Mesh(np.array(devices), ("data",))\n'
        "    return use(m)\n"
        "def b(devices):\n"
        '    m = Mesh(np.array(devices), ("data", "model"))\n'
        "    return use(m)\n"
    )
    model = model_of(src)
    assert model.param_mesh_axes[("m::use", "mesh")] == {"data", "model"}


def test_mesh_returns_resolve_through_wrapper_chains():
    """``get()`` forwarding ``make()``'s mesh must still supply axes to the
    shard_map check — the fixpoint chases call edges, not just direct
    constructions."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def get(devices):\n"
        "    m = make(devices)\n"
        "    return m\n"
        "def body(x):\n"
        '    return jax.lax.psum(x, "model")\n'
        "def wire(devices):\n"
        "    mesh = get(devices)\n"
        "    return jax.shard_map(body, mesh=mesh, in_specs=None, out_specs=None)\n"
    )
    model = model_of(src)
    assert model.mesh_returns["m::get"] == frozenset({"data"})
    findings = analyze_source(src)
    assert any("shard_map" in f.message for f in findings), findings


def test_mesh_resolution_stops_at_the_use_site():
    """A mesh rebind AFTER a shard_map must not shadow the mesh the call
    actually received — local resolution is bounded by the use line."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def body(x):\n"
        '    return jax.lax.psum(x, "model")\n'
        "def wire(devices, sub):\n"
        '    mesh = Mesh(np.array(devices), ("data", "model"))\n'
        "    out = jax.shard_map(body, mesh=mesh, in_specs=None, out_specs=None)\n"
        '    mesh = Mesh(np.array(sub), ("data",))\n'
        "    return out, mesh\n"
    )
    assert analyze_source(src) == []


def test_g015_helper_obtained_registration_specs_count():
    """Registration symmetry: a spec lowered under a spec-returning helper
    (the sds/win_spec idiom) is registered — dispatching under the same
    helper's spec must not flag."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "class Engine:\n"
        "    def _sh(self):\n"
        '        return NamedSharding(self.mesh, P("data"))\n'
        "    def _submit_aot(self, state):\n"
        "        seed_t = jax.ShapeDtypeStruct(\n"
        "            (), jnp.int32, sharding=NamedSharding(self.mesh, P()))\n"
        "        win = self._sh()\n"
        "        win_t = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=win)\n"
        '        self._aot.submit(("fused", 0), state, (seed_t, win_t))\n'
        "    def _dispatch(self, x):\n"
        "        sp = self._sh()\n"
        "        return jax.device_put(x, sp)\n"
    )
    assert analyze_source(src) == []


def test_required_axes_propagate_bottom_up():
    src = (
        "import jax\n"
        "def leaf(x):\n"
        '    return jax.lax.psum(x, "data")\n'
        "def mid(x):\n"
        "    return leaf(x)\n"
        "def top(x):\n"
        "    return mid(x)\n"
    )
    model = model_of(src)
    assert model.required_axes["m::top"] == {"data"}


def test_shard_map_over_partial_wrapped_target():
    """The repo idiom: shard_map(functools.partial(fn, ...), mesh=...) —
    the partial's bound callable is the demand side."""
    src = (
        "import jax\n"
        "import functools\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def body(x, causal=True):\n"
        '    return jax.lax.psum(x, "model")\n'
        "def wire(devices):\n"
        '    mesh = Mesh(np.array(devices), ("data", "model"))\n'
        '    small = Mesh(np.array(devices), ("data",))\n'
        "    return jax.shard_map(\n"
        "        functools.partial(body, causal=False),\n"
        "        mesh=small, in_specs=None, out_specs=None)\n"
    )
    findings = analyze_source(src)
    assert any(
        f.code == "G014" and "shard_map" in f.message for f in findings
    ), findings


def test_elastic_reshard_axis_rebind_unit():
    """The elastic contract: _reshard_world rebuilds the mesh from RUNTIME
    state. Sizing a placed vector from self.world_size (which the re-shard
    rebinds) is clean; sizing it from cfg.world_size fires."""
    base = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "class Engine:\n"
        "    def __init__(self, cfg, devices):\n"
        "        self.cfg = cfg\n"
        "        self.world_size = cfg.world_size\n"
        '        self.mesh = Mesh(np.array(devices), ("data",))\n'
        "    def _reshard_world(self, active):\n"
        "        self.world_size = len(active)\n"
        '        self.mesh = Mesh(np.array(active), ("data",))\n'
        "    def stage(self):\n"
        "        slow = np.zeros({size}, np.int32)\n"
        "        return jax.device_put(slow, NamedSharding(self.mesh, P()))\n"
    )
    clean = base.format(size="self.world_size")
    assert analyze_source(clean) == [], analyze_source(clean)
    dirty = base.format(size="self.cfg.world_size")
    findings = analyze_source(dirty)
    assert any(
        f.code == "G014" and "world_size" in f.message for f in findings
    ), findings


def test_world_size_gated_placement_is_not_a_sizing():
    """Gating a placement on cfg.world_size is not SIZING by it — the sink
    only fires when its own arguments carry the cfg-sized value."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "class Engine:\n"
        "    def __init__(self, cfg, devices):\n"
        "        self.cfg = cfg\n"
        '        self.mesh = Mesh(np.array(devices), ("data",))\n'
        "    def _reshard_world(self, active):\n"
        '        self.mesh = Mesh(np.array(active), ("data",))\n'
        "    def place(self, x):\n"
        "        sh = NamedSharding(self.mesh, P())\n"
        "        return jax.device_put(x, sh) if self.cfg.world_size > 1 else x\n"
    )
    assert analyze_source(src) == []


def test_spec_returns_cross_function_resolution():
    src = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "class E:\n"
        "    def _sh(self):\n"
        '        return NamedSharding(self.mesh, P("data"))\n'
        "    def _sh2(self):\n"
        "        s = self._sh()\n"
        "        return s\n"
    )
    model = model_of(src)
    assert model.spec_returns["m::E._sh"] == (("sharding", ("data",)), True)
    assert model.spec_returns["m::E._sh2"] == (("sharding", ("data",)), True)


def test_g015_gen_keyed_placement_is_sanctioned():
    """A placement whose statement carries the _aot_gen generation marker
    is sanctioned — the same model G013 uses (stale keys can never hit)."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "class Engine:\n"
        "    def _sh(self):\n"
        "        return NamedSharding(self.mesh, P())\n"
        "    def _reshard_world(self, active):\n"
        '        self.mesh = Mesh(np.array(active), ("data",))\n'
        "        self._aot_gen += 1\n"
        "    def resume(self, ckpt, active):\n"
        "        sh = self._sh()\n"
        "        self._reshard_world(active)\n"
        "        return jax.device_put(ckpt.state, sh), self._aot_gen\n"
    )
    assert analyze_source(src) == []
    # and without the marker it fires
    bare = src.replace(", self._aot_gen\n", "\n")
    assert codes(analyze_source(bare)) == {"G015"}


def test_g016_cleanse_through_quantize_markers():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def epoch(shares, global_batch, bucket):\n"
        "    batches = integer_batch_split(shares, global_batch)\n"
        "    snapped = quantize_batches(batches, bucket, global_batch)\n"
        '    return jax.lax.all_gather(snapped, "data")\n'
    )
    assert analyze_source(src) == []
    raw = src.replace(
        "snapped = quantize_batches(batches, bucket, global_batch)",
        "snapped = batches",
    )
    assert codes(analyze_source(raw)) == {"G016"}


def test_g016_interprocedural_param_sink():
    """The taint and the collective live in different functions: the
    finding lands at the CALL site handing the raw plan widths over."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def gather_all(vec):\n"
        '    return jax.lax.all_gather(vec, "data")\n'
        "def epoch(shares, global_batch):\n"
        "    batches = integer_batch_split(shares, global_batch)\n"
        "    return gather_all(batches)\n"
    )
    findings = analyze_source(src)
    assert [f.code for f in findings] == ["G016"], findings
    assert findings[0].line == 10


def test_g016_taint_climbs_multi_level_call_chains():
    """A param handed straight into a callee's sink position keeps the
    chain climbing: top -> mid -> helper -> all_gather still flags top."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def helper(x):\n"
        '    return jax.lax.all_gather(x, "data")\n'
        "def mid(v):\n"
        "    return helper(v)\n"
        "def top(shares, global_batch):\n"
        "    batches = integer_batch_split(shares, global_batch)\n"
        "    return mid(batches)\n"
    )
    findings = analyze_source(src)
    assert [f.code for f in findings] == ["G016"], findings
    assert findings[0].line == 12


def test_g016_taint_flows_through_self_attrs():
    """ISSUE 11 satellite: a plan-derived value stored on ``self`` in one
    method and sunk in ANOTHER method of the same class must flag — and the
    quantized twin must stay quiet (cleanse at the attr write)."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "class Ctl:\n"
        "    def plan(self, shares, global_batch):\n"
        "        self._sizes = integer_batch_split(shares, global_batch)\n"
        "    def flush(self, parts):\n"
        "        cols = [p[:b] for p, b in zip(parts, self._sizes)]\n"
        "        return jnp.stack(cols)\n"
    )
    findings = analyze_source(src)
    assert codes(findings) == {"G016"}, findings
    clean = src.replace(
        "self._sizes = integer_batch_split(shares, global_batch)",
        "self._sizes = quantize_batches(\n"
        "            integer_batch_split(shares, global_batch), 8, global_batch)",
    )
    assert analyze_source(clean) == []


def test_g016_taint_flows_through_container_mutation():
    """``cols.append(batches)`` then ``jnp.stack(cols)`` is the same bug as
    stacking the raw widths directly — mutation taints the receiver (local
    containers and self-attr containers alike); appending a quantized value
    stays quiet."""
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def epoch(shares, global_batch):\n"
        "    cols = []\n"
        "    batches = integer_batch_split(shares, global_batch)\n"
        "    cols.append(batches)\n"
        "    return jnp.stack(cols)\n"
    )
    findings = analyze_source(src)
    assert codes(findings) == {"G016"}, findings
    clean = src.replace(
        "cols.append(batches)",
        "cols.append(quantize_batches(batches, 8, global_batch))",
    )
    assert analyze_source(clean) == []


def test_g016_subscript_store_unions_container_taint():
    """An element store into a container neither replaces nor (when clean)
    un-taints it: ``d[0] = raw`` taints, and a later clean element store
    must not wash the earlier taint away."""
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def epoch(shares, global_batch, other):\n"
        "    cols = {}\n"
        "    cols[0] = integer_batch_split(shares, global_batch)\n"
        "    cols[1] = other\n"
        "    return jnp.stack(list(cols.values()))\n"
    )
    findings = analyze_source(src)
    assert codes(findings) == {"G016"}, findings


def test_inline_suppression_silences_mesh_findings():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def make(devices):\n"
        '    return Mesh(np.array(devices), ("data",))\n'
        "def combine(tree):\n"
        '    return jax.lax.psum(tree, "dcn")  # graftlint: disable=G014\n'
    )
    assert analyze_source(src) == []


# ------------------------------------------------- runtime budget (tier-1)


def test_mesh_self_runtime_budget(tmp_path):
    """ISSUE acceptance: the full-repo --flow run including G014-G016 must
    stay within 2x of graftflow's budget (cold) and the cached warm run
    decisively under it. Bounds mirror tests/test_graftflow.py."""
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
