"""graftlint rules G001-G008.

Each rule encodes one structural TPU/JAX perf-bug class this repo has
actually shipped (the motivating incident is listed in README "Static
analysis"). Rules are syntactic and single-file: they know the repo's idioms
(``self.steps.worker_step_first``, ``snap_to_bucket``, the bucket ladder) and
trade exhaustive soundness for zero-noise precision — a finding should always
be worth reading.

Suppress a deliberate violation inline with ``# graftlint: disable=G001``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from dynamic_load_balance_distributeddnn_tpu.analysis.astutil import (
    assign_targets,
    call_name,
    decorator_names,
    dotted_name,
    enclosing_functions,
    enclosing_loop,
    identifiers_in,
    is_jit_construction,
    jit_kwarg,
    literal_int_tuple,
)

def _finding(code, ctx, node, message, fix_hint):
    # local import: linter.py imports this module at its own import time
    from dynamic_load_balance_distributeddnn_tpu.analysis.linter import Finding

    return Finding(
        code=code,
        path=ctx.path,
        line=getattr(node, "lineno", 0),
        col=getattr(node, "col_offset", 0),
        message=message,
        fix_hint=fix_hint,
    )


def Finding_at(code, ctx, line, col, message, fix_hint):
    """_finding for IR facts, which carry (line, col) instead of AST nodes."""
    from dynamic_load_balance_distributeddnn_tpu.analysis.linter import Finding

    return Finding(
        code=code, path=ctx.path, line=line, col=col,
        message=message, fix_hint=fix_hint,
    )


# --------------------------------------------------------------------------
# Shared repo knowledge

# StepLibrary executables: calling one of these attributes dispatches a
# compiled XLA program (engine/bench call them via ``self.steps.<name>``).
KNOWN_STEP_ATTRS = {
    "worker_step_first",
    "worker_step_acc",
    "worker_step_first_idx",
    "worker_step_acc_idx",
    "worker_step_first_win",
    "worker_step_acc_win",
    "worker_step_first_win_idx",
    "worker_step_acc_win_idx",
    "group_superstep",
    "group_superstep_idx",
    "combine_update",
    "combine_probe",
    "fused_step",
    "fused_epoch",
    "fused_epoch_idx",
    "fused_step_probe",
    "fused_step_nocomm",
    "comm_probe",
    "fused_eval_step",
}

# StepLibrary executables that donate input buffers (steps.py donate_argnums),
# keyed by attribute name -> donated positional indices.
KNOWN_DONOR_ATTRS: Dict[str, Tuple[int, ...]] = {
    "combine_update": (0, 1),
    "fused_step": (0,),
    "fused_epoch": (0,),
    "fused_epoch_idx": (0,),
    "worker_step_acc": (1,),
    "worker_step_acc_idx": (1,),
    "worker_step_acc_win": (1,),
    "worker_step_acc_win_idx": (1,),
    "group_superstep": (0,),
    "group_superstep_idx": (0,),
}

_CLOCK_CALLS = {
    "time.time",
    "time.perf_counter",
    "time.monotonic",
    "perf_counter",
    "monotonic",
}

_SYNC_TAILS = ("block_until_ready", "device_get", "item", "effects_barrier")
_SYNC_NAMES = {"float", "np.asarray", "numpy.asarray", "np.array", "numpy.array"}

_TRACE_ENTRY_TAILS = (
    "jax.jit",
    "jit",
    "pjit",
    "jax.pjit",
    "shard_map",
    "jax.shard_map",
    "jax.vmap",
    "vmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.lax.scan",
    "lax.scan",
    "jax.lax.cond",
    "lax.cond",
    "jax.lax.while_loop",
    "lax.while_loop",
    "jax.lax.fori_loop",
    "lax.fori_loop",
    "jax.lax.switch",
    "lax.switch",
)

# Names whose presence in an expression marks its value as living on a
# sanctioned shape discipline (G003). Vision: the bucket ladder (planner/
# quantizer surface plus the engine's capacity-width properties). LM/SP
# (ISSUE 2 satellite — the rule used to model only the vision ladder): the
# column-batch/bptt-window channel — shapes must flow through batchify/
# bptt_windows (window length discipline, pad_bsz column padding) or
# shard_tokens (the SP mesh split), not reach a compiled shape raw.
_BUCKET_MARKERS = {
    "bucket",
    "snap_to_bucket",
    "quantize_batches",
    "ladder",
    "_cap_b",
    "cap_b",
    "_cap_packed",
    "cap_packed",
    "padded_batch",
    "pad_to",
    # LM/SP discipline channels
    "batchify",
    "bptt_windows",
    "pad_bsz",
    "shard_tokens",
}
# Raw shape-determining values: the global batch knob and the solver's raw
# per-worker split (LM column counts derive from it before padding).
_BATCH_SOURCES = {"batch_size", "batch_sizes"}

_SHAPE_BUILDERS = {
    "np.zeros",
    "numpy.zeros",
    "jnp.zeros",
    "np.ones",
    "numpy.ones",
    "jnp.ones",
    "np.full",
    "numpy.full",
    "jnp.full",
    "np.empty",
    "numpy.empty",
    "np.pad",
    "numpy.pad",
    "jnp.pad",
    "_dummy_batch",
}


def _attr_tail(name: Optional[str]) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def _is_steps_attr(name: Optional[str]) -> bool:
    if not name:
        return False
    return ".steps." in name or _attr_tail(name) in KNOWN_STEP_ATTRS


def _rhs_binds_jitted(value: ast.expr) -> bool:
    """Does this assignment RHS produce a jitted/compiled callable?

    jax.jit(...) itself, a StepLibrary executable attribute, a builder-idiom
    call (``make_*``/``build_*`` returning a jitted callable), or a
    conditional expression choosing between such values."""
    if isinstance(value, ast.Call):
        if is_jit_construction(value):
            return True
        name = call_name(value)
        tail = _attr_tail(name)
        if tail.startswith(("make_", "build_")):
            return True
        return False
    if isinstance(value, ast.Attribute):
        return _is_steps_attr(dotted_name(value))
    if isinstance(value, ast.IfExp):
        return _rhs_binds_jitted(value.body) or _rhs_binds_jitted(value.orelse)
    return False


def _jit_bound_names(tree: ast.Module) -> Set[str]:
    """Every (possibly dotted) name the module ever binds to a jitted
    callable. Module-wide and flow-insensitive — good enough for a linter."""
    bound: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _rhs_binds_jitted(node.value):
            for target in node.targets:
                name = dotted_name(target)
                if name:
                    bound.add(name)
    return bound


def _is_dispatch_call(node: ast.Call, jit_bound: Set[str]) -> bool:
    name = call_name(node)
    if name is None:
        # jax.jit(f)(x): the callee is itself a jit construction
        return isinstance(node.func, ast.Call) and is_jit_construction(node.func)
    if name in jit_bound:
        return True
    return _is_steps_attr(name)


def _is_sync_call(node: ast.Call) -> bool:
    # method spelling works on any receiver, resolvable or not:
    # fn(args).block_until_ready(), arr.item(), ...
    if isinstance(node.func, ast.Attribute) and node.func.attr in _SYNC_TAILS:
        return True
    name = call_name(node)
    if name is None:
        return False
    return name in _SYNC_NAMES or _attr_tail(name) in _SYNC_TAILS


def _innermost_function(node: ast.AST, parents) -> Optional[ast.AST]:
    chain = enclosing_functions(node, parents)
    return chain[0] if chain else None


def _function_calls(fn: ast.AST, parents) -> List[ast.Call]:
    """Call nodes whose innermost enclosing function is ``fn`` itself (nested
    defs and lambdas are their own scopes and analyzed separately)."""
    return [
        n
        for n in ast.walk(fn)
        if isinstance(n, ast.Call) and _innermost_function(n, parents) is fn
    ]


# --------------------------------------------------------------------------
# G001 — jit construction in a hot scope


class RuleG001:
    code = "G001"
    summary = "jax.jit/pjit constructed inside a per-call function or loop body"
    fix_hint = (
        "hoist the jit construction to module scope, __init__, or a cached "
        "builder (functools.cached_property/lru_cache) so the executable "
        "compiles once instead of per call"
    )

    _ALLOWED_NAMES = {"__init__", "__post_init__", "setup", "__init_subclass__"}
    _ALLOWED_PREFIXES = ("build", "_build", "make_", "_make", "create_", "_create")
    _ALLOWED_DECORATORS = {
        "cached_property",
        "functools.cached_property",
        "lru_cache",
        "functools.lru_cache",
        "cache",
        "functools.cache",
    }

    def _scope_allowed_shallow(self, fn: ast.AST) -> bool:
        if isinstance(fn, ast.Lambda):
            return False
        name = fn.name
        if name in self._ALLOWED_NAMES or name.startswith(self._ALLOWED_PREFIXES):
            return True
        return bool(set(decorator_names(fn)) & self._ALLOWED_DECORATORS)

    def _scope_allowed(
        self,
        fn: ast.AST,
        ctx,
        memo: Dict[ast.AST, bool],
        stack: Set[ast.AST],
    ) -> bool:
        """A scope is setup-safe if it IS a setup scope, or every call site of
        it in this module sits inside a setup-safe scope (transitively) — the
        ``_fused_probe``-called-from-cached_property pattern."""
        if fn in memo:
            return memo[fn]
        if fn in stack:  # recursion: cannot prove, disallow
            return False
        if self._scope_allowed_shallow(fn):
            memo[fn] = True
            return True
        if isinstance(fn, ast.Lambda):
            memo[fn] = False
            return False
        stack.add(fn)
        try:
            sites = [
                c
                for c in ast.walk(ctx.tree)
                if isinstance(c, ast.Call) and _attr_tail(call_name(c)) == fn.name
            ]
            if not sites:
                memo[fn] = False
                return False
            for site in sites:
                enclosing = _innermost_function(site, ctx.parents)
                if enclosing is None:
                    continue  # module-scope call site: setup by definition
                if not self._scope_allowed(enclosing, ctx, memo, stack):
                    memo[fn] = False
                    return False
            memo[fn] = True
            return True
        finally:
            stack.discard(fn)

    def check(self, ctx) -> Iterator["Finding"]:
        memo: Dict[ast.AST, bool] = {}
        sites: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and is_jit_construction(node):
                # skip bare functools.partial(jax.jit, ...) used as a
                # decorator — the decorated def is handled below
                parent = ctx.parents.get(node)
                if (
                    isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node in parent.decorator_list
                ):
                    continue
                sites.append((node, "jit construction"))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                jit_tails = {"jax.jit", "jit", "pjit", "jax.pjit"}
                if set(decorator_names(node)) & jit_tails:
                    sites.append((node, f"@jit-decorated def {node.name}"))

        for node, what in sites:
            fn = _innermost_function(node, ctx.parents)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and fn is node:
                fn = _innermost_function(ctx.parents.get(node), ctx.parents)
            loop = enclosing_loop(node, ctx.parents, stop_at=fn)
            if loop is not None:
                yield _finding(
                    self.code,
                    ctx,
                    node,
                    f"{what} inside a loop body recompiles every iteration",
                    self.fix_hint,
                )
                continue
            if fn is None:
                continue  # module/class scope: compiled once per import
            if not self._scope_allowed(fn, ctx, memo, set()):
                yield _finding(
                    self.code,
                    ctx,
                    node,
                    f"{what} inside `{getattr(fn, 'name', '<lambda>')}` "
                    "(a per-call scope): each call builds a fresh wrapper and "
                    "recompiles — the engine.py _probe_workers `tiny` bug class",
                    self.fix_hint,
                )


# --------------------------------------------------------------------------
# G002 — wall-clock window spans a dispatch with no sync on the timed path


class RuleG002:
    code = "G002"
    summary = "wall-clock timing spans a dispatched JAX call with no sync"
    fix_hint = (
        "call jax.block_until_ready(...) (or read the value back with "
        "float()/device_get) on the dispatched result before taking the "
        "closing timestamp — async dispatch returns immediately and the "
        "wall measures nothing"
    )

    @staticmethod
    def _is_clock_call(node: ast.expr) -> bool:
        return isinstance(node, ast.Call) and call_name(node) in _CLOCK_CALLS

    def _windows(self, fn: ast.AST, ctx) -> List[Tuple[str, int, int]]:
        """(varname, start_line, end_line) spans: ``t0 = clock()`` up to the
        nearest later use of ``clock() - t0``."""
        starts: List[Tuple[str, int]] = []
        deltas: List[Tuple[str, int]] = []
        for node in ast.walk(fn):
            if _innermost_function(node, ctx.parents) is not fn:
                continue
            if isinstance(node, ast.Assign) and self._is_clock_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        starts.append((t.id, node.lineno))
            elif (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and self._is_clock_call(node.left)
                and isinstance(node.right, ast.Name)
            ):
                deltas.append((node.right.id, node.lineno))
        windows = []
        for var, s_line in starts:
            ends = sorted(line for v, line in deltas if v == var and line > s_line)
            if ends:
                windows.append((var, s_line, ends[0]))
        return windows

    def check(self, ctx) -> Iterator["Finding"]:
        jit_bound = _jit_bound_names(ctx.tree)
        fns = [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for fn in fns:
            windows = self._windows(fn, ctx)
            if not windows:
                continue
            calls = _function_calls(fn, ctx.parents)
            for var, s_line, e_line in windows:
                in_window = [
                    c for c in calls if s_line < c.lineno <= e_line
                ]
                dispatches = [
                    c for c in in_window if _is_dispatch_call(c, jit_bound)
                ]
                if not dispatches:
                    continue
                # the sync must cover the LAST dispatch: a block_until_ready
                # that merely drains earlier work (the warm-then-time mistake)
                # leaves the timed dispatch itself unsynced
                last_dispatch_line = max(c.lineno for c in dispatches)
                if any(
                    _is_sync_call(c) and c.lineno >= last_dispatch_line
                    for c in in_window
                ):
                    continue
                c0 = dispatches[0]
                yield _finding(
                    self.code,
                    ctx,
                    c0,
                    f"timed window `{var}` (lines {s_line}-{e_line}) spans the "
                    f"dispatched call `{call_name(c0) or '<jit>'}` with no "
                    "block_until_ready/device_get/readback on the timed path",
                    self.fix_hint,
                )


# --------------------------------------------------------------------------
# G003 — batch shapes at jit call sites off the bucket ladder


class RuleG003:
    code = "G003"
    summary = "batch-size value reaches a jitted call site without bucket snapping"
    fix_hint = (
        "route the batch size through quantize_batches/snap_to_bucket (or a "
        "capacity width like _cap_b) before it determines a compiled shape — "
        "every off-ladder shape is a fresh XLA compile inside a timed epoch"
    )

    @staticmethod
    def _mentions(node: ast.AST, idents: Set[str]) -> bool:
        return bool(identifiers_in(node) & idents)

    def _tainted_names(self, fn: ast.AST, ctx) -> Set[str]:
        """Names assigned from raw-batch-size expressions that never pass a
        bucketing marker. One forward pass + fixpoint over local assigns."""
        assigns: List[Tuple[Set[str], ast.expr]] = []
        for node in ast.walk(fn):
            if _innermost_function(node, ctx.parents) is not fn:
                continue
            if isinstance(node, ast.Assign):
                targets = assign_targets(node)
                if targets:
                    assigns.append((targets, node.value))
        tainted: Set[str] = set()
        for _ in range(4):  # tiny fixpoint; local chains are short
            changed = False
            for targets, value in assigns:
                if self._mentions(value, _BUCKET_MARKERS):
                    continue
                if self._mentions(value, _BATCH_SOURCES | tainted):
                    new = targets - tainted
                    if new:
                        tainted |= new
                        changed = True
            if not changed:
                break
        return tainted

    def check(self, ctx) -> Iterator["Finding"]:
        jit_bound = _jit_bound_names(ctx.tree)
        fns = [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for fn in fns:
            calls = _function_calls(fn, ctx.parents)
            dispatches = [c for c in calls if _is_dispatch_call(c, jit_bound)]
            if not dispatches:
                continue
            tainted = self._tainted_names(fn, ctx)
            hot = _BATCH_SOURCES | tainted
            for c in calls:
                name = call_name(c)
                is_shape_builder = (
                    name in _SHAPE_BUILDERS or _attr_tail(name) in _SHAPE_BUILDERS
                )
                is_dispatch = c in dispatches
                if not (is_shape_builder or is_dispatch):
                    continue
                for arg in list(c.args) + [kw.value for kw in c.keywords]:
                    if self._mentions(arg, _BUCKET_MARKERS):
                        continue
                    if self._mentions(arg, hot):
                        kind = "shape builder" if is_shape_builder else "jitted call"
                        yield _finding(
                            self.code,
                            ctx,
                            c,
                            f"{kind} `{name}` in `{fn.name}` consumes a raw "
                            "batch-size value that never passed "
                            "snap_to_bucket/quantize_batches — off-ladder "
                            "shapes recompile every rebalance",
                            self.fix_hint,
                        )
                        break


# --------------------------------------------------------------------------
# G004 — host coercion / Python control flow on traced values


class RuleG004:
    code = "G004"
    summary = "host coercion or Python control flow on a traced value in a jitted scope"
    fix_hint = (
        "inside jit, branch with jax.lax.cond/select and keep values as jnp "
        "arrays; float()/int()/bool()/np.asarray() on a tracer either raises "
        "ConcretizationTypeError or silently constant-folds at trace time"
    )

    _COERCIONS = {
        "float",
        "int",
        "bool",
        "complex",
        "np.asarray",
        "numpy.asarray",
        "np.array",
        "numpy.array",
        "np.float32",
        "np.float64",
        "np.int32",
        "np.int64",
        "np.bool_",
    }
    _COERCION_TAILS = ("item", "tolist")
    _STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}

    def _traced_scopes(self, ctx) -> List[Tuple[ast.AST, Set[str]]]:
        """(function node, traced parameter names). Scopes: defs decorated
        with jit, defs/lambdas passed by name into a jax trace entry point
        (jit, shard_map, grad, scan, ...)."""
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        scopes: Dict[ast.AST, Tuple[Optional[Tuple[int, ...]], object]] = {}

        def add(fn: ast.AST, static_argnums=None, static_argnames=None):
            # merge: a def can be marked traced from several sites (decorator
            # plus a by-name lax.scan reference); statics learned at any one
            # of them must not be clobbered by a later site's None
            prev_nums, prev_names = scopes.get(fn, (None, None))
            scopes[fn] = (
                static_argnums if static_argnums is not None else prev_nums,
                static_argnames if static_argnames is not None else prev_names,
            )

        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                decs = set(decorator_names(node))
                if decs & {"jax.jit", "jit", "pjit", "jax.pjit"}:
                    nums = names = None
                    for dec in node.decorator_list:
                        # read statics only off the jit decorator itself, not
                        # any other Call decorator stacked on the same def
                        if not (isinstance(dec, ast.Call) and is_jit_construction(dec)):
                            continue
                        nums = literal_int_tuple(jit_kwarg(dec, "static_argnums"))
                        names_node = jit_kwarg(dec, "static_argnames")
                        try:
                            names = ast.literal_eval(names_node) if names_node else None
                        except (ValueError, SyntaxError):
                            names = None
                    add(node, nums, names)
            elif isinstance(node, ast.Call) and call_name(node) in _TRACE_ENTRY_TAILS:
                nums = literal_int_tuple(jit_kwarg(node, "static_argnums"))
                names_node = jit_kwarg(node, "static_argnames")
                try:
                    names = ast.literal_eval(names_node) if names_node else None
                except (ValueError, SyntaxError):
                    names = None
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        for d in defs.get(arg.id, []):
                            add(d, nums, names)
                    elif isinstance(arg, ast.Lambda):
                        add(arg, nums, names)

        out: List[Tuple[ast.AST, Set[str]]] = []
        for fn, statics in scopes.items():
            nums, names = statics if statics else (None, None)
            args = fn.args
            params = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
            traced = set(params) - {"self", "cls"}
            if nums:
                all_pos = [a.arg for a in args.posonlyargs + args.args]
                for i in nums:
                    if 0 <= i < len(all_pos):
                        traced.discard(all_pos[i])
            if names:
                if isinstance(names, str):
                    names = (names,)
                traced -= set(names)
            out.append((fn, traced))
        return out

    def _live_traced(self, expr: ast.AST, traced: Set[str]) -> bool:
        """Does ``expr`` mention a traced name outside static accessors
        (``x.shape``/``x.ndim``/``x.dtype``/``len(x)``)?"""

        def walk(node: ast.AST) -> bool:
            if isinstance(node, ast.Attribute) and node.attr in self._STATIC_ATTRS:
                return False
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "len"
            ):
                return False
            if isinstance(node, ast.Name) and node.id in traced:
                return True
            return any(walk(c) for c in ast.iter_child_nodes(node))

        return walk(expr)

    def check(self, ctx) -> Iterator["Finding"]:
        for fn, params in self._traced_scopes(ctx):
            traced = set(params)
            # forward propagation through local assignments
            for node in ast.walk(fn):
                if _innermost_function(node, ctx.parents) is not fn:
                    continue
                if isinstance(node, ast.Assign) and self._live_traced(
                    node.value, traced
                ):
                    traced |= assign_targets(node)
            for node in ast.walk(fn):
                if _innermost_function(node, ctx.parents) is not fn:
                    continue
                if isinstance(node, ast.Call):
                    name = call_name(node)
                    coercing = name in self._COERCIONS or (
                        _attr_tail(name) in self._COERCION_TAILS and not node.args
                    )
                    if coercing and any(
                        self._live_traced(a, traced) for a in node.args
                    ):
                        yield _finding(
                            self.code,
                            ctx,
                            node,
                            f"`{name}` coerces a traced value to host inside "
                            f"jitted scope `{getattr(fn, 'name', '<lambda>')}`",
                            self.fix_hint,
                        )
                    elif coercing and _attr_tail(name) in self._COERCION_TAILS:
                        recv = node.func.value if isinstance(node.func, ast.Attribute) else None
                        if recv is not None and self._live_traced(recv, traced):
                            yield _finding(
                                self.code,
                                ctx,
                                node,
                                f"`.{_attr_tail(name)}()` reads a traced value "
                                f"back to host inside jitted scope "
                                f"`{getattr(fn, 'name', '<lambda>')}`",
                                self.fix_hint,
                            )
                elif isinstance(node, (ast.If, ast.While)):
                    if self._live_traced(node.test, traced):
                        yield _finding(
                            self.code,
                            ctx,
                            node,
                            "Python control flow on a traced value inside "
                            f"jitted scope `{getattr(fn, 'name', '<lambda>')}` "
                            "— the branch is resolved once at trace time",
                            self.fix_hint,
                        )
                elif isinstance(node, ast.Assert):
                    if self._live_traced(node.test, traced):
                        yield _finding(
                            self.code,
                            ctx,
                            node,
                            "assert on a traced value inside jitted scope "
                            f"`{getattr(fn, 'name', '<lambda>')}`",
                            self.fix_hint,
                        )
                elif isinstance(node, (ast.For, ast.AsyncFor)):
                    if self._live_traced(node.iter, traced):
                        yield _finding(
                            self.code,
                            ctx,
                            node,
                            "Python loop over a traced value inside jitted "
                            f"scope `{getattr(fn, 'name', '<lambda>')}` — use "
                            "lax.fori_loop/scan",
                            self.fix_hint,
                        )


# --------------------------------------------------------------------------
# G005 — donated buffer referenced after the donating call
#
# Since ISSUE 8 this rule runs on the graftflow IR (analysis/flow/ir.py):
# the statement flattening, branch-exclusivity guards, and token read/bind
# checks are the same machinery G011 propagates interprocedurally — G005
# stays the fast single-file tier (exact donated token, direct donor call),
# G011 adds aliases/containers/returns/self-attrs across functions.


class RuleG005:
    code = "G005"
    summary = "donated buffer read after a donate_argnums call"
    fix_hint = (
        "rebind the variable from the call's result (x = f(x, ...)) or use "
        "the non-donating probe twin; a donated buffer's storage is reused "
        "by XLA and reading it is undefined (DeletedBuffer on TPU)"
    )

    def check(self, ctx) -> Iterator["Finding"]:
        from dynamic_load_balance_distributeddnn_tpu.analysis.flow.ir import (
            summarize_module,
        )
        from dynamic_load_balance_distributeddnn_tpu.analysis.flow.rules import (
            _mutually_exclusive,
            _reads_token,
        )

        mod = summarize_module(
            ctx.tree, path=ctx.path, module="<single>", parents=ctx.parents
        )
        donors: Dict[str, Tuple[int, ...]] = dict(KNOWN_DONOR_ATTRS)
        donors.update(mod.jit_donors)
        for fn in mod.functions.values():
            stmts = list(fn.stmts)
            # locals bound to jit(..., donate_argnums=...) in this function
            local_donors = dict(donors)
            for stmt in stmts:
                if stmt.bind is not None and stmt.bind.donate_argnums:
                    for t in stmt.bind.targets:
                        local_donors[t.rsplit(".", 1)[-1]] = (
                            stmt.bind.donate_argnums
                        )
            for i, stmt in enumerate(stmts):
                for call in stmt.calls:
                    nums = local_donors.get(call.tail)
                    if not nums:
                        continue
                    for argnum in nums:
                        if argnum >= len(call.args):
                            continue
                        token = call.args[argnum]
                        if token is None:
                            continue
                        # donated-and-rebound in the same statement is the
                        # safe idiom: state = f(state, ...)
                        if stmt.bind is not None and token in stmt.bind.targets:
                            continue
                        for later in stmts[i + 1:]:
                            if _mutually_exclusive(stmt, later):
                                continue
                            read = _reads_token(later, token)
                            if read is not None:
                                read_tok, line, col = read
                                yield Finding_at(
                                    self.code,
                                    ctx,
                                    line,
                                    col,
                                    f"`{token}` was donated to "
                                    f"`{call.name or call.tail}` on line "
                                    f"{call.line} and read again here",
                                    self.fix_hint,
                                )
                                break
                            if later.bind is not None and token in later.bind.targets:
                                break


# --------------------------------------------------------------------------
# G006 — per-step device_put interleaved with dispatch in a hot loop


class RuleG006:
    code = "G006"
    summary = "per-step jax.device_put interleaved with compiled dispatch in a loop"
    fix_hint = (
        "hoist the transfer out of the step loop: stage the whole window "
        "once per window (train/pipeline.py WindowTransferPipeline, or a "
        "single [win, ...] put sliced on device) so host→device traffic "
        "overlaps compute instead of serializing with every dispatch"
    )

    # Setup/instrumentation scopes where a per-iteration put alongside a
    # dispatch is the point (warm ladders, probe/calibration passes) — the
    # rule targets hot TRAINING loops, not one-off epochs of measurement.
    _ALLOWED_NAMES = {"__init__", "__post_init__", "setup"}
    _ALLOWED_PREFIXES = (
        "warm", "_warm",
        "build", "_build",
        "make_", "_make",
        "create_", "_create",
        "probe", "_probe",
        "calibrate", "_calibrate",
    )

    _PUT_TAILS = {"device_put", "device_put_sharded", "device_put_replicated"}

    def _scope_allowed(self, fn: Optional[ast.AST]) -> bool:
        if fn is None or isinstance(fn, ast.Lambda):
            return fn is None  # module-scope loops are setup by definition
        name = fn.name
        return name in self._ALLOWED_NAMES or name.startswith(
            self._ALLOWED_PREFIXES
        )

    def check(self, ctx) -> Iterator["Finding"]:
        jit_bound = _jit_bound_names(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not (
                isinstance(node, ast.Call)
                and _attr_tail(call_name(node)) in self._PUT_TAILS
            ):
                continue
            fn = _innermost_function(node, ctx.parents)
            if self._scope_allowed(fn):
                continue
            loop = enclosing_loop(node, ctx.parents, stop_at=fn)
            if loop is None:
                continue
            # the INNERMOST loop containing the put must itself dispatch a
            # compiled executable: per-window staging loops (puts only, the
            # dispatch lives in a sibling loop) are the sanctioned idiom
            dispatches = [
                c
                for c in ast.walk(loop)
                if isinstance(c, ast.Call)
                and _is_dispatch_call(c, jit_bound)
                and enclosing_loop(c, ctx.parents, stop_at=fn) is loop
                and _innermost_function(c, ctx.parents) is fn
            ]
            if not dispatches:
                continue
            yield _finding(
                self.code,
                ctx,
                node,
                f"`{call_name(node)}` inside the same loop as the compiled "
                f"dispatch `{call_name(dispatches[0]) or '<jit>'}` — a "
                "host→device transfer is issued every iteration of a "
                "scan-capable step loop",
                self.fix_hint,
            )


# --------------------------------------------------------------------------
# G007 — execute-to-compile warm loops / blocking compile in a timed region


class RuleG007:
    code = "G007"
    summary = (
        "execute-to-compile warm loop, or blocking .compile() inside a "
        "timed region"
    )
    fix_hint = (
        "compile ahead of time: submit jit(fn).lower(abstract_args).compile() "
        "jobs to the AOT compile service (runtime/compiler.py) instead of "
        "executing dummy steps — no execution, no device_put traffic, "
        "concurrent backend compiles off the timed path"
    )

    # Warm/init scopes: the execute-to-compile pattern (dispatch a dummy
    # step + block on it, discard the result) is only a finding THERE — in a
    # hot training loop a dispatch+sync is just training.
    _WARM_NAMES = {"__init__", "__post_init__", "setup"}
    _WARM_MARKERS = ("warm",)
    # Scopes allowed to call .compile() under a timer: the compile service
    # itself (its job is measuring compile walls).
    _COMPILE_SCOPE_PREFIXES = ("compile", "_compile", "aot", "_aot")

    def _is_warm_scope(self, fn: Optional[ast.AST]) -> bool:
        if fn is None or isinstance(fn, ast.Lambda):
            return False
        name = fn.name
        return name in self._WARM_NAMES or any(
            m in name.lower() for m in self._WARM_MARKERS
        )

    # ---- pattern A: dispatch + sync inside a loop in a warm scope

    def _check_warm_loops(self, ctx, jit_bound) -> Iterator["Finding"]:
        seen_loops: Set[int] = set()
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and _is_dispatch_call(node, jit_bound)):
                continue
            fn = _innermost_function(node, ctx.parents)
            if not self._is_warm_scope(fn):
                continue
            loop = enclosing_loop(node, ctx.parents, stop_at=fn)
            if loop is None or id(loop) in seen_loops:
                continue
            loop_calls = [
                c
                for c in ast.walk(loop)
                if isinstance(c, ast.Call)
                and _innermost_function(c, ctx.parents) is fn
            ]
            if not any(_is_sync_call(c) for c in loop_calls):
                continue
            seen_loops.add(id(loop))
            first = min(
                (c for c in loop_calls if _is_dispatch_call(c, jit_bound)),
                key=lambda c: (c.lineno, c.col_offset),
            )
            yield _finding(
                self.code,
                ctx,
                first,
                f"warm scope `{fn.name}` compiles by EXECUTING "
                f"`{call_name(first) or '<jit>'}` in a loop (dispatch + sync, "
                "result discarded): a serial execute-to-compile warm wall",
                self.fix_hint,
            )

    # ---- pattern B: lowered.compile() inside a wall-clock window

    @staticmethod
    def _lowered_names(fn: ast.AST, ctx) -> Set[str]:
        """Local names bound from a ``*.lower(...)`` call."""
        out: Set[str] = set()
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _attr_tail(call_name(node.value)) == "lower"
            ):
                out |= assign_targets(node)
        return out

    def _is_blocking_compile(self, node: ast.Call, lowered: Set[str]) -> bool:
        if not (
            isinstance(node.func, ast.Attribute) and node.func.attr == "compile"
        ):
            return False
        recv = node.func.value
        if isinstance(recv, ast.Call) and _attr_tail(call_name(recv)) == "lower":
            return True  # fn.lower(...).compile()
        return isinstance(recv, ast.Name) and recv.id in lowered

    def _check_timed_compiles(self, ctx) -> Iterator["Finding"]:
        window_rule = RULES_G002_WINDOWS
        for fn in [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]:
            if fn.name.startswith(self._COMPILE_SCOPE_PREFIXES):
                continue
            windows = window_rule._windows(fn, ctx)
            if not windows:
                continue
            lowered = self._lowered_names(fn, ctx)
            calls = _function_calls(fn, ctx.parents)
            for var, s_line, e_line in windows:
                for c in calls:
                    if s_line < c.lineno <= e_line and self._is_blocking_compile(
                        c, lowered
                    ):
                        yield _finding(
                            self.code,
                            ctx,
                            c,
                            f"blocking XLA `.compile()` inside timed window "
                            f"`{var}` (lines {s_line}-{e_line}) — the wall "
                            "measures the compiler, not the program; compile "
                            "ahead of time and fetch the executable",
                            self.fix_hint,
                        )
                        break

    def check(self, ctx) -> Iterator["Finding"]:
        jit_bound = _jit_bound_names(ctx.tree)
        yield from self._check_warm_loops(ctx, jit_bound)
        yield from self._check_timed_compiles(ctx)


# --------------------------------------------------------------------------
# G008 — bare wall-clock delta recorded as a metric without span coverage


class RuleG008:
    code = "G008"
    summary = (
        "bare perf_counter/time wall recorded as a metric outside "
        "TimeKeeper/graftscope-span coverage"
    )
    fix_hint = (
        "measure the region under a graftscope span (obs/trace.py — the "
        "wall then lands in the trace and `graftscope summarize` can "
        "attribute it) or aggregate it through TimeKeeper/HostOverheadMeter "
        "before it reaches the recorder; a bare wall fed straight into a "
        "recorded series is invisible to epoch attribution"
    )

    # Metric-recording sinks: the per-epoch series entry point, or anything
    # reached through a `recorder` handle (meta subscript writes included).
    _SINK_TAILS = {"record_epoch"}

    @staticmethod
    def _is_recorder_path(name: Optional[str]) -> bool:
        return bool(name) and "recorder" in name.split(".")

    @classmethod
    def _is_sink_call(cls, node: ast.Call) -> bool:
        name = call_name(node)
        if name is None:
            return False
        return _attr_tail(name) in cls._SINK_TAILS or cls._is_recorder_path(name)

    @staticmethod
    def _contains_wall_delta(expr: ast.expr) -> bool:
        """Does this RHS contain ``<clock>() - <name>`` anywhere (also nested
        in min()/round()/arithmetic, the repo's usual wall idioms)?"""
        for n in ast.walk(expr):
            if (
                isinstance(n, ast.BinOp)
                and isinstance(n.op, ast.Sub)
                and isinstance(n.left, ast.Call)
                and call_name(n.left) in _CLOCK_CALLS
                and isinstance(n.right, ast.Name)
            ):
                return True
        return False

    @staticmethod
    def _span_covered(node: ast.AST, ctx, fn) -> bool:
        """Is this statement lexically inside a ``with *.span(...)`` block?
        A wall measured under a span is already attributable in the trace —
        the sanctioned bare-wall form."""
        cur = ctx.parents.get(node)
        while cur is not None and cur is not fn:
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    if (
                        isinstance(item.context_expr, ast.Call)
                        and _attr_tail(call_name(item.context_expr)) == "span"
                    ):
                        return True
            cur = ctx.parents.get(cur)
        return False

    @staticmethod
    def _bind_tokens(stmt: ast.Assign) -> Set[str]:
        """Identifiers this assignment taints: plain/dotted Name targets
        (their attribute tail too) and the CONTAINER of a subscript target
        (``extras["k"] = wall`` taints ``extras``)."""
        out: Set[str] = set()
        for t in stmt.targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            name = dotted_name(base)
            if name:
                out.add(name)
                out.add(_attr_tail(name))
        return out

    def _tainted(self, fn: ast.AST, ctx) -> Set[str]:
        assigns: List[ast.Assign] = [
            n
            for n in ast.walk(fn)
            if isinstance(n, ast.Assign)
            and _innermost_function(n, ctx.parents) is fn
        ]
        tainted: Set[str] = set()
        for stmt in assigns:
            if self._contains_wall_delta(stmt.value) and not self._span_covered(
                stmt, ctx, fn
            ):
                tainted |= self._bind_tokens(stmt)
        for _ in range(4):  # local chains are short
            changed = False
            for stmt in assigns:
                if identifiers_in(stmt.value) & tainted:
                    new = self._bind_tokens(stmt) - tainted
                    if new:
                        tainted |= new
                        changed = True
            if not changed:
                break
        return tainted

    def check(self, ctx) -> Iterator["Finding"]:
        fns = [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for fn in fns:
            tainted = self._tainted(fn, ctx)
            if not tainted:
                continue
            for node in ast.walk(fn):
                if _innermost_function(node, ctx.parents) is not fn:
                    continue
                if isinstance(node, ast.Call) and self._is_sink_call(node):
                    values = list(node.args) + [kw.value for kw in node.keywords]
                    hit = next(
                        (v for v in values if identifiers_in(v) & tainted), None
                    )
                    if hit is not None:
                        yield _finding(
                            self.code,
                            ctx,
                            node,
                            f"`{call_name(node)}` in `{fn.name}` records a "
                            "bare wall-clock delta that never went through a "
                            "graftscope span or TimeKeeper — the metric is "
                            "unattributable in the trace",
                            self.fix_hint,
                        )
                elif isinstance(node, ast.Assign):
                    sub_sinks = [
                        t
                        for t in node.targets
                        if isinstance(t, ast.Subscript)
                        and self._is_recorder_path(dotted_name(t.value))
                    ]
                    if sub_sinks and identifiers_in(node.value) & tainted:
                        yield _finding(
                            self.code,
                            ctx,
                            node,
                            f"recorder metadata write in `{fn.name}` stores a "
                            "bare wall-clock delta that never went through a "
                            "graftscope span or TimeKeeper",
                            self.fix_hint,
                        )


# --------------------------------------------------------------------------
# G009 — hot-path dispatch/compile bypassing the AOTCompileService registry


class RuleG009:
    code = "G009"
    summary = (
        "engine hot path dispatches or compiles an executable directly, "
        "bypassing the AOTCompileService registry"
    )
    fix_hint = (
        "resolve the executable from the AOT service registry "
        "(service.get(key), the engine's _aot_resolve* helpers) and pass "
        "the lazy jit only as the uncalled fallback VALUE — then warm and "
        "speculative compiles are actually reused, dispatch hits the "
        "pre-compiled object, and the compile guards can attribute what "
        "compiles; a direct .lower()/.compile() likewise never registers "
        "its executable for reuse"
    )

    # The rule only makes sense where a registry EXISTS: modules that hold
    # an AOT service handle. Matching code tokens (not docstrings) keeps
    # engines without a service — and the lint fixtures — out of scope.
    _GATE_NAMES = {"AOTCompileService", "aot_service"}
    _GATE_ATTRS = {"_aot", "aot_service"}
    # Steady-state dispatch scopes: the per-epoch/per-window hot path. Warm
    # scopes (the sanctioned serial A/B reference) and probes are excluded
    # by name.
    _DISPATCH_MARKERS = ("dispatch", "train_epoch")
    _DISPATCH_NAMES = {"run_epoch"}
    # Scopes allowed to lower/compile directly: the service and its
    # plumbing (same convention as G007's timed-compile sanction).
    _COMPILE_SCOPE_PREFIXES = ("compile", "_compile", "aot", "_aot")

    def _module_gated(self, ctx) -> bool:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Name) and node.id in self._GATE_NAMES:
                return True
            if isinstance(node, ast.Attribute) and node.attr in self._GATE_ATTRS:
                return True
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                if any(
                    (a.asname or a.name).split(".")[-1] in self._GATE_NAMES
                    for a in node.names
                ):
                    return True
        return False

    def _is_dispatch_scope(self, fn: Optional[ast.AST]) -> bool:
        if fn is None or isinstance(fn, ast.Lambda):
            return False
        name = fn.name.lower()
        return name in self._DISPATCH_NAMES or any(
            m in name for m in self._DISPATCH_MARKERS
        )

    # ---- pattern A: direct StepLibrary/jit dispatch in a dispatch scope

    # Registry-resolution RHS tails: a local bound from one of these calls
    # is the SANCTIONED dispatch handle (service executable, lazy fallback
    # only on a registry miss) even when another branch binds it from a
    # steps attribute.
    _RESOLVE_TAILS_PREFIXES = ("_aot_resolve", "_resolve", "resolve")
    _RESOLVE_TAILS = {"get", "compile_now"}

    @classmethod
    def _is_resolution_rhs(cls, value: ast.expr) -> bool:
        if isinstance(value, ast.IfExp):
            return cls._is_resolution_rhs(value.body) or cls._is_resolution_rhs(
                value.orelse
            )
        if not isinstance(value, ast.Call):
            return False
        tail = _attr_tail(call_name(value))
        return tail in cls._RESOLVE_TAILS or tail.startswith(
            cls._RESOLVE_TAILS_PREFIXES
        )

    @staticmethod
    def _module_jit_bound(ctx) -> Set[str]:
        """Names bound to jitted callables at MODULE scope only (the
        flow-insensitive module-wide set would taint every reuse of a common
        local name like ``fn`` across unrelated functions)."""
        bound: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Assign)
                and _innermost_function(node, ctx.parents) is None
                and _rhs_binds_jitted(node.value)
            ):
                bound |= assign_targets(node)
        return bound

    def _check_dispatch_bypass(self, ctx, module_jit_bound) -> Iterator["Finding"]:
        for fn in [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]:
            if not self._is_dispatch_scope(fn):
                continue
            local_jitted: Set[str] = set()
            local_resolved: Set[str] = set()
            for stmt in ast.walk(fn):
                if not (
                    isinstance(stmt, ast.Assign)
                    and _innermost_function(stmt, ctx.parents) is fn
                ):
                    continue
                if self._is_resolution_rhs(stmt.value):
                    local_resolved |= assign_targets(stmt)
                elif _rhs_binds_jitted(stmt.value):
                    local_jitted |= assign_targets(stmt)
            bypass = (module_jit_bound | local_jitted) - local_resolved
            for node in _function_calls(fn, ctx.parents):
                name = call_name(node)
                tail = _attr_tail(name)
                direct = (
                    tail in KNOWN_STEP_ATTRS and name and ".steps." in name
                ) or (name in bypass)
                if not direct:
                    continue
                yield _finding(
                    self.code,
                    ctx,
                    node,
                    f"dispatch scope `{fn.name}` calls `{name}` directly — "
                    "the AOT service registry (warm + speculative compiles) "
                    "is bypassed, so a shape already compiled in the "
                    "background recompiles lazily in the foreground",
                    self.fix_hint,
                )

    # ---- pattern B: direct lower()/compile() outside the service

    def _check_unregistered_compiles(self, ctx) -> Iterator["Finding"]:
        for fn in [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]:
            if fn.name.startswith(self._COMPILE_SCOPE_PREFIXES):
                continue
            lowered = RuleG007._lowered_names(fn, ctx)
            for node in _function_calls(fn, ctx.parents):
                is_lower = (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "lower"
                    # jit lowering takes the abstract args; a bare str.lower()
                    # takes none
                    and bool(node.args or node.keywords)
                )
                is_compile = (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "compile"
                    and (
                        (
                            isinstance(node.func.value, ast.Call)
                            and _attr_tail(call_name(node.func.value)) == "lower"
                        )
                        or (
                            isinstance(node.func.value, ast.Name)
                            and node.func.value.id in lowered
                        )
                    )
                )
                if not (is_lower or is_compile):
                    continue
                what = "lowers" if is_lower else "compiles"
                yield _finding(
                    self.code,
                    ctx,
                    node,
                    f"`{fn.name}` {what} an XLA program directly "
                    f"(`{call_name(node)}`) outside the AOT compile service — "
                    "the executable never registers for reuse and the "
                    "compile is invisible to the service's dedup/stats",
                    self.fix_hint,
                )

    def check(self, ctx) -> Iterator["Finding"]:
        if not self._module_gated(ctx):
            return
        yield from self._check_dispatch_bypass(ctx, self._module_jit_bound(ctx))
        yield from self._check_unregistered_compiles(ctx)


# --------------------------------------------------------------------------
# G010 — unguarded blocking device calls in elastic retry/recovery scopes


class RuleG010:
    code = "G010"
    summary = (
        "blocking device-side or rendezvous call in a retry/recovery scope "
        "without heartbeat()/tick() coverage or a retry/timeout wrapper"
    )
    fix_hint = (
        "recovery and rendezvous scopes run exactly when the fleet is "
        "misbehaving — a blocking PJRT call (block_until_ready/device_put/"
        "device_get/.compile()) or coordination edge (jax.distributed "
        "initialize/shutdown, client connect, barrier waits) there can hang "
        "in C++ against a dead runtime or peer, and without a heartbeat() "
        "the stall watchdog reads the recovery itself as the hang. Call "
        "heartbeat() (or the state machine's tick()) after each blocking "
        "edge in the scope, or wrap the edge in retry_transient(..., "
        "tick=heartbeat) with a bounded timeout"
    )

    # The rule only makes sense where the elasticity machinery EXISTS:
    # modules that name the health/recovery surface. Token match (not
    # docstrings) keeps unrelated modules — and the other lint fixtures —
    # out of scope.
    _GATE_NAMES = {"WorkerLost", "WorkerHealth", "retry_transient"}
    # Recovery scopes by naming convention (mirrors G009's dispatch-scope
    # convention): the engine's failure-detection -> drain -> re-solve ->
    # re-shard -> readmit path, plus the multi-host RENDEZVOUS scopes
    # (ISSUE 14) — propose/agree/barrier/establish run exactly while the
    # fleet is broken, so an unarmored blocking edge there hangs the
    # recovery itself.
    _SCOPE_MARKERS = (
        "recover",
        "readmit",
        "reshard",
        "rendezvous",
        "rdzv",
        "establish",
        "agree",
        "elastic_initialize",
        "retire",
    )
    # Blocking device-side call tails.
    _BLOCKING_TAILS = {
        "block_until_ready",
        "device_put",
        "device_get",
        # rendezvous-scope blocking edges: coordination-service bring-up /
        # teardown and its barriers block on REMOTE processes — the peers a
        # recovery exists to outlive
        "initialize",
        "shutdown",
        "connect",
        "wait_at_barrier",
    }

    def _module_gated(self, ctx) -> bool:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Name) and node.id in self._GATE_NAMES:
                return True
            if isinstance(node, ast.Attribute) and node.attr in self._GATE_NAMES:
                return True
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                if any(
                    (a.asname or a.name).split(".")[-1] in self._GATE_NAMES
                    for a in node.names
                ):
                    return True
        return False

    def _is_recovery_scope(self, fn: ast.AST) -> bool:
        if isinstance(fn, ast.Lambda):
            return False
        name = fn.name.lower()
        if name == "retry_transient":
            return False  # the wrapper itself is the sanctioned armor
        return any(m in name for m in self._SCOPE_MARKERS)

    @staticmethod
    def _is_blocking(node: ast.Call, tails) -> bool:
        if isinstance(node.func, ast.Attribute) and node.func.attr in tails:
            return True
        # lowered.compile() / jit(f).lower(...).compile(): a blocking XLA
        # backend compile (re-warm edges of a re-shard)
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "compile"
            and not node.args
            and not node.keywords
        ):
            return True
        name = call_name(node)
        return bool(name) and _attr_tail(name) in tails

    @staticmethod
    def _covered(fn: ast.AST) -> bool:
        """heartbeat() anywhere in the scope keeps the watchdog fed across
        its blocking edges; ``tick()`` is the rendezvous state machine's
        injected spelling of the same pulse (runtime/rendezvous.py wires
        ``tick=heartbeat``)."""
        for n in ast.walk(fn):
            if isinstance(n, ast.Call):
                tail = _attr_tail(call_name(n))
                if tail in ("heartbeat", "tick"):
                    return True
        return False

    def check(self, ctx) -> Iterator["Finding"]:
        if not self._module_gated(ctx):
            return
        for fn in [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]:
            if not self._is_recovery_scope(fn):
                continue
            if self._covered(fn):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                # calls inside a retry_transient(...) argument are armored
                # by the wrapper's tick/backoff
                p = ctx.parents.get(node)
                armored = False
                while p is not None and p is not fn:
                    if (
                        isinstance(p, ast.Call)
                        and _attr_tail(call_name(p)) == "retry_transient"
                    ):
                        armored = True
                        break
                    p = ctx.parents.get(p)
                if armored:
                    continue
                if self._is_blocking(node, self._BLOCKING_TAILS):
                    yield _finding(
                        self.code,
                        ctx,
                        node,
                        f"recovery scope `{fn.name}` blocks on the device "
                        f"(`{call_name(node) or node.func.attr}`) with no "
                        "heartbeat() in scope and no retry/timeout wrapper "
                        "— a hang here reads as a watchdog stall of the "
                        "recovery itself",
                        self.fix_hint,
                    )


# G007 reuses G002's timed-window extraction; share one instance.
RULES_G002_WINDOWS = RuleG002()

RULES: Dict[str, object] = {
    r.code: r
    for r in (
        RuleG001(),
        RULES_G002_WINDOWS,
        RuleG003(),
        RuleG004(),
        RuleG005(),
        RuleG006(),
        RuleG007(),
        RuleG008(),
        RuleG009(),
        RuleG010(),
    )
}
