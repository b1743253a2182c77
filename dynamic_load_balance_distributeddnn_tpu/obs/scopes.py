"""Device scopes: stable names for the parts of a step, and the map from a
compiled program's instructions to them.

A profiler trace names device work by HLO instruction (``fusion.825``), and
those names change with any edit to a step. The step bodies (train/steps.py)
therefore wrap their parts in ``jax.named_scope`` under the names below — a
scope is metadata on the HLO and costs nothing at run time — and, while the
tracer is on, every program that is compiled leaves one line in
``<trace_dir>/hlo_scopes.jsonl``::

    {"module": <HLO module name>, "key": <registry key>,
     "scopes": {<instruction name>: <scope, or "" for none>}}

A reader joins a device event (module, instruction) with that file and gets
seconds by scope; ``benchmark/scope_reduce.py`` is the one that exists. The
names, the rule that turns an ``op_name`` path into one of them, and the
parser of ``compiled.as_text()`` live here and nowhere else.

The scopes are read from the executable that runs, so the persistent compile
cache must not serve one compiled under other names: ``compile_cache.py``
makes the op names part of every key.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
from typing import Dict, Tuple

from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer

AUGMENT = "augment"    # input preparation of a training batch: crop, flip, normalise, cast
FORWARD = "forward"    # the model's apply and the loss
BACKWARD = "backward"  # JAX's transpose of `forward`; no code enters it by name
CLIP = "clip"          # per-worker gradient clipping
INJECT = "inject"      # the injected synthetic load of a straggler
COMBINE = "combine"    # gradient sums and collectives, the metrics' psum
UPDATE = "update"      # the optimizer's update and its application
EVAL = "eval"          # the evaluation step, whole
# parts of a decoder's forward pass (models/afmoe.py, models/qwen3_next.py). The innermost scope
# wins, so these take their time, forward and transposed alike, out of
# `forward` and `backward`.
ATTENTION_WINDOW = "attention_window"  # a window layer's attention, projections to output
ATTENTION_FULL = "attention_full"      # a full layer's
ROUTER = "router"                      # scores, top-k and routing weights
EXPERTS = "experts"                    # dispatch, grouped products, weighted combine
SHARED_EXPERT = "shared_expert"
LM_HEAD = "lm_head"                    # the product with the output vocabulary
LINEAR_ATTENTION = "linear_attention"  # a linear layer's mixer: projections, convolution,
                                       # norms, gate and output, around `delta_rule`
DELTA_RULE = "delta_rule"              # the gated delta rule alone (ops/linear_attention.py)

SCOPES = (AUGMENT, FORWARD, BACKWARD, CLIP, INJECT, COMBINE, UPDATE, EVAL,
          ATTENTION_WINDOW, ATTENTION_FULL, ROUTER, EXPERTS, SHARED_EXPERT, LM_HEAD,
          LINEAR_ATTENTION, DELTA_RULE)
MAP_FILE = "hlo_scopes.jsonl"

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+)\s*=\s*")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}"
)
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_write_lock = threading.Lock()


def scope_of(op_name: str) -> str:
    """The scope an ``op_name`` path lies in, ``""`` for none. JAX wraps each
    component of the name stack in the transforms it went through
    (``jit(f)/transpose(jvp(forward))/mul``): a transposed ``forward`` is
    ``backward``, the innermost scope wins, and a ``forward`` nested in the
    backward pass (rematerialisation) stays ``backward``."""
    found = ""
    for part in op_name.split("/"):
        transforms = []
        m = _WRAPPED.match(part)
        while m:
            transforms.append(m.group(1))
            part = m.group(2)
            m = _WRAPPED.match(part)
        if part not in SCOPES or "jit" in transforms or "pjit" in transforms:
            continue
        if part == FORWARD:
            if "transpose" in transforms:
                found = BACKWARD
            elif found != BACKWARD:
                found = FORWARD
        else:
            found = part
    return found


def instruction_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module name, {instruction name: scope})`` of a compiled program's
    text. A fusion takes the scope of its root's ``op_name``; any other
    instruction that of its own; one that has none (XLA's own loops carry no
    metadata: the row-by-row loop it makes of a gather) inherits from the
    instruction that calls its computation. Instructions inside fused
    computations are left out: no device event carries their names."""
    module = ""
    rows = []                      # (computation, instruction, own scope, fused computation)
    roots: Dict[str, str] = {}     # computation -> scope of its root
    caller: Dict[str, tuple] = {}  # computation -> (computation, own scope) of its first caller
    computation = None
    for line in hlo_text.splitlines():
        if computation is None:
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            elif not module:
                m = _MODULE.match(line)
                module = m.group(1) if m else ""
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else ""
        called = [c for one, many in _CALLED.findall(line)
                  for c in re.findall(r"[\w.\-]+", one or many)]
        for c in called:
            caller.setdefault(c, (computation, scope))
        fused = called[0] if called and " fusion(" in line else None
        rows.append((computation, m.group(2), scope, fused))
        if m.group(1):
            roots[computation] = scope

    def inherited(comp: str) -> str:
        seen = set()
        while comp in caller and comp not in seen:
            seen.add(comp)
            comp, scope = caller[comp]
            if scope:
                return scope
        return ""

    fused_computations = {fused for _, _, _, fused in rows if fused}
    scopes = {
        name: (roots.get(fused, "") if fused else "") or scope or inherited(computation)
        for computation, name, scope, fused in rows
        if computation not in fused_computations
    }
    return module, scopes


def record_program(key, compiled) -> None:
    """Append ``compiled``'s scope map to the tracer's directory. With the
    tracer off, or told no directory, nothing is parsed or written."""
    tr = get_tracer()
    if not tr.enabled or not tr.trace_dir:
        return
    module, scopes = instruction_scopes(compiled.as_text())
    line = json.dumps({"module": module, "key": repr(key), "scopes": scopes})
    try:
        with _write_lock:
            os.makedirs(tr.trace_dir, exist_ok=True)
            with open(os.path.join(tr.trace_dir, MAP_FILE), "a") as f:
                f.write(line + "\n")
    except OSError as e:  # the program compiled; only its map is lost
        logging.getLogger("graftscope").warning("scope map of %s not written: %s", key, e)
