#!/usr/bin/env python
"""chip_smoke.py — does today's code start, train and agree with itself on
the TPU? One process, the normal entry points, full model width.

    python chip_smoke.py             # one chip   (what the driver runs)
    python chip_smoke.py --chips 4   # four chips: the cross-chip path only

Without an accelerator (``jax.devices()[0].platform != "tpu"``) the script
exits non-zero and prints no result: there is no CPU continuation and no
flag that allows one.

One chip, phases in order, one JSON line each:

``device``         what JAX reports; the compile-cache directory; whether the
                   native host library loaded or the numpy twin runs.
``cnn_main_path``  ``cli.run`` (what ``cli.main`` calls) on the paper's
                   recipe: DenseNet-121 at full width on CIFAR-10 shapes,
                   ws 4, B 512, bf16, a 3:1 compute-mode straggler on worker
                   0, DBS on, 3 epochs of ~20 steps (so two re-plans). All
                   four workers share chip 0. Fails on a non-finite loss, a
                   failed AOT job, a run that trained nothing, or a final
                   partition that left worker 0 at >= 0.25.
``lm_main_path``   ``LMTrainer`` through the same entry: the 2-layer 200-wide
                   Transformer LM at bptt 35, ws 4, one epoch of 6 steps.
``kernels``        the three Pallas kernels with ``interpret=False`` at real
                   widths, forward and gradient, against their XLA
                   references on the chip, within a stated bf16 tolerance,
                   and ``tpu_custom_call`` present in the compiled text.

``--chips 4`` runs only the data-parallel path that exists across chips —
the same DenseNet recipe, DBS off, one worker per chip, a few steps — and
what it is compared with: the same seed and steps with all four workers on
chip 0 (``-gpu 0``). It asserts that all four devices hold state, that the
compiled step contains an ``all-reduce``, and that the two loss sequences
agree within bf16 tolerance.

Every time printed here is a SMOKE TIMING (one cold run, compiles included):
not a benchmark, no rate, no utilization. The last line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT_ROOT = os.path.join(_HERE, "chiprun_out", "chip_smoke")

# bf16 has an 8-bit mantissa (2^-8 = 0.4% per rounding); the kernels and
# their references accumulate in f32 but round inputs/outputs to bf16, and
# the gradients chain several such roundings. Errors are measured as
# max|got - ref| / max|ref| over the whole tensor.
KERNEL_FWD_TOL = 2e-2
KERNEL_GRAD_TOL = 4e-2
# per-epoch mean train loss, 4 chips (fused SPMD) vs the same workers packed
# on chip 0: same seed, data order and math, different reduction order in
# bf16. Measured on the chip: relative gaps 1.4e-4 to 4.4e-4 (PERF.md, PR 21).
# (A model with dropout would not agree this closely: the two paths fold the
# dropout key differently. DenseNet has none.)
MULTICHIP_LOSS_TOL = 1e-2

# (id, kind, shape): the widths the main paths really run. attention: the
# routed decoder's fused kernel (ops/attention.py takes it for bfloat16 calls
# lowered for a TPU), queries [B,T,H,D] over one key-value head, with a window
# of T/2 and without, at both head sizes the decoders have; groupnorm: DenseNet-121's first and
# last stage at per-worker batch 128, [B,H,W,C]; xent: the CNN criterion at
# B 512 x 10 classes and the LM's 20x35 tokens over the wikitext-2 vocab.
KERNEL_CASES = (
    ("fused_attention_window512_b2_t1024_h8_d128", "attention_window", (2, 1024, 8, 128)),
    ("fused_attention_full_b2_t1024_h8_d128", "attention_full", (2, 1024, 8, 128)),
    # Qwen3-Next's full layers: heads of 256 in groups of 8 query heads
    ("fused_attention_full_b2_t1024_h8_d256", "attention_full", (2, 1024, 8, 256)),
    # Qwen3-Next's linear layers: the gated delta rule, 32 value over 16 key heads of 128
    ("fused_delta_rule_b2_t1024_h32_d128", "delta_rule", (2, 1024, 32, 128)),
    ("groupnorm_relu_128x32x32x64", "groupnorm", (128, 32, 32, 64)),
    ("groupnorm_relu_128x8x8x512", "groupnorm", (128, 8, 8, 512)),
    ("xent_512x10", "xent", (512, 10)),
    ("xent_700x33278", "xent", (700, 33278)),
)


class SmokeFailure(RuntimeError):
    """A phase ran but what came out is wrong."""


# ------------------------------------------------------------------ kernels


def kernel_case(kind: str, shape):
    """``(pallas_fn, reference_fn, arg_specs, diff_argnums)`` for one kernel
    at one shape. ``pallas_fn`` calls the repo's kernel with
    ``interpret=False`` (never the backend-chosen default); the reference is
    plain XLA computing in f32 from the same bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from dynamic_load_balance_distributeddnn_tpu.ops.attention import (
        FUSED_BLOCK,
        _blocked,
    )
    from dynamic_load_balance_distributeddnn_tpu.ops.losses import (
        per_example_cross_entropy,
    )
    from dynamic_load_balance_distributeddnn_tpu.ops.pallas import (
        fused_group_norm,
        fused_softmax_xent,
    )
    from dynamic_load_balance_distributeddnn_tpu.ops.pallas.fused_attention import (
        fused_causal_attention,
    )

    bf16, f32 = jnp.bfloat16, jnp.float32
    if kind in ("attention_window", "attention_full"):
        b, t, _, d = shape  # queries; keys and values have one head
        window = t // 2 if kind == "attention_window" else None
        q_spec = jax.ShapeDtypeStruct(shape, bf16)
        kv_spec = jax.ShapeDtypeStruct((b, t, 1, d), bf16)

        def pallas_fn(q, k, v):
            return fused_causal_attention(
                q, k, v, window, FUSED_BLOCK, interpret=False
            )

        def ref_fn(q, k, v):
            q, k, v = (a.astype(f32) for a in (q, k, v))
            return _blocked(q, k, v, window, 256)

        return pallas_fn, ref_fn, (q_spec, kv_spec, kv_spec), (0, 1, 2)
    if kind == "delta_rule":
        from dynamic_load_balance_distributeddnn_tpu.ops import linear_attention
        from dynamic_load_balance_distributeddnn_tpu.ops.pallas.delta_rule import (
            fused_delta_rule,
        )

        b, t, h, d = shape  # values; queries and keys have half the heads

        def operands(q, k, v, g, beta):
            """Normal draws as the model's operands: unit q (scaled) and k,
            g <= 0, beta in (0, 1)."""
            def unit(x):
                x = x.astype(f32)
                return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

            return ((unit(q) * d ** -0.5).astype(bf16), unit(k).astype(bf16), v,
                    -jax.nn.softplus(g), jax.nn.sigmoid(beta))

        def pallas_fn(*a):
            block = math.gcd(t, linear_attention.FUSED_BLOCK)  # as gated_delta_rule takes it
            return fused_delta_rule(*operands(*a), block_t=block, interpret=False)

        def ref_fn(*a):  # the chunked XLA form on the same bfloat16 operands
            return linear_attention._chunked(*operands(*a), linear_attention.CHUNK)

        keys = jax.ShapeDtypeStruct((b, t, h // 2, d), bf16)
        gates = jax.ShapeDtypeStruct((b, t, h), f32)
        return (pallas_fn, ref_fn, (keys, keys, jax.ShapeDtypeStruct(shape, bf16), gates, gates),
                (0, 1, 2, 3, 4))
    if kind == "groupnorm":
        c = shape[-1]
        groups = math.gcd(32, c)  # models/common.py group_norm
        specs = (
            jax.ShapeDtypeStruct(shape, bf16),
            jax.ShapeDtypeStruct((c,), f32),
            jax.ShapeDtypeStruct((c,), f32),
        )

        def pallas_fn(x, scale, bias):
            return fused_group_norm(
                x, scale, bias, groups, relu=True, interpret=False
            )

        def ref_fn(x, scale, bias, eps=1e-6):
            x = x.astype(f32)
            xg = x.reshape(x.shape[0], -1, groups, c // groups)
            mean = xg.mean(axis=(1, 3), keepdims=True)
            var = xg.var(axis=(1, 3), keepdims=True)
            y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
            return jax.nn.relu(y * scale + bias)

        return pallas_fn, ref_fn, specs, (0, 1, 2)
    if kind == "xent":
        specs = (
            jax.ShapeDtypeStruct(shape, bf16),
            jax.ShapeDtypeStruct(shape[:1], jnp.int32),
        )

        def pallas_fn(logits, labels):
            return fused_softmax_xent(logits, labels, interpret=False)

        def ref_fn(logits, labels):
            return per_example_cross_entropy(logits.astype(f32), labels)

        return pallas_fn, ref_fn, specs, (0,)
    raise ValueError(f"unknown kernel kind {kind!r}")


def grad_of(fn, diff_argnums):
    """Gradient of a non-trivial scalar of ``fn``'s output (a plain sum has
    a zero gradient through a normalization)."""
    import jax
    import jax.numpy as jnp

    def scalar(*args):
        return jnp.sum(jnp.sin(fn(*args).astype(jnp.float32)))

    return jax.grad(scalar, argnums=diff_argnums)


def _kernel_args(specs, seed: int):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = []
    for key, s in zip(keys, specs):
        if jnp.issubdtype(s.dtype, jnp.integer):
            # labels: the one integer input, classes = the logits' last dim
            out.append(jax.random.randint(key, s.shape, 0, specs[0].shape[-1], s.dtype))
        else:
            out.append(jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype))
    return tuple(out)


def _rel_err(got, ref) -> float:
    import jax
    import numpy as np

    worst = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if not np.isfinite(g).all():
            return float("inf")
        worst = max(worst, float(np.abs(g - r).max() / (np.abs(r).max() + 1e-6)))
    return worst


def kernels_phase(seed: int) -> dict:
    """Every KERNEL_CASES entry, forward and gradient, run on the chip and
    held to its XLA reference."""
    import jax

    rows = []
    for i, (case_id, kind, shape) in enumerate(KERNEL_CASES):
        pallas_fn, ref_fn, specs, argnums = kernel_case(kind, shape)
        args = _kernel_args(specs, seed + i)
        for mode, tol, got_fn, want_fn in (
            ("forward", KERNEL_FWD_TOL, pallas_fn, ref_fn),
            ("gradient", KERNEL_GRAD_TOL, grad_of(pallas_fn, argnums),
             grad_of(ref_fn, argnums)),
        ):
            compiled = jax.jit(got_fn).lower(*args).compile()
            if "tpu_custom_call" not in compiled.as_text():
                raise SmokeFailure(
                    f"kernels: {case_id} {mode} compiled without a "
                    "tpu_custom_call — the Pallas kernel is not in the program"
                )
            err = _rel_err(compiled(*args), jax.jit(want_fn)(*args))
            rows.append({"case": case_id, "mode": mode, "rel_err": err, "tol": tol})
            if not err <= tol:
                raise SmokeFailure(
                    f"kernels: {case_id} {mode} rel_err {err:.4g} > {tol}"
                )
    return {"cases": rows, "n_cases": len(rows)}


# --------------------------------------------------------------- main paths


def cnn_argv(
    run_dir: str,
    *,
    model: str = "densenet",
    dataset: str = "cifar10",
    batch: int = 512,
    n_train: int = 10240,
    epochs: int = 3,
    dbs: bool = True,
    straggler: str = "3,1,1,1",
    seed: int = 1234,
    extra=(),
):
    """The command line of the CNN main path, writing under ``run_dir``."""
    argv = [
        "-d", "false", "-ws", "4", "-b", str(batch), "-m", model,
        "-ds", dataset, "-e", str(epochs), "-dbs", str(dbs).lower(),
        "--n_train", str(n_train), "--precision", "bfloat16",
        "--seed", str(seed),
        "--log_dir", os.path.join(run_dir, "logs"),
        "--stat_dir", os.path.join(run_dir, "statis"),
    ]
    if straggler:
        argv += ["--straggler", straggler, "--fault_mode", "compute"]
    return argv + list(extra)


def lm_argv(run_dir: str, *, n_train: int = 16880, seed: int = 1234, extra=()):
    """The LM leg: the model's own width, bptt 35, ws 4, one epoch of 6 steps
    (80 columns x (6 x 35 + 1) tokens). Depth of the run is what is cut: the
    elastic scan path unrolls a whole window of steps into one program, and
    a 16-step window of this model took 9.5 min to compile on the v5e host
    (PERF.md, PR 21) — a smoke cannot afford that inside its limit."""
    return [
        "-d", "false", "-ws", "4", "-b", "80", "-m", "transformer",
        "-ds", "wikitext2", "-e", "1", "--bptt", "35",
        "--n_train", str(n_train), "--precision", "bfloat16",
        "--seed", str(seed),
        "--lm_data_dir", os.path.join(_HERE, "rnn_data", "wikitext-2"),
        "--log_dir", os.path.join(run_dir, "logs"),
        "--stat_dir", os.path.join(run_dir, "statis"),
    ] + list(extra)


def train_phase(argv) -> tuple:
    """Run one configuration through ``cli.run`` (the body of ``cli.main``)
    and report what the run itself recorded. Returns ``(report, trainer)``.
    Raises when nothing trained — the idempotence probe's "skipping" is a
    failure here, never a silent pass — on a non-finite loss, and on any
    AOT compile job that failed (the engine replaces those by lazy jit with
    a warning, which would hide exactly what this script exists to show)."""
    from dynamic_load_balance_distributeddnn_tpu import cli

    trainer = cli.run(argv)
    if trainer is None:
        raise SmokeFailure(
            "cli.run trained nothing: a 'done' sentinel from an earlier run "
            "made the idempotence probe skip it"
        )
    data, meta = trainer.recorder.data, trainer.recorder.meta
    steps = int(sum(data.get("steps", [])))
    if steps <= 0:
        raise SmokeFailure("the run finished without executing a step")
    losses = [float(x) for x in data["train_loss"]]
    if not all(math.isfinite(x) for x in losses + [float(v) for v in data["val_loss"]]):
        raise SmokeFailure(f"non-finite loss: train {losses}, val {data['val_loss']}")
    aot = meta.get("aot_stats") or {}
    if aot.get("failed", 0) != 0:
        raise SmokeFailure(f"AOT compile jobs failed: {aot}")
    report = {
        "steps": steps,
        "epochs": len(losses),
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "loss_per_epoch": losses,
        "final_partition": [float(p) for p in data["partition"][-1]],
        "exec_path": meta.get("exec_path"),
        "smoke_epoch_wall_s": [round(float(w), 3) for w in _epoch_walls(data)],
        "compile_s_per_epoch": [round(float(c), 2) for c in data.get("compile_s", [])],
        "compiles_per_epoch": [
            int(f + b) for f, b in zip(
                data.get("xla_compiles", []),
                data.get("aot_compiles", [0] * len(losses)),
            )
        ],
        "aot_stats": {k: aot.get(k) for k in ("submitted", "compiled", "failed")},
        # max over the devices that host a worker (one device on one chip)
        "probe_dispatch_overhead_s": meta.get("probe_dispatch_overhead_s"),
        "nominal_injection_profile": meta.get("straggler_factors"),
        "realized_injection_profile": meta.get("realized_injection_profile"),
        "synthetic_data": meta.get("synthetic"),
    }
    return report, trainer


def _epoch_walls(data):
    total = [0.0] + [float(w) for w in data["wallclock_time"]]
    return [b - a for a, b in zip(total, total[1:])]


def cnn_main_path(run_dir: str, **kw) -> dict:
    """The CNN main path under the 3:1 straggler with DBS re-planning.
    ``run_dir`` must be this run's own (the caller makes a fresh one)."""
    report, _ = train_phase(cnn_argv(run_dir, **kw))
    share0 = report["final_partition"][0]
    if not share0 < 0.25:
        raise SmokeFailure(
            f"DBS left the 3x straggler at share {share0} (>= 0.25) "
            f"after {report['epochs']} epochs: {report['final_partition']}"
        )
    return report


def lm_main_path(run_dir: str, **kw) -> dict:
    report, _ = train_phase(lm_argv(run_dir, **kw))
    return report


# ------------------------------------------------------------- four chips


def multichip_phases(out_root: str, seed: int, emit, **kw) -> None:
    """The cross-chip data-parallel path and what it is compared with."""
    import jax

    recipe = dict(dbs=False, straggler="", n_train=2048, epochs=3, seed=seed)
    recipe.update(kw)

    def spread():
        report, trainer = train_phase(cnn_argv(_fresh_dir(out_root, "dp4"), **recipe))
        devices_with_state = sorted(
            {
                int(s.device.id)
                for leaf in jax.tree_util.tree_leaves(trainer.state.params)
                for s in leaf.addressable_shards
            }
        )
        in_use = {
            str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()
        }
        texts = [
            c.as_text() for c in map(trainer._aot.get, trainer._aot.keys())
            if c is not None
        ]
        n_allreduce = sum("all-reduce" in t for t in texts)
        report.update(
            devices_with_state=devices_with_state,
            peak_bytes_in_use_by_device=in_use,
            compiled_programs=len(texts),
            programs_with_all_reduce=n_allreduce,
        )
        if len(devices_with_state) != 4:
            raise SmokeFailure(f"state lives on devices {devices_with_state}, not on 4")
        if any(v == 0 for v in in_use.values()):
            raise SmokeFailure(f"a chip never held a byte: {in_use}")
        if n_allreduce == 0:
            raise SmokeFailure("no compiled step contains an all-reduce")
        return report

    def packed():
        report, _ = train_phase(
            cnn_argv(_fresh_dir(out_root, "dp1"), extra=("-gpu", "0"), **recipe)
        )
        return report

    four = emit("dp_four_chips", spread)
    one = emit("dp_all_on_chip0", packed)

    def compare():
        a, b = four["loss_per_epoch"], one["loss_per_epoch"]
        gaps = [abs(x - y) / max(abs(y), 1e-6) for x, y in zip(a, b)]
        if len(a) != len(b) or not all(g <= MULTICHIP_LOSS_TOL for g in gaps):
            raise SmokeFailure(
                f"loss sequences disagree: 4 chips {a} vs chip 0 {b} "
                f"(rel gaps {gaps}, tol {MULTICHIP_LOSS_TOL})"
            )
        return {"rel_gaps": gaps, "tol": MULTICHIP_LOSS_TOL}

    emit("dp_losses_agree", compare)


# ------------------------------------------------------------------ driver


def _fresh_dir(out_root: str, name: str) -> str:
    os.makedirs(out_root, exist_ok=True)
    return tempfile.mkdtemp(prefix=f"{name}_", dir=out_root)


class _Counters:
    """Process-wide compile and persistent-cache counters (jax.monitoring)."""

    def __init__(self):
        from jax import monitoring

        from dynamic_load_balance_distributeddnn_tpu.analysis.guards import (
            compile_seconds,
        )

        self.hits = self.misses = 0
        self._compile_seconds = compile_seconds
        compile_seconds()  # installs the duration listener now
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self._compile_seconds(), self.hits, self.misses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1234)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: no TPU (jax.devices()[0].platform = {dev0.platform!r}); "
            "this script has no CPU continuation\n"
        )
        return 2
    if len(devices) < args.chips:
        sys.stderr.write(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}\n"
        )
        return 2

    from dynamic_load_balance_distributeddnn_tpu.compile_cache import (
        enable_compile_cache,
    )
    from dynamic_load_balance_distributeddnn_tpu.runtime.native import (
        native_available,
    )

    cache_dir = enable_compile_cache()
    counters = _Counters()
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
    }

    def emit(name, fn):
        """Run one phase; print its JSON line; any exception ends the run
        non-zero (after the line that says which phase failed)."""
        t0 = time.perf_counter()
        c0, h0, m0 = counters.snapshot()
        try:
            report = fn()
        except BaseException as e:
            print(json.dumps({"phase": name, "ok": False, "error": repr(e)[:2000]}),
                  flush=True)
            raise
        c1, h1, m1 = counters.snapshot()
        line = {
            "phase": name,
            "ok": True,
            "smoke_seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(c1 - c0, 2),
            "cache_dir": cache_dir,
            "cache_hits": h1 - h0,
            "cache_misses": m1 - m0,
            "peak_hbm_bytes": max(
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices
            ),
            **report,
        }
        print(json.dumps(line), flush=True)
        return report

    try:
        emit("device", lambda: {
            **device,
            "host_runtime": "native" if native_available() else "numpy",
            "jax": jax.__version__,
        })
        if args.chips == 4:
            multichip_phases(OUT_ROOT, args.seed, emit)
        else:
            emit("cnn_main_path", lambda: cnn_main_path(
                _fresh_dir(OUT_ROOT, "cnn"), seed=args.seed))
            emit("lm_main_path", lambda: lm_main_path(
                _fresh_dir(OUT_ROOT, "lm"), seed=args.seed))
            emit("kernels", lambda: kernels_phase(args.seed))
    except Exception as e:
        import traceback

        traceback.print_exc()
        sys.stderr.write(f"chip_smoke: FAILED: {e!r}\n")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
