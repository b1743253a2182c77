#!/usr/bin/env python
"""Pallas-vs-XLA kernel microbenchmark on the real chip.

For each custom kernel behind ``--use_pallas`` (ops/pallas/: fused GroupNorm,
fused softmax-xent) and each shape the model zoo actually uses, time the
jitted forward and forward+grad against the plain-XLA equivalent the kernel
would replace (the reference delegates these to cuDNN, SURVEY §2.2; here the
alternative is stock XLA fusion).

Writes <out_dir>/kernel_bench_<platform>.json and a markdown table to
<out_dir>/KERNELS.md.

Usage: python scripts/kernel_bench.py --out_dir DIR [--repeats 30] [--quick]
       python scripts/kernel_bench.py --out_dir DIR --only cell_attention
       python scripts/kernel_bench.py --out_dir DIR --only delta_rule
       (PERF.md's table of the Trinity-Mini cell's attention: blocked XLA
       against each fused candidate, window and full)
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compile cache, placed by the one helper every entry uses
from dynamic_load_balance_distributeddnn_tpu.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import jax
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.ops.losses import per_example_cross_entropy
from dynamic_load_balance_distributeddnn_tpu.ops.pallas.groupnorm import fused_group_norm
from dynamic_load_balance_distributeddnn_tpu.ops.pallas.xent import fused_softmax_xent


def timeit(fn, *args, repeats=30):
    """Median wall of a jitted call, post-warmup, fully fenced."""
    out = fn(*args)
    jax.block_until_ready(out)
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


# ------------------------------------------------------------ XLA baselines


def xla_group_norm(x, scale, bias, groups, eps=1e-6):
    shape = x.shape
    c = shape[-1]
    xg = x.reshape(shape[0], -1, groups, c // groups).astype(jnp.float32)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = (xg - mean) / jnp.sqrt(var + eps)
    y = y.reshape(shape[0], -1, c) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.reshape(shape).astype(x.dtype)


# ------------------------------------------------------------ benchmark legs


# ------------------------------------------- the Trinity-Mini cell's attention

# (block_q, block_k) of ops/pallas/fused_attention.py, and of JAX's bundled
# splash attention (block_q, block_kv, block_kv_compute, fused backward): what
# PR 28 tried on the chip
FUSED_BLOCKS = [(256, 256), (256, 512), (512, 256), (512, 512), (512, 1024), (1024, 512), (1024, 1024)]
SPLASH_BLOCKS = [(512, 512, 512, True), (512, 1024, 512, True), (1024, 1024, 512, True),
                 (512, 2048, 512, True), (512, 1024, 256, True), (512, 1024, 512, False)]


def splash_attention(q, k, v, window, block_q, block_kv, block_kv_compute, fused_bwd):
    """JAX's bundled splash attention in its MQA form behind the model's
    ``[B, T, H, D]`` interface: the scale goes into q, the layout changes
    happen here (and are timed), the key-value heads and the batch are mapped."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    b, t, h, d = q.shape
    hkv = k.shape[2]
    mask = sm.CausalMask((t, t))
    if window is not None:
        mask = sm.LogicalAnd(mask, sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    dq = {} if fused_bwd else {"block_q_dq": block_q, "block_kv_dq": block_kv}
    sizes = sk.BlockSizes(
        block_q=block_q, block_kv=block_kv, block_kv_compute=block_kv_compute,
        block_q_dkv=block_q, block_kv_dkv=block_kv, block_kv_dkv_compute=block_kv_compute,
        use_fused_bwd_kernel=fused_bwd, **dq)
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([mask] * (h // hkv)), block_sizes=sizes)
    q = (q * (1.0 / d ** 0.5)).astype(q.dtype)
    q = q.reshape(b, t, hkv, h // hkv, d).transpose(0, 2, 3, 1, 4)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    o = jax.vmap(jax.vmap(kernel))(q, k, v)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)


def cell_attention_candidates(window):
    """``(name, attention(q, k, v))`` of every form the cell's attention was
    measured in."""
    from dynamic_load_balance_distributeddnn_tpu.ops import attention
    from dynamic_load_balance_distributeddnn_tpu.ops.pallas.fused_attention import (
        fused_causal_attention,
    )

    yield "blocked_xla_256", lambda q, k, v: attention._blocked(q, k, v, window, 256)
    yield "default_path", lambda q, k, v: attention.blocked_causal_attention(q, k, v, window)
    for bq, bk in FUSED_BLOCKS:
        yield f"fused_{bq}x{bk}", (
            lambda q, k, v, bq=bq, bk=bk: fused_causal_attention(q, k, v, window, bq, bk))
    for blocks in SPLASH_BLOCKS:
        yield "splash_" + "x".join(str(int(x)) for x in blocks), (
            lambda q, k, v, blocks=blocks: splash_attention(q, k, v, window, *blocks))


def bf16_peak():
    """The device's published bf16 FLOP/s (``benchmark/peaks.json``), None for
    a device the table does not hold: no share of a peak is printed then."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        return json.load(f).get(jax.devices()[0].device_kind, {}).get("bf16_flops_per_s")


def best_of(fn, *args, sets=3, calls=10):
    """Best of ``sets`` means over ``calls`` calls in a row, seconds a call."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def bench_cell_attention(results, dtype, repeats, quick):
    """The attention of ``trinity_mini.ws4_even_dbs`` as one worker's step calls
    it: 2 columns of 4,096 tokens, 32 query and 4 key-value heads of 128, the
    window of 2,048 and none. The FLOPs are the visible pairs' alone (two
    products forward, five backward), as a share of the device's bf16 peak."""
    del repeats, quick
    peak = bf16_peak()
    b, t, h, hkv, d = 2, 4096, 32, 4, 128
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (b, t, h, d), dtype)
    k = jax.random.normal(keys[1], (b, t, hkv, d), dtype)
    v = jax.random.normal(keys[2], (b, t, hkv, d), dtype)
    w = jax.random.normal(keys[3], (b, t, h, d), jnp.float32)
    for window in (2048, None):
        pairs = b * h * sum(min(i + 1, window or t) for i in range(t))
        for name, fn in cell_attention_candidates(window):
            row = {"kernel": "cell_attention", "form": name, "window": window,
                   "shape": f"B{b}xT{t}xH{h}/{hkv}xD{d}", "dtype": str(dtype.__name__)}
            try:
                fwd = jax.jit(fn)
                grad = jax.jit(jax.grad(
                    lambda q, k, v, w, fn=fn: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
                    argnums=(0, 1, 2)))
                row["fwd_ms"] = best_of(fwd, q, k, v) * 1e3
                row["fwd_bwd_ms"] = best_of(grad, q, k, v, w) * 1e3
                if peak:
                    row["fwd_pct_of_peak"] = 100 * 4 * d * pairs / (row["fwd_ms"] * 1e-3) / peak
                    row["fwd_bwd_pct_of_peak"] = (
                        100 * 14 * d * pairs / (row["fwd_bwd_ms"] * 1e-3) / peak)
            except Exception as e:  # a candidate the compiler refuses is a result
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            results.append(row)
            print(json.dumps(row), flush=True)


def bench_delta_rule(results, dtype, repeats, quick):
    """The mixers of ``qwen3_next.ws4_even_dbs`` as one worker's step calls
    them, 2 columns of 4,096 tokens: the gated delta rule alone (32 heads of
    128 x 128; its FLOPs are the recurrence's three products a token and
    head, whatever form computes them) in the chunked XLA form and in the
    fused one at each block, on the same operands, then as the model calls it
    (q and k at their 16 key heads), and, beside it, the full layers'
    attention (16 query / 2 key-value heads of 256)."""
    del repeats, quick
    from dynamic_load_balance_distributeddnn_tpu.ops import attention, linear_attention
    from dynamic_load_balance_distributeddnn_tpu.ops.pallas.delta_rule import fused_delta_rule

    peak = bf16_peak()
    b, t, h, d = 2, 4096, 32, 128
    keys = jax.random.split(jax.random.PRNGKey(5), 6)

    def unit(key):
        x = jax.random.normal(key, (b, t, h, d), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    rule = ((unit(keys[0]) * d ** -0.5).astype(dtype), unit(keys[1]).astype(dtype),
            jax.random.normal(keys[2], (b, t, h, d), dtype),
            -jax.random.uniform(keys[3], (b, t, h), jnp.float32, 0.0, 2.0),
            jax.random.uniform(keys[4], (b, t, h), jnp.float32))
    hq, hkv, dq = 16, 2, 256
    attn = (jax.random.normal(keys[0], (b, t, hq, dq), dtype),
            jax.random.normal(keys[1], (b, t, hkv, dq), dtype),
            jax.random.normal(keys[2], (b, t, hkv, dq), dtype))
    pairs = b * hq * t * (t + 1) // 2
    rule_shape, rule_flops = f"B{b}xT{t}xH{h}xD{d}x{d}", 3 * 2 * b * t * h * d * d
    cases = (
        ("delta_rule", "chunked_xla_64",
         lambda *a: linear_attention._chunked(*a, linear_attention.CHUNK), rule, rule_shape,
         rule_flops, 3),
        *((("delta_rule", f"fused_{block}",
            lambda *a, block=block: fused_delta_rule(*a, block_t=block), rule, rule_shape,
            rule_flops, 3) for block in (128, 256, 512))),
        ("delta_rule", "default_path_16_key_heads", linear_attention.gated_delta_rule,
         (rule[0][:, :, ::2], rule[1][:, :, ::2]) + rule[2:], f"B{b}xT{t}xH{h}/{h // 2}xD{d}x{d}",
         rule_flops, 3),
        ("cell_attention_256", "blocked_xla_256",
         lambda q, k, v: attention._blocked(q, k, v, None, 256), attn,
         f"B{b}xT{t}xH{hq}/{hkv}xD{dq}", 4 * dq * pairs, 3.5),
        ("cell_attention_256", "default_path", attention.blocked_causal_attention, attn,
         f"B{b}xT{t}xH{hq}/{hkv}xD{dq}", 4 * dq * pairs, 3.5),
    )
    for kernel, form, fn, args, shape, fwd_flops, bwd_over_fwd in cases:
        row = {"kernel": kernel, "form": form, "shape": shape, "dtype": str(dtype.__name__)}
        try:
            w = jax.random.normal(keys[5], jax.eval_shape(fn, *args).shape, jnp.float32)
            grad = jax.jit(jax.grad(
                lambda *a, fn=fn: jnp.sum(fn(*a[:-1]).astype(jnp.float32) * a[-1]),
                argnums=tuple(range(len(args)))))
            row["fwd_ms"] = best_of(jax.jit(fn), *args) * 1e3
            row["fwd_bwd_ms"] = best_of(grad, *args, w) * 1e3
            if peak:
                row["fwd_pct_of_peak"] = 100 * fwd_flops / (row["fwd_ms"] * 1e-3) / peak
                row["fwd_bwd_pct_of_peak"] = (
                    100 * bwd_over_fwd * fwd_flops / (row["fwd_bwd_ms"] * 1e-3) / peak)
        except Exception as e:  # a form the compiler refuses is a result
            row["error"] = f"{type(e).__name__}: {e}"[:300]
        results.append(row)
        print(json.dumps(row), flush=True)


def bench_groupnorm(results, dtype, repeats, quick):
    """CNN shapes: 32x32 CIFAR maps through the zoo's widths, GroupNorm(32)
    (Net/Resnet.py:11-13); batch = per-worker 128 of the B=512/ws=4 recipe."""
    shapes = [(128, 32, 32, 64), (128, 16, 16, 256), (128, 8, 8, 512)]
    if not quick:
        shapes.append((256, 32, 32, 128))
    for b, hh, ww, c in shapes:
        groups = 32
        kx, ks = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(kx, (b, hh, ww, c), dtype)
        scale = jax.random.normal(ks, (c,), jnp.float32)
        bias = jnp.zeros((c,), jnp.float32)

        # plain GN, and the GN->relu pair every CNN block actually runs
        # (models/*: nn.relu(group_norm(...))) with the kernel's fused
        # relu epilogue vs XLA fusing the pair itself
        variants = [
            ("fused_group_norm",
             lambda x, s, b_: fused_group_norm(x, s, b_, groups, interpret=False),
             lambda x, s, b_: xla_group_norm(x, s, b_, groups)),
            ("fused_group_norm_relu",
             # bare kernel call, as the models run it (group_norm(relu=True)
             # with NO outer relu — an outer relu over the custom call would
             # re-add the elementwise pass the epilogue removes)
             lambda x, s, b_: fused_group_norm(
                 x, s, b_, groups, interpret=False, relu=True
             ),
             lambda x, s, b_: jax.nn.relu(xla_group_norm(x, s, b_, groups))),
        ]
        for kname, pfn, bfn in variants:
            pall = jax.jit(pfn)
            base = jax.jit(bfn)
            pall_g = jax.jit(jax.grad(lambda x, s, b_: pfn(x, s, b_).sum(), argnums=(0, 1, 2)))
            base_g = jax.jit(jax.grad(lambda x, s, b_: bfn(x, s, b_).sum(), argnums=(0, 1, 2)))
            row = {
                "kernel": kname,
                "shape": f"B{b}x{hh}x{ww}xC{c}/g{groups}",
                "dtype": str(dtype.__name__),
            }
            try:
                row["fwd_pallas_ms"] = timeit(pall, x, scale, bias, repeats=repeats) * 1e3
                row["fwd_xla_ms"] = timeit(base, x, scale, bias, repeats=repeats) * 1e3
                row["grad_pallas_ms"] = timeit(pall_g, x, scale, bias, repeats=repeats) * 1e3
                row["grad_xla_ms"] = timeit(base_g, x, scale, bias, repeats=repeats) * 1e3
            except Exception as e:
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            results.append(row)
            print(json.dumps(row), flush=True)


def bench_xent(results, dtype, repeats, quick):
    """Loss shapes: CIFAR [B,10/100] and the LM's [B*bptt, V=33278]
    (dbs.py:270, 337)."""
    shapes = [(512, 10), (512, 100), (700, 33278)]
    if not quick:
        shapes.append((2800, 33278))
    for r, v in shapes:
        kx, kl = jax.random.split(jax.random.PRNGKey(2))
        logits = jax.random.normal(kx, (r, v), dtype)
        labels = jax.random.randint(kl, (r,), 0, v)

        pall = jax.jit(lambda lg, lb: fused_softmax_xent(lg, lb, interpret=False).sum())
        base = jax.jit(lambda lg, lb: per_example_cross_entropy(lg, lb).sum())
        pall_g = jax.jit(jax.grad(lambda lg, lb: fused_softmax_xent(lg, lb, interpret=False).sum(), argnums=0))
        base_g = jax.jit(jax.grad(lambda lg, lb: per_example_cross_entropy(lg, lb).sum(), argnums=0))
        row = {"kernel": "fused_softmax_xent", "shape": f"R{r}xV{v}", "dtype": str(dtype.__name__)}
        try:
            row["fwd_pallas_ms"] = timeit(pall, logits, labels, repeats=repeats) * 1e3
            row["fwd_xla_ms"] = timeit(base, logits, labels, repeats=repeats) * 1e3
            row["grad_pallas_ms"] = timeit(pall_g, logits, labels, repeats=repeats) * 1e3
            row["grad_xla_ms"] = timeit(base_g, logits, labels, repeats=repeats) * 1e3
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:300]
        results.append(row)
        print(json.dumps(row), flush=True)


def to_markdown(results, platform, kind):
    lines = [
        f"# Kernel microbenchmarks — {platform} ({kind})",
        "",
        "Median jitted wall (ms), post-warmup, `block_until_ready`-fenced.",
        "`speedup` = XLA / Pallas (>1 means the Pallas kernel wins).",
        "Generated by `scripts/kernel_bench.py`.",
        "",
        "| kernel | shape | dtype | fwd pallas | fwd xla | fwd speedup | grad pallas | grad xla | grad speedup |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        if r["kernel"] in ("cell_attention", "cell_attention_256", "delta_rule"):
            continue  # printed as JSON lines and kept in the .json: other columns
        if "error" in r:
            lines.append(
                f"| {r['kernel']} | {r['shape']} | {r['dtype']} | ERROR: {r['error'][:80]} | | | | | |"
            )
            continue
        fs = r["fwd_xla_ms"] / r["fwd_pallas_ms"]
        gs = r["grad_xla_ms"] / r["grad_pallas_ms"]
        lines.append(
            f"| {r['kernel']} | {r['shape']} | {r['dtype']} "
            f"| {r['fwd_pallas_ms']:.3f} | {r['fwd_xla_ms']:.3f} | {fs:.2f}x "
            f"| {r['grad_pallas_ms']:.3f} | {r['grad_xla_ms']:.3f} | {gs:.2f}x |"
        )
    return "\n".join(lines) + "\n"


LEGS = {"groupnorm": bench_groupnorm, "xent": bench_xent,
        "cell_attention": bench_cell_attention, "delta_rule": bench_delta_rule}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    ap.add_argument("--out_dir", required=True)
    ap.add_argument("--only", default="", choices=["", *LEGS],
                    help="run one leg (default: groupnorm and xent)")
    ns = ap.parse_args()

    dev = jax.devices()[0]
    platform = dev.platform
    kind = getattr(dev, "device_kind", "?")
    print(f"[kernel_bench] {platform}/{kind}", flush=True)
    dtype = jnp.bfloat16 if ns.dtype == "bfloat16" else jnp.float32

    os.makedirs(ns.out_dir, exist_ok=True)
    json_path = os.path.join(ns.out_dir, f"kernel_bench_{platform}.json")

    # Rows persist incrementally to json_path; if no row lands for
    # KB_STALL_S the backend is hung — exit non-zero.
    from dynamic_load_balance_distributeddnn_tpu.runtime.watchdog import (
        arm_stall_watchdog,
    )

    arm_stall_watchdog(
        json_path + ".hb",
        float(os.environ.get("KB_STALL_S", 900)),
        extra_paths=(json_path,),
    )

    class _IncrementalResults(list):
        """Persist after every row — a runtime outage mid-bench must not
        lose completed measurements."""

        def append(self, row):
            super().append(row)
            payload = {
                "platform": platform,
                "device_kind": kind,
                "dtype": ns.dtype,
                "results": list(self),
            }
            tmp = json_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=2)
            os.replace(tmp, json_path)
            with open(os.path.join(ns.out_dir, "KERNELS.md"), "w") as f:
                f.write(to_markdown(self, platform, kind))

    results = _IncrementalResults()
    for leg in [ns.only] if ns.only else ["groupnorm", "xent"]:
        LEGS[leg](results, dtype, ns.repeats, ns.quick)
    print(f"[kernel_bench] wrote {json_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
