"""AFMoE decoder (``model_type`` ``afmoe``, Arcee Trinity), the plain way.

``model`` holds the published keys (letter for letter) with the cut beside
them: ``layers`` (the published layers kept), ``num_experts`` (the routed
experts HELD here, ``first_expert`` the first of them) next to
``published_num_experts`` (what the router ranges over), ``vocab_size`` (the
slice) and ``seq_len``. x is the residual stream, eps = ``rms_norm_eps``::

    h0 = E[ids] * sqrt(hidden)                     (mup_enabled)
    a = RMSNorm1(h);  h = h + RMSNorm2(Attn(a))
    m = RMSNorm3(h);  h = h + RMSNorm4(FFN(m))
    logits = RMSNorm(h_L) @ W_head                 (untied)

``Attn``: q = a W_q (heads x head_dim), k = a W_k, v = a W_v (kv heads), g =
a W_g; q and k take an RMSNorm over each head; rotary positions (whole head,
``rotate_half`` pairs) on ``sliding_attention`` layers only; causal softmax
attention at 1/sqrt(head_dim), a query at i seeing keys j with 0 <= i - j <
``sliding_window`` on those layers; out = (o * sigmoid(g)) W_o. ``FFN``:
W_down(silu(W_gate m) * W_up m), width ``intermediate_size``, on published
layers below ``num_dense_layers``; on later ones s = sigmoid(m W_r) over all
published experts, the ``num_experts_per_tok`` largest of s + b chosen (b the
expert bias, zero), weights s at the chosen experts / their sum
(``route_norm``) x ``route_scale``, and y = Shared(m) + the sum over chosen
experts HELD HERE of weight x Expert(m): an expert held elsewhere adds
nothing, in the program and here alike.

Every held expert is computed for every token and weighted (nought where not
chosen): no sort, no groups, no capacity. Attention is taken a block of
queries at a time against all keys under the mask, and ``loss`` takes a block
of columns one column at a time with a checkpoint per layer; both one after
the other (``lax.map``), so that 4,096 tokens in float32 fit beside the five
trees the job's recipe holds (left to itself the compiler ran the blocks side
by side and asked for 8.6 GB of temporaries). ``jax.numpy`` and ``lax.map``
only, no kernel; imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .common import cross_entropy, einsum

INIT_STD = 0.02
QUERY_BLOCK = 512


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """``x`` ``[T, H, D]``; channel ``i`` pairs with ``i + D/2``."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def visible(t: int, q0, n: int, window):
    """``[n, t]``: which of ``t`` keys each of the queries ``q0 .. q0 + n - 1``
    sees."""
    qpos = q0 + jnp.arange(n)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = qpos >= kpos
    return mask if window is None else mask & (qpos - kpos < window)


def attention(a, p, model, window, precision):
    """One column ``[T, hidden]``. The queries are taken ``QUERY_BLOCK`` at a
    time against all keys under the mask, one block after the other
    (``lax.map`` over a checkpointed body), so that one block's ``[heads,
    block, T]`` scores are all that either pass holds."""
    t = a.shape[0]
    h, hkv, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q = einsum("td,df->tf", a, p["q_kernel"], precision).reshape(t, h, d)
    k = einsum("td,df->tf", a, p["k_kernel"], precision).reshape(t, hkv, d)
    v = einsum("td,df->tf", a, p["v_kernel"], precision).reshape(t, hkv, d)
    gate = einsum("td,df->tf", a, p["gate_kernel"], precision)
    q, k = rms_norm(q, p["q_norm_scale"], eps), rms_norm(k, p["k_norm_scale"], eps)
    if window is not None:
        q, k = rotary(q, model["rope_theta"]), rotary(k, model["rope_theta"])
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(q0):
        qb = lax.dynamic_slice_in_dim(q, q0, n, axis=0)
        s = einsum("qhd,khd->hqk", qb / math.sqrt(d), k, precision)
        s = jnp.where(visible(t, q0, n, window)[None], s, jnp.finfo(jnp.float32).min)
        return einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)

    o = lax.map(jax.checkpoint(block), jnp.arange(0, t, n)).reshape(t, h * d)
    o = o * jax.nn.sigmoid(gate)
    return einsum("tf,fd->td", o, p["o_kernel"], precision)


def gated_mlp(m, w_gate, w_up, w_down, precision):
    hidden = jax.nn.silu(einsum("td,df->tf", m, w_gate, precision)) * einsum(
        "td,df->tf", m, w_up, precision)
    return einsum("tf,fd->td", hidden, w_down, precision)


def routing(m, w_router, model):
    """``[T, published experts]`` float32: each token's weight on each expert,
    nought where not chosen. The product is in float32 at ``highest`` whatever
    the precision of the rest (as the published code keeps it)."""
    scores = jax.nn.sigmoid(jnp.dot(m, w_router, precision=lax.Precision.HIGHEST))
    expert_bias = jnp.zeros((scores.shape[-1],), jnp.float32)
    _, chosen = lax.top_k(scores + expert_bias, model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * model["route_scale"]
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def expert_ffn(m, p, model, precision):
    weights = routing(m, p["router_kernel"], model)
    y = gated_mlp(m, p["shared_gate_kernel"], p["shared_up_kernel"], p["shared_down_kernel"],
                  precision)
    first = model.get("first_expert", 0)
    for e in range(model["num_experts"]):  # the held ones; the rest add nothing here
        out = gated_mlp(m, p["experts_gate_kernel"][e], p["experts_up_kernel"][e],
                        p["experts_down_kernel"][e], precision)
        y = y + weights[:, first + e][:, None] * out
    return y


def kept_layers(model):
    return list(model.get("layers") or range(model["num_hidden_layers"]))


def block(h, p, model, published_index, precision):
    eps = model["rms_norm_eps"]
    kind = model["layer_types"][published_index]
    window = model["sliding_window"] if kind == "sliding_attention" else None
    a = rms_norm(h, p["norm1_scale"], eps)
    h = h + rms_norm(attention(a, p["attn"], model, window, precision), p["norm2_scale"], eps)
    m = rms_norm(h, p["norm3_scale"], eps)
    if published_index < model["num_dense_layers"]:
        f = p["ffn"]
        ffn = gated_mlp(m, f["gate_kernel"], f["up_kernel"], f["down_kernel"], precision)
    else:
        ffn = expert_ffn(m, p["moe"], model, precision)
    return h + rms_norm(ffn, p["norm4_scale"], eps)


def column_logits(p, ids, model, precision, checkpoint=False):
    """One column: int32 ``[T]`` -> float32 logits ``[T, vocab]``."""
    h = p["embedding"][ids]
    if model["mup_enabled"]:
        h = h * math.sqrt(model["hidden_size"])
    for i, published_index in enumerate(kept_layers(model)):
        def run(hh, pp, published_index=published_index):
            return block(hh, pp, model, published_index, precision)

        h = (jax.checkpoint(run) if checkpoint else run)(h, p[f"layer_{i}"])
    h = rms_norm(h, p["norm_scale"], model["rms_norm_eps"])
    return einsum("td,dv->tv", h, p["head_kernel"], precision)


def forward(params, x, model: dict, precision: str = "f32"):
    p = params["params"]
    return lax.map(lambda ids: column_logits(p, ids, model, precision), x)


def loss(params, x, y, weights, model: dict, precision: str = "f32"):
    """The next-token loss alone (no auxiliary term is built), one column
    after the other with a checkpoint per column and per layer: recomputation
    is still plain mathematics, and one column's one layer is all the
    backward pass holds."""
    p = params["params"]

    def column(ids_labels):
        ids, labels = ids_labels
        return cross_entropy(column_logits(p, ids, model, precision, checkpoint=True), labels)

    losses = lax.map(jax.checkpoint(column), (x, y))
    return jnp.sum(losses * weights), losses


def param_shapes(model: dict):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    d, hd = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    f, fe = model["intermediate_size"], model["moe_intermediate_size"]
    fs, held = fe * model["num_shared_experts"], model["num_experts"]
    vocab = model["vocab_size"]
    routed_over = model.get("published_num_experts", model["num_experts"])
    p = {"embedding": f32(vocab, d), "norm_scale": f32(d), "head_kernel": f32(d, vocab)}
    for i, published_index in enumerate(kept_layers(model)):
        layer = {f"norm{n}_scale": f32(d) for n in (1, 2, 3, 4)}
        layer["attn"] = {"q_kernel": f32(d, hq), "k_kernel": f32(d, hkv), "v_kernel": f32(d, hkv),
                         "gate_kernel": f32(d, hq), "o_kernel": f32(hq, d),
                         "q_norm_scale": f32(hd), "k_norm_scale": f32(hd)}
        if published_index < model["num_dense_layers"]:
            layer["ffn"] = {"gate_kernel": f32(d, f), "up_kernel": f32(d, f),
                            "down_kernel": f32(f, d)}
        else:
            layer["moe"] = {
                "router_kernel": f32(d, routed_over),
                "shared_gate_kernel": f32(d, fs), "shared_up_kernel": f32(d, fs),
                "shared_down_kernel": f32(fs, d),
                "experts_gate_kernel": f32(held, d, fe), "experts_up_kernel": f32(held, d, fe),
                "experts_down_kernel": f32(held, fe, d)}
        p[f"layer_{i}"] = layer
    return {"params": p}


def init_std(path: str, shape):
    """Every leaf at the family's 0.02: kernels and the embedding around 0,
    norm scales around 1 (not exactly 1, so that each one's gradient is
    exercised)."""
    return INIT_STD
