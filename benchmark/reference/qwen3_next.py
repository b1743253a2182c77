"""Qwen3-Next decoder (``model_type`` ``qwen3_next``), the plain way.

``model`` holds the published keys (letter for letter) with the cut beside
them, as ``afmoe.py``'s does: ``layers`` (the published layers kept),
``num_experts`` (the routed experts HELD here, ``first_expert`` the first of
them) next to ``published_num_experts`` (what the router ranges over),
``vocab_size`` (the slice) and ``seq_len``. x is the residual stream, ``Norm``
the zero-centred RMSNorm ``x / rms(x) * (1 + w)``, eps = ``rms_norm_eps``::

    h0 = E[ids]
    h = x + Mixer(Norm1(x));  out = h + FFN(Norm2(h))
    logits = Norm(h_L) @ W_head                    (untied)

``Mixer`` on published layer i is full attention where ``(i + 1) %
full_attention_interval == 0``, else linear attention.

*Linear attention* (Gated DeltaNet), Hk key heads of Dk, Hv value heads of Dv:
[q | k | v | z] = a W_qkvz (widths Hk Dk, Hk Dk, Hv Dv, Hv Dv), [b | a'] = a
W_ba (Hv each). concat(q, k, v) goes through a causal depthwise convolution of
``linear_conv_kernel_dim`` taps (y_t = sum_i w_i x_(t - taps + 1 + i), nothing
before the window's start, no bias) and SiLU. Value head h reads query and key
head h // (Hv / Hk); q and k are divided by their L2 norm over the head
(x / sqrt(sum x^2 + 1e-6)), q then by sqrt(Dk). beta_t = sigmoid(b_t), g_t =
-exp(A_log) softplus(a'_t + dt_bias). With S [Dk, Dv] = 0 at the window's
start, token by token::

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;  o_t = S^T q_t

out = (o / rms(o) * w * SiLU(z)) W_o, the norm per head with a plain weight.

*Full attention*: each head's share of a W_q is its query beside a gate of
the same size; k = a W_k, v = a W_v (kv heads); the zero-centred RMSNorm over
each head of q and k; rotary positions (``rotate_half`` pairs) on the first
``partial_rotary_factor`` of the head; causal softmax attention at
1/sqrt(head_dim); out = (o * sigmoid(gate)) W_o.

``FFN``: p = softmax(m W_r) over all published experts in float32, the
``num_experts_per_tok`` largest chosen, their p divided by their sum
(``norm_topk_prob``), and y = sigmoid(m . w_sg) Shared(m) + the sum over
chosen experts HELD HERE of weight x Expert(m), every expert
W_down(silu(W_gate m) * W_up m): an expert held elsewhere adds nothing, in the
program and here alike.

The delta rule is the recurrence as written, a token at a time, never in
chunks: a ``lax.scan`` over blocks of ``TOKEN_BLOCK`` tokens, each a
``jax.checkpoint`` around a ``lax.scan`` over its tokens, so that the backward
pass holds the states between blocks and one block's steps (every step's
state would be 2 MB a token, 8.6 GB a layer at 4,096). Every held expert is
computed for every token and weighted (nought where not chosen). Attention is
taken a block of queries at a time against all keys under the mask, and
``loss`` takes one column at a time with a checkpoint per layer, all one after
the other (``lax.map``). ``jax.numpy``, ``lax.scan`` and ``lax.map`` only, no
kernel; imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .common import cross_entropy, einsum

INIT_STD = 0.02
QUERY_BLOCK = 512
TOKEN_BLOCK = 64
# the draws of the two leaves that set the decay (see `init_std`)
A_LOG_STD = 1.0
DT_BIAS_STD = 0.5


def zero_centred_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, theta):
    """``x`` ``[T, H, D]``, all ``D`` turned; channel ``i`` pairs with ``i + D/2``."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def full_attention(a, p, model, precision):
    """One column ``[T, hidden]``; the queries ``QUERY_BLOCK`` at a time
    against all keys under the causal mask, one block after the other."""
    t = a.shape[0]
    h, hkv, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q_gate = einsum("td,df->tf", a, p["q_kernel"], precision).reshape(t, h, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:].reshape(t, h * d)
    k = einsum("td,df->tf", a, p["k_kernel"], precision).reshape(t, hkv, d)
    v = einsum("td,df->tf", a, p["v_kernel"], precision).reshape(t, hkv, d)
    q = zero_centred_norm(q, p["q_norm_weight"], eps)
    k = zero_centred_norm(k, p["k_norm_weight"], eps)
    turned = int(d * model["partial_rotary_factor"])
    q, k = (jnp.concatenate([rotary(x[..., :turned], model["rope_theta"]), x[..., turned:]], -1)
            for x in (q, k))
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(q0):
        qb = lax.dynamic_slice_in_dim(q, q0, n, axis=0)
        s = einsum("qhd,khd->hqk", qb / math.sqrt(d), k, precision)
        seen = q0 + jnp.arange(n)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(seen[None], s, jnp.finfo(jnp.float32).min)
        return einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)

    o = lax.map(jax.checkpoint(block), jnp.arange(0, t, n)).reshape(t, h * d)
    return einsum("tf,fd->td", o * jax.nn.sigmoid(gate), p["o_kernel"], precision)


def causal_conv(x, w):
    """``x`` ``[T, C]``, ``w`` ``[taps, C]``: ``y_t = sum_i w_i x_(t - taps + 1 + i)``."""
    taps, t = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[i:i + t] * w[i] for i in range(taps))


def delta_rule(q, k, v, g, beta, precision):
    """The recurrence over one column: ``q``, ``k`` ``[T, H, Dk]``, ``v``
    ``[T, H, Dv]``, ``g`` and ``beta`` ``[T, H]``; ``[T, H, Dv]``."""
    t, h, dk = q.shape
    n = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        u = beta_t[:, None] * (v_t - einsum("hkv,hk->hv", s, k_t, precision))
        s = s + einsum("hk,hv->hkv", k_t, u, precision)
        return s, einsum("hkv,hk->hv", s, q_t, precision)

    def tokens(s, xs):
        return lax.scan(token, s, xs)

    blocks = tuple(x.reshape(t // n, n, *x.shape[1:]) for x in (q, k, v, g, beta))
    _, o = lax.scan(jax.checkpoint(tokens), jnp.zeros((h, dk, v.shape[-1]), jnp.float32), blocks)
    return o.reshape(t, h, -1)


def linear_attention(a, p, model, precision):
    """One column ``[T, hidden]``."""
    t = a.shape[0]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    key_w, value_w = hk * dk, hv * dv
    qkvz = einsum("td,df->tf", a, p["qkvz_kernel"], precision)
    ba = einsum("td,df->tf", a, p["ba_kernel"], precision)
    mixed = jax.nn.silu(causal_conv(qkvz[:, : 2 * key_w + value_w], p["conv_kernel"]))
    z = qkvz[:, 2 * key_w + value_w:].reshape(t, hv, dv)
    q = mixed[:, :key_w].reshape(t, hk, dk)
    k = mixed[:, key_w:2 * key_w].reshape(t, hk, dk)
    v = mixed[:, 2 * key_w:].reshape(t, hv, dv)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) / math.sqrt(dk), hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta, precision)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + model["rms_norm_eps"])
    o = o * p["out_norm_scale"] * jax.nn.silu(z)
    return einsum("tf,fd->td", o.reshape(t, value_w), p["o_kernel"], precision)


def gated_mlp(m, w_gate, w_up, w_down, precision):
    hidden = jax.nn.silu(einsum("td,df->tf", m, w_gate, precision)) * einsum(
        "td,df->tf", m, w_up, precision)
    return einsum("tf,fd->td", hidden, w_down, precision)


def routing(m, w_router, model):
    """``[T, published experts]`` float32: each token's weight on each expert,
    nought where not chosen. The product is in float32 at ``highest`` whatever
    the precision of the rest (as the published code keeps it)."""
    scores = jax.nn.softmax(jnp.dot(m, w_router, precision=lax.Precision.HIGHEST), axis=-1)
    picked, chosen = lax.top_k(scores, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def expert_ffn(m, p, model, precision):
    weights = routing(m, p["router_kernel"], model)
    shared = gated_mlp(m, p["shared_gate_kernel"], p["shared_up_kernel"],
                       p["shared_down_kernel"], precision)
    y = jax.nn.sigmoid(einsum("td,do->to", m, p["shared_out_gate_kernel"], precision)) * shared
    first = model.get("first_expert", 0)
    for e in range(model["num_experts"]):  # the held ones; the rest add nothing here
        out = gated_mlp(m, p["experts_gate_kernel"][e], p["experts_up_kernel"][e],
                        p["experts_down_kernel"][e], precision)
        y = y + weights[:, first + e][:, None] * out
    return y


def kept_layers(model):
    return list(model.get("layers") or range(model["num_hidden_layers"]))


def is_full(model, published_index):
    return (published_index + 1) % model["full_attention_interval"] == 0


def block(h, p, model, published_index, precision):
    eps = model["rms_norm_eps"]
    a = zero_centred_norm(h, p["norm1_weight"], eps)
    if is_full(model, published_index):
        h = h + full_attention(a, p["attn"], model, precision)
    else:
        h = h + linear_attention(a, p["linear_attn"], model, precision)
    m = zero_centred_norm(h, p["norm2_weight"], eps)
    return h + expert_ffn(m, p["moe"], model, precision)


def column_logits(p, ids, model, precision, checkpoint=False):
    """One column: int32 ``[T]`` -> float32 logits ``[T, vocab]``."""
    h = p["embedding"][ids]
    for i, published_index in enumerate(kept_layers(model)):
        def run(hh, pp, published_index=published_index):
            return block(hh, pp, model, published_index, precision)

        h = (jax.checkpoint(run) if checkpoint else run)(h, p[f"layer_{i}"])
    h = zero_centred_norm(h, p["norm_weight"], model["rms_norm_eps"])
    return einsum("td,dv->tv", h, p["head_kernel"], precision)


def forward(params, x, model: dict, precision: str = "f32"):
    p = params["params"]
    return lax.map(lambda ids: column_logits(p, ids, model, precision), x)


def loss(params, x, y, weights, model: dict, precision: str = "f32"):
    """The next-token loss alone (no auxiliary term is built), one column
    after the other with a checkpoint per column and per layer."""
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
    hv, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    key_w = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    fe, fs = model["moe_intermediate_size"], model["shared_expert_intermediate_size"]
    held, vocab = model["num_experts"], model["vocab_size"]
    routed_over = model.get("published_num_experts", held)
    p = {"embedding": f32(vocab, d), "norm_weight": f32(d), "head_kernel": f32(d, vocab)}
    for i, published_index in enumerate(kept_layers(model)):
        layer = {"norm1_weight": f32(d), "norm2_weight": f32(d)}
        if is_full(model, published_index):
            layer["attn"] = {"q_kernel": f32(d, 2 * hq), "k_kernel": f32(d, hkv),
                             "v_kernel": f32(d, hkv), "o_kernel": f32(hq, d),
                             "q_norm_weight": f32(hd), "k_norm_weight": f32(hd)}
        else:
            layer["linear_attn"] = {
                "qkvz_kernel": f32(d, 2 * key_w + 2 * hv * dv), "ba_kernel": f32(d, 2 * hv),
                "conv_kernel": f32(model["linear_conv_kernel_dim"], 2 * key_w + hv * dv),
                "A_log": f32(hv), "dt_bias": f32(hv), "out_norm_scale": f32(dv),
                "o_kernel": f32(hv * dv, d)}
        layer["moe"] = {
            "router_kernel": f32(d, routed_over),
            "shared_gate_kernel": f32(d, fs), "shared_up_kernel": f32(d, fs),
            "shared_down_kernel": f32(fs, d), "shared_out_gate_kernel": f32(d, 1),
            "experts_gate_kernel": f32(held, d, fe), "experts_up_kernel": f32(held, d, fe),
            "experts_down_kernel": f32(held, fe, d)}
        p[f"layer_{i}"] = layer
    return {"params": p}


def init_std(path: str, shape):
    """The standard deviation of a leaf's draw (``harness.make_weights`` draws
    a leaf whose name holds ``scale`` around 1, any other around 0): kernels,
    the embedding and the zero-centred norms' weights at 0.02 around 0, the
    gated norm's ``out_norm_scale`` at 0.02 around 1. ``A_log`` at 1 around 0
    (decay rates ``exp(A_log)`` spread over a factor of some fifty, a median
    of one a token) and ``dt_bias`` at a half around 0, so that heads that
    forget within a few tokens sit beside heads that carry a state across
    chunks, and both leaves' gradients are exercised."""
    if "A_log" in path:
        return A_LOG_STD
    if "dt_bias" in path:
        return DT_BIAS_STD
    return INIT_STD
