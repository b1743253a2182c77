"""Qwen3-Next decoder (``model_type`` ``qwen3_next``), built from a published
``config.json`` kept letter for letter beside this file (``qwen3_next.json``)
and a cut given on the command line, as ``afmoe.py``'s is: which published
layers are kept, which routed experts this chip holds.

Block (no bias anywhere, no dropout; ``Norm`` is the zero-centred RMSNorm,
``x / rms(x) * (1 + w)``)::

    h = x + Mixer(Norm1(x));  out = h + FFN(Norm2(h))

``Mixer`` is full attention on published layer ``i`` where ``(i + 1) %
full_attention_interval == 0``, else linear attention.

*Linear attention* (Gated DeltaNet): ``q, k, v, z`` from one projection and
``b, a`` from another; ``concat(q, k, v)`` through a causal depthwise
convolution of ``linear_conv_kernel_dim`` taps and SiLU; per value head (its
query and key head is ``h // (value heads / key heads)``) ``q`` and ``k``
L2-normalised, ``q`` scaled by ``key_dim^-0.5``, ``beta = sigmoid(b)``, ``g =
-exp(A_log) softplus(a + dt_bias)`` in float32, then the gated delta rule
(``ops/linear_attention.py``) from a zero state; ``RMSNorm(o) * w * SiLU(z)``
per head (a plain weight), and the output projection.

*Full attention*: ``q_proj`` gives each head its query and, beside it, a gate
of the same size; zero-centred RMSNorm over the head on q and k; rotary
positions on the first ``partial_rotary_factor`` of the head; causal softmax
attention (``ops/attention.py``); the result times ``sigmoid(gate)``; output
projection.

``FFN``: a float32 softmax router over all published experts, the
``num_experts_per_tok`` largest renormalised (``norm_topk_prob``), the routed
experts held here (``ops/moe.py``), plus the shared expert under its
one-output sigmoid gate.

With ``train=True`` the model returns ``(logits, arrivals)`` as ``afmoe.py``'s
does. Not built: the multi-token-prediction module (no key of the published
config describes it) and the auxiliary balance loss.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.models.afmoe import _kernel, gated_mlp
from dynamic_load_balance_distributeddnn_tpu.obs import scopes
from dynamic_load_balance_distributeddnn_tpu.ops import linear_attention, moe
from dynamic_load_balance_distributeddnn_tpu.ops.attention import (
    blocked_causal_attention,
    rms_norm,
    rotary,
)

# leaves the step's bfloat16 cast leaves alone
F32_LEAVES = ("router", "A_log", "dt_bias")
SERIAL_WORKERS = True  # ModelSpec.serial_workers


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int          # the published count the router ranges over
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    layer_full: Tuple[bool, ...]  # of the KEPT layers, in order: full attention or linear
    first_expert: int         # this chip holds [first_expert, first_expert + held)
    experts_held: int


def cut_config(pub: dict, vocab_size: int, layers: Sequence[int] = (),
               experts_held: Optional[Tuple[int, int]] = None) -> Qwen3NextConfig:
    """``pub`` cut to the published layers ``layers`` (all where empty) and
    the routed experts ``experts_held`` (``(first, end)``; all where
    ``None``), over a vocabulary of ``vocab_size``."""
    if pub.get("mlp_only_layers") or pub.get("decoder_sparse_step", 1) != 1:
        raise ValueError("qwen3_next: only an expert FFN in every layer is built")
    kept = list(layers) or list(range(pub["num_hidden_layers"]))
    first, end = experts_held or (0, pub["num_experts"])
    if not 0 <= first < end <= pub["num_experts"] or any(
            not 0 <= i < pub["num_hidden_layers"] for i in kept):
        raise ValueError(
            f"qwen3_next: cut {layers!r} / {experts_held!r} outside the published model")
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
            "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")
    return Qwen3NextConfig(
        vocab_size=int(vocab_size), rope_theta=float(pub["rope_theta"]),
        layer_full=tuple((i + 1) % pub["full_attention_interval"] == 0 for i in kept),
        first_expert=first, experts_held=end - first, **{k: pub[k] for k in same},
    )


def expert_layers(cfg: Qwen3NextConfig) -> int:
    return len(cfg.layer_full)


def _norm(module: nn.Module, name: str, x, eps: float):
    """Zero-centred RMSNorm over the last axis: the weight starts at 0."""
    w = module.param(name, nn.initializers.zeros, (x.shape[-1],))
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def unit(x):
    """``x`` over its L2 norm along the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + 1e-6)


def sigmoid_gated(x, gate):
    return x * jax.nn.sigmoid(gate)


def _a_log(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class LinearAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, a):
        c = self.cfg
        b, t, d = a.shape
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        key_w, value_w = hk * dk, hv * dv
        if t % linear_attention.CHUNK:
            raise ValueError(f"qwen3_next: --bptt {t} must divide by {linear_attention.CHUNK}, "
                             "the chunk of the delta rule")
        qkvz = jnp.dot(a, _kernel(self, "qkvz_kernel", (d, 2 * key_w + 2 * value_w)))
        ba = jnp.dot(a, _kernel(self, "ba_kernel", (d, 2 * hv))).astype(jnp.float32)
        mixed = jax.nn.silu(linear_attention.causal_conv(
            qkvz[..., : 2 * key_w + value_w],
            _kernel(self, "conv_kernel", (c.linear_conv_kernel_dim, 2 * key_w + value_w))))
        z = qkvz[..., 2 * key_w + value_w:].reshape(b, t, hv, dv)
        q = mixed[..., :key_w].reshape(b, t, hk, dk)
        k = mixed[..., key_w:2 * key_w].reshape(b, t, hk, dk)
        v = mixed[..., 2 * key_w:].reshape(b, t, hv, dv)

        # at their own heads: the rule gives value head h its key head h // (hv / hk)
        q = (unit(q) * dk ** -0.5).astype(a.dtype)
        k = unit(k).astype(a.dtype)
        beta = jax.nn.sigmoid(ba[..., :hv])
        a_log = self.param("A_log", _a_log, (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        with jax.named_scope(scopes.DELTA_RULE):
            o = linear_attention.gated_delta_rule(q, k, v, g, beta)
        scale = self.param("out_norm_scale", nn.initializers.ones, (dv,))
        o = rms_norm(o, scale, c.rms_norm_eps) * jax.nn.silu(z)
        return jnp.dot(o.reshape(b, t, value_w), _kernel(self, "o_kernel", (value_w, d)))


class FullAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, a):
        c = self.cfg
        b, t, d = a.shape
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q_gate = jnp.dot(a, _kernel(self, "q_kernel", (d, h * 2 * hd))).reshape(b, t, h, 2 * hd)
        q, gate = q_gate[..., :hd], q_gate[..., hd:].reshape(b, t, h * hd)
        k = jnp.dot(a, _kernel(self, "k_kernel", (d, hkv * hd))).reshape(b, t, hkv, hd)
        v = jnp.dot(a, _kernel(self, "v_kernel", (d, hkv * hd))).reshape(b, t, hkv, hd)
        q = _norm(self, "q_norm_weight", q, c.rms_norm_eps)
        k = _norm(self, "k_norm_weight", k, c.rms_norm_eps)
        turned = int(hd * c.partial_rotary_factor)

        def partial_rotary(x):
            return jnp.concatenate([rotary(x[..., :turned], c.rope_theta), x[..., turned:]], -1)

        o = blocked_causal_attention(partial_rotary(q), partial_rotary(k), v)
        o = sigmoid_gated(o.reshape(b, t, h * hd), gate)
        return jnp.dot(o, _kernel(self, "o_kernel", (h * hd, d)))


class ExpertFFN(nn.Module):
    """The gated shared expert and this chip's share of the routed ones."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, m):
        c = self.cfg
        b, t, d = m.shape
        f, fs, held = c.moe_intermediate_size, c.shared_expert_intermediate_size, c.experts_held
        flat = m.reshape(b * t, d)
        with jax.named_scope(scopes.ROUTER):
            chosen, weights = moe.route(
                flat, _kernel(self, "router_kernel", (d, c.num_experts)), None,
                c.num_experts_per_tok, c.norm_topk_prob, 1.0, score_func="softmax")
        with jax.named_scope(scopes.SHARED_EXPERT):
            shared = gated_mlp(flat, _kernel(self, "shared_gate_kernel", (d, fs)),
                               _kernel(self, "shared_up_kernel", (d, fs)),
                               _kernel(self, "shared_down_kernel", (fs, d)))
            shared = sigmoid_gated(
                shared, jnp.dot(flat, _kernel(self, "shared_out_gate_kernel", (d, 1))))
        with jax.named_scope(scopes.EXPERTS):
            routed, arrivals = moe.expert_ffn(
                flat, chosen, weights, c.first_expert,
                _kernel(self, "experts_gate_kernel", (held, d, f)),
                _kernel(self, "experts_up_kernel", (held, d, f)),
                _kernel(self, "experts_down_kernel", (held, f, d)), c.num_experts)
        return (shared + routed).reshape(b, t, d), arrivals


class Block(nn.Module):
    cfg: Qwen3NextConfig
    full: bool

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        a = _norm(self, "norm1_weight", h, c.rms_norm_eps)
        if self.full:
            with jax.named_scope(scopes.ATTENTION_FULL):
                h = h + FullAttention(c, name="attn")(a)
        else:
            with jax.named_scope(scopes.LINEAR_ATTENTION):
                h = h + LinearAttention(c, name="linear_attn")(a)
        ffn, arrivals = ExpertFFN(c, name="moe")(_norm(self, "norm2_weight", h, c.rms_norm_eps))
        return h + ffn, arrivals


class Qwen3NextLM(nn.Module):
    cfg: Qwen3NextConfig
    remat: bool = False  # --remat: recompute each block in the backward pass

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, train: bool = False):
        c = self.cfg
        embedding = _kernel(self, "embedding", (c.vocab_size, c.hidden_size))
        h = jnp.take(embedding, tokens, axis=0)
        # --remat: a checkpoint a block, as afmoe.py places them (own_remat)
        block = nn.remat(Block) if self.remat else Block
        arrivals = []
        for i, full in enumerate(c.layer_full):
            h, arrived = block(c, full, name=f"layer_{i}")(h)
            arrivals.append(arrived)
        h = _norm(self, "norm_weight", h, c.rms_norm_eps)
        with jax.named_scope(scopes.LM_HEAD):
            logits = jnp.dot(h, _kernel(self, "head_kernel", (c.hidden_size, c.vocab_size)))
        return (logits, jnp.stack(arrivals)) if train else logits
