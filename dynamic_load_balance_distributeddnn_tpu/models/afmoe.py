"""AFMoE decoder (``model_type`` ``afmoe``: Arcee's Trinity family), built
from a published ``config.json`` kept letter for letter beside this file
(``trinity_mini.json``) and a cut given on the command line: which published
layers are kept, which routed experts this chip holds.

Block (dual norm, no bias anywhere, no dropout)::

    a = RMSNorm1(h);  h = h + RMSNorm2(Attn(a))
    m = RMSNorm3(h);  h = h + RMSNorm4(FFN(m))

``Attn``: grouped-query attention with an RMSNorm over each head of q and k,
rotary positions on window layers only (full layers carry none), keys limited
to the last ``sliding_window`` positions on window layers, and an output gate:
``(o * sigmoid(a @ W_g)) @ W_o``. ``FFN``: a gated SiLU MLP on the first
``num_dense_layers`` published layers; on every later one a shared expert
plus the routed experts held here (``ops/moe.py``). Embedding times
``sqrt(hidden)`` (``mup_enabled``); final RMSNorm; untied head.

With ``train=True`` the model returns ``(logits, arrivals)``: ``arrivals`` is
``[expert layers, held + 1]``, each layer's (token, choice) pairs by held
expert and, last, those routed to experts held elsewhere.

Not built: the training-time update of ``expert_bias`` (not in the config; it
is a zero buffer here) and the auxiliary balance loss (``load_balance_coeff``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.obs import scopes
from dynamic_load_balance_distributeddnn_tpu.ops import moe
from dynamic_load_balance_distributeddnn_tpu.ops.attention import (
    blocked_causal_attention,
    rms_norm,
    rotary,
)

INIT_STD = 0.02
F32_LEAVES = ("router",)  # leaves the step's bfloat16 cast leaves alone
SERIAL_WORKERS = False  # ModelSpec.serial_workers: its superstep fits with the workers left free


def published(arch: str) -> dict:
    """The published ``config.json`` keys of ``arch``: the name of a file kept
    beside this one, or the path of a ``.json`` file."""
    path = arch if arch.endswith(".json") else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), arch + ".json")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class AFMoEConfig:
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int          # the published count the router ranges over
    num_experts_per_tok: int
    num_shared_experts: int
    num_dense_layers: int
    sliding_window: int
    rope_theta: float
    rms_norm_eps: float
    route_norm: bool
    route_scale: float
    mup_enabled: bool
    layer_types: Tuple[str, ...]  # of the KEPT layers, in order
    layer_dense: Tuple[bool, ...]  # which kept layers have the dense FFN
    first_expert: int         # this chip holds [first_expert, first_expert + held)
    experts_held: int


def cut_config(pub: dict, vocab_size: int, layers: Sequence[int] = (),
               experts_held: Optional[Tuple[int, int]] = None) -> AFMoEConfig:
    """``pub`` cut to the published layers ``layers`` (all where empty) and
    the routed experts ``experts_held`` (``(first, end)``; all where
    ``None``), over a vocabulary of ``vocab_size``."""
    if pub.get("score_func") != "sigmoid" or pub.get("num_expert_groups", 1) != 1:
        raise ValueError("afmoe: only sigmoid scores in one expert group are built")
    kept = list(layers) or list(range(pub["num_hidden_layers"]))
    first, end = experts_held or (0, pub["num_experts"])
    if not 0 <= first < end <= pub["num_experts"] or any(
            not 0 <= i < pub["num_hidden_layers"] for i in kept):
        raise ValueError(f"afmoe: cut {layers!r} / {experts_held!r} outside the published model")
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "num_shared_experts", "num_dense_layers", "sliding_window", "rms_norm_eps",
            "route_norm", "route_scale", "mup_enabled")
    return AFMoEConfig(
        vocab_size=int(vocab_size), rope_theta=float(pub["rope_theta"]),
        layer_types=tuple(pub["layer_types"][i] for i in kept),
        layer_dense=tuple(i < pub["num_dense_layers"] for i in kept),
        first_expert=first, experts_held=end - first, **{k: pub[k] for k in same},
    )


def expert_layers(cfg: AFMoEConfig) -> int:
    return cfg.layer_dense.count(False)


def _kernel(module: nn.Module, name: str, shape) -> jnp.ndarray:
    return module.param(name, nn.initializers.normal(INIT_STD), shape)


def _scale(module: nn.Module, name: str, width: int) -> jnp.ndarray:
    return module.param(name, nn.initializers.ones, (width,))


def gated_mlp(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


class Attention(nn.Module):
    cfg: AFMoEConfig
    window: bool

    @nn.compact
    def __call__(self, a):
        c = self.cfg
        b, t, d = a.shape
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = jnp.dot(a, _kernel(self, "q_kernel", (d, h * hd))).reshape(b, t, h, hd)
        k = jnp.dot(a, _kernel(self, "k_kernel", (d, hkv * hd))).reshape(b, t, hkv, hd)
        v = jnp.dot(a, _kernel(self, "v_kernel", (d, hkv * hd))).reshape(b, t, hkv, hd)
        gate = jnp.dot(a, _kernel(self, "gate_kernel", (d, h * hd)))
        q = rms_norm(q, _scale(self, "q_norm_scale", hd), c.rms_norm_eps)
        k = rms_norm(k, _scale(self, "k_norm_scale", hd), c.rms_norm_eps)
        if self.window:
            q, k = rotary(q, c.rope_theta), rotary(k, c.rope_theta)
        o = blocked_causal_attention(q, k, v, c.sliding_window if self.window else None)
        o = o.reshape(b, t, h * hd) * jax.nn.sigmoid(gate)
        return jnp.dot(o, _kernel(self, "o_kernel", (h * hd, d)))


class DenseFFN(nn.Module):
    cfg: AFMoEConfig

    @nn.compact
    def __call__(self, m):
        d, f = self.cfg.hidden_size, self.cfg.intermediate_size
        return gated_mlp(m, _kernel(self, "gate_kernel", (d, f)), _kernel(self, "up_kernel", (d, f)),
                         _kernel(self, "down_kernel", (f, d)))


class ExpertFFN(nn.Module):
    """The shared expert and this chip's share of the routed ones."""

    cfg: AFMoEConfig

    @nn.compact
    def __call__(self, m):
        c = self.cfg
        b, t, d = m.shape
        f, held = c.moe_intermediate_size, c.experts_held
        flat = m.reshape(b * t, d)
        with jax.named_scope(scopes.ROUTER):
            # expert_bias: a zero buffer (its training-time update is not in
            # the published config and is not built)
            chosen, weights = moe.route(
                flat, _kernel(self, "router_kernel", (d, c.num_experts)),
                jnp.zeros((c.num_experts,), jnp.float32), c.num_experts_per_tok,
                c.route_norm, c.route_scale)
        with jax.named_scope(scopes.SHARED_EXPERT):
            fs = f * c.num_shared_experts
            shared = gated_mlp(flat, _kernel(self, "shared_gate_kernel", (d, fs)),
                               _kernel(self, "shared_up_kernel", (d, fs)),
                               _kernel(self, "shared_down_kernel", (fs, d)))
        with jax.named_scope(scopes.EXPERTS):
            routed, arrivals = moe.expert_ffn(
                flat, chosen, weights, c.first_expert,
                _kernel(self, "experts_gate_kernel", (held, d, f)),
                _kernel(self, "experts_up_kernel", (held, d, f)),
                _kernel(self, "experts_down_kernel", (held, f, d)), c.num_experts)
        return (shared + routed).reshape(b, t, d), arrivals


class Block(nn.Module):
    cfg: AFMoEConfig
    window: bool
    dense: bool

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        eps, d = c.rms_norm_eps, c.hidden_size
        a = rms_norm(h, _scale(self, "norm1_scale", d), eps)
        with jax.named_scope(scopes.ATTENTION_WINDOW if self.window else scopes.ATTENTION_FULL):
            attn = Attention(c, self.window, name="attn")(a)
        h = h + rms_norm(attn, _scale(self, "norm2_scale", d), eps)
        m = rms_norm(h, _scale(self, "norm3_scale", d), eps)
        if self.dense:
            ffn, arrivals = DenseFFN(c, name="ffn")(m), jnp.zeros((0,), jnp.float32)
        else:
            ffn, arrivals = ExpertFFN(c, name="moe")(m)
        return h + rms_norm(ffn, _scale(self, "norm4_scale", d), eps), arrivals


class AFMoELM(nn.Module):
    cfg: AFMoEConfig
    remat: bool = False  # --remat: recompute each block in the backward pass

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, train: bool = False):
        c = self.cfg
        embedding = self.param("embedding", nn.initializers.normal(INIT_STD),
                               (c.vocab_size, c.hidden_size))
        h = jnp.take(embedding, tokens, axis=0)
        if c.mup_enabled:
            h = h * math.sqrt(c.hidden_size)
        arrivals = []
        # --remat: each block is rematerialised on its own, so the backward
        # pass holds one layer's activations, not the depth's (prevent_cse on:
        # unrolled layers, which XLA would otherwise merge with the forward
        # pass). The step adds no checkpoint of the whole forward on top
        # (ModelSpec.own_remat): that would compute it a third time.
        block = nn.remat(Block) if self.remat else Block
        for i, (kind, dense) in enumerate(zip(c.layer_types, c.layer_dense)):
            h, arrived = block(c, kind == "sliding_attention", dense, name=f"layer_{i}")(h)
            if not dense:
                arrivals.append(arrived)
        h = rms_norm(h, _scale(self, "norm_scale", c.hidden_size), c.rms_norm_eps)
        with jax.named_scope(scopes.LM_HEAD):
            logits = jnp.dot(h, _kernel(self, "head_kernel", (c.hidden_size, c.vocab_size)))
        if not train:
            return logits
        return logits, (jnp.stack(arrivals) if arrivals
                        else jnp.zeros((0, c.experts_held + 1), jnp.float32))
