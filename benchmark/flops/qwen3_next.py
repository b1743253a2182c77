"""Qwen3-Next decoder forward FLOPs per sample from its layer table. A sample
is one window of ``seq_len`` tokens in one column; ``model`` is the
configuration's group (``benchmark/reference/qwen3_next.py`` says what it
holds).

Counted, 2 FLOP a multiply-add. A linear layer: the q, k, v, z projection, the
b, a projection, the output projection, the convolution's taps, and the delta
rule as **three products of key x value a token and value head** (``S^T k``,
``k u^T``, ``S^T q``), the same number whatever form computes it (a chunked
form does other products and more of them; they are its own). A full layer:
the query-and-gate, key, value and output projections and attention's scores
and mix over the **unmasked pairs only** (the causal half with its diagonal).
Every layer: the shared expert with its one-output gate, the router over all
published experts, the routed experts **at the expected arrivals**, ``seq_len
x experts per token x held / published`` rows (a program's counter shows the
real share). Then the head over the vocabulary slice. Norms, rotary
positions, softmaxes, decays, gates' activations and the embedding's lookup
are left out.

``as_computed_plainly=True`` counts what the plain reference computes for the
same result instead: every key for every query, every held expert for every
token (its delta rule is the recurrence itself: the same three products). The
tests hold that count to the reference's jaxpr and the other to a hand count.
No kernel comes with this family (the delta rule is plain XLA), so there is no
operations-and-bytes function here.
"""

from __future__ import annotations


def is_full(model: dict, published_index: int) -> bool:
    return (published_index + 1) % model["full_attention_interval"] == 0


def layer_flops(model: dict, published_index: int, as_computed_plainly: bool = False) -> dict:
    """One kept layer's forward FLOPs by part."""
    t, d = model["seq_len"], model["hidden_size"]
    if is_full(model, published_index):
        hd = model["head_dim"]
        hq, hkv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
        pairs = t * t if as_computed_plainly else t * (t + 1) // 2
        parts = {"projections": 2 * t * d * (3 * hq + 2 * hkv), "attention": 4 * pairs * hq}
    else:
        hv, dk, dv = (model["linear_num_value_heads"], model["linear_key_head_dim"],
                      model["linear_value_head_dim"])
        key_w, value_w = model["linear_num_key_heads"] * dk, hv * dv
        parts = {"projections": 2 * t * d * (2 * key_w + 3 * value_w + 2 * hv),
                 "convolution": 2 * t * model["linear_conv_kernel_dim"] * (2 * key_w + value_w),
                 "delta_rule": 3 * 2 * t * hv * dk * dv}
    fe, fs = model["moe_intermediate_size"], model["shared_expert_intermediate_size"]
    routed_over = model.get("published_num_experts", model["num_experts"])
    rows = (t * model["num_experts"] if as_computed_plainly
            else t * model["num_experts_per_tok"] * model["num_experts"] / routed_over)
    parts["shared_expert"] = 6 * t * d * fs + 2 * t * d
    parts["router"] = 2 * t * d * routed_over
    parts["experts"] = 6 * rows * d * fe
    return parts


def forward_flops(model: dict, as_computed_plainly: bool = False) -> int:
    layers = model.get("layers") or range(model["num_hidden_layers"])
    total = sum(sum(layer_flops(model, i, as_computed_plainly).values()) for i in layers)
    return int(total + 2 * model["seq_len"] * model["hidden_size"] * model["vocab_size"])
