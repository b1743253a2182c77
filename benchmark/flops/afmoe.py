"""AFMoE decoder forward FLOPs per sample from its layer table. A sample is
one window of ``seq_len`` tokens in one column; ``model`` is the
configuration's group (``benchmark/reference/afmoe.py`` says what it holds).

Counted, 2 FLOP a multiply-add: the q, k, v, gate and output projections;
attention's scores and mix over the **unmasked pairs only** (a window layer:
sum over t of min(t + 1, window); a full layer: the causal half with its
diagonal); the dense or the shared FFN; the router over all published
experts; the routed experts **at the expected arrivals**, ``seq_len x
experts per token x held / published`` rows (a program's counter shows the
real share); the head over the vocabulary slice. Norms, rotary positions,
the softmax, gates and the embedding's lookup are left out.

``as_computed_plainly=True`` counts what the plain reference computes for the
same result instead: every key for every query, every held expert for every
token. The tests hold that count to the reference's jaxpr and the other to a
hand count. No kernel comes with this family (the program's default path is
XLA throughout), so there is no operations-and-bytes function here.
"""

from __future__ import annotations


def attention_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs the mask leaves."""
    if window is None:
        return seq_len * (seq_len + 1) // 2
    return sum(min(t + 1, window) for t in range(seq_len))


def layer_flops(model: dict, published_index: int, as_computed_plainly: bool = False) -> dict:
    """One kept layer's forward FLOPs by part."""
    t, d, hd = model["seq_len"], model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    window = (model["sliding_window"]
              if model["layer_types"][published_index] == "sliding_attention" else None)
    pairs = t * t if as_computed_plainly else attention_pairs(t, window)
    parts = {"projections": 2 * t * d * (3 * hq + 2 * hkv),
             "attention": 4 * pairs * hq}
    if published_index < model["num_dense_layers"]:
        parts["dense_ffn"] = 6 * t * d * model["intermediate_size"]
        return parts
    fe = model["moe_intermediate_size"]
    routed_over = model.get("published_num_experts", model["num_experts"])
    rows = (t * model["num_experts"] if as_computed_plainly
            else t * model["num_experts_per_tok"] * model["num_experts"] / routed_over)
    parts["shared_expert"] = 6 * t * d * fe * model["num_shared_experts"]
    parts["router"] = 2 * t * d * routed_over
    parts["experts"] = 6 * rows * d * fe
    return parts


def forward_flops(model: dict, as_computed_plainly: bool = False) -> int:
    layers = model.get("layers") or range(model["num_hidden_layers"])
    total = sum(sum(layer_flops(model, i, as_computed_plainly).values()) for i in layers)
    return int(total + 2 * model["seq_len"] * model["hidden_size"] * model["vocab_size"])
