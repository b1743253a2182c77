"""The gated delta rule's operations and bytes, from the layer table alone:
what the recurrence needs, whatever form computes it (the counterpart, for
the rule, of a kernel's operations-and-bytes function).

One forward pass over one window of ``seq_len`` tokens in one linear layer.
Operations: the three products of key x value a token and value head that
``qwen3_next.py``'s table counts (``S^T k``, ``k u^T``, ``S^T q``), 2 FLOP a
multiply-add. Bytes: q and k at their key heads, v, g and beta read once and
o written once; q, k, v and o at the pass's element size, g and beta in
float32 as the program holds them. A chunked form's other products, its
inverse, the states between chunks and anything read twice are its own and
are not counted; neither is recomputation.

A trained window is a forward and a backward pass: three times the forward
operations (``flops.TRAIN_OVER_FORWARD``), and twice the bytes (the backward
pass reads do and writes the five gradients, the forward's bytes mirrored;
that it reads q, k, v, g and beta again is the form's). A validated window is
one forward pass."""

from __future__ import annotations

from benchmark import flops
from benchmark.flops import qwen3_next


def linear_layers(model: dict) -> int:
    """Kept layers whose mixer is the rule."""
    layers = model.get("layers") or range(model["num_hidden_layers"])
    return sum(not qwen3_next.is_full(model, i) for i in layers)


def forward_operations_and_bytes(model: dict, element_bytes: int) -> tuple:
    """``(FLOPs, bytes)`` of one window's forward pass through one linear layer."""
    t, hk, hv = model["seq_len"], model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    operations = 3 * 2 * t * hv * dk * dv
    moved = element_bytes * (2 * t * hk * dk + 2 * t * hv * dv) + 4 * 2 * t * hv
    return operations, moved


def epoch_operations_and_bytes(model: dict, trained: float, validated: float,
                               train_element_bytes: int, eval_element_bytes: int = 4) -> tuple:
    """``(FLOPs, bytes)`` of an epoch that trains ``trained`` windows and
    validates ``validated`` ones, over all linear layers."""
    train_ops, train_bytes = forward_operations_and_bytes(model, train_element_bytes)
    eval_ops, eval_bytes = forward_operations_and_bytes(model, eval_element_bytes)
    layers = linear_layers(model)
    return (layers * (flops.TRAIN_OVER_FORWARD * trained * train_ops + validated * eval_ops),
            layers * (2 * trained * train_bytes + validated * eval_bytes))
