"""Encoder-stack language model forward FLOPs per sample from its layer
table. A sample is one window of ``seq_len`` tokens in one column. Counted:
the query, key, value and output projections, attention's scores and mix over
the full ``seq_len`` x ``seq_len`` square (the causal half is what a kernel
may skip; the plain reference computes the square), the feed-forward and the
decoder. The embedding is a lookup."""

from __future__ import annotations


def forward_flops(model: dict) -> int:
    t, d, ff, vocab = model["seq_len"], model["ninp"], model["nhid"], model["vocab_size"]
    layer = 4 * 2 * t * d * d + 2 * 2 * t * t * d + 2 * 2 * t * d * ff
    return model["nlayers"] * layer + 2 * t * d * vocab
