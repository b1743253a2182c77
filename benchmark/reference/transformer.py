"""The paper's language model (``Net/Transformer.py``) as the program's
``models/transformer.py`` computes it: token embedding x sqrt(ninp) plus
sinusoidal positions; ``nlayers`` post-norm encoder layers under a causal
mask (multi-head self-attention with biased query, key, value and output
projections, residual, LayerNorm; a ReLU feed-forward of width ``nhid``,
residual, LayerNorm); a linear decoder to the vocabulary. No dropout: a plain
reference cannot follow a program's masks, and a job compared against it runs
with dropout 0.

``forward`` takes int32 ``[rows, seq]`` and returns float32 logits ``[rows,
seq, vocab]``. Parameters arrive as the tree the program's model keeps them
in (flax: ``Embed_0``, ``EncoderLayer_i/{attn/{query,key,value,out},
LayerNorm_0, Dense_0, Dense_1, LayerNorm_1}``, ``Dense_0``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import einsum

LN_EPS = 1e-6  # flax.linen.LayerNorm's default, which the program's model uses


def positions(seq: int, width: int) -> np.ndarray:
    pos = np.arange(seq, dtype=np.float32)[:, None]
    div = np.exp(np.arange(0, width, 2, dtype=np.float32) * (-np.log(10000.0) / width))
    pe = np.zeros((seq, width), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def attention(x, p, precision):
    """Causal self-attention over ``[rows, seq, width]``; the projections'
    kernels are ``[width, heads, head]`` (``out``: ``[heads, head, width]``)."""
    q = einsum("bsd,dhk->bshk", x, p["query"]["kernel"], precision) + p["query"]["bias"]
    k = einsum("bsd,dhk->bshk", x, p["key"]["kernel"], precision) + p["key"]["bias"]
    v = einsum("bsd,dhk->bshk", x, p["value"]["kernel"], precision) + p["value"]["bias"]
    scores = einsum("bqhk,bshk->bhqs", q / math.sqrt(q.shape[-1]), k, precision)
    seq = x.shape[1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(scores, axis=-1)
    mixed = einsum("bhqs,bshk->bqhk", weights, v, precision)
    return einsum("bqhk,hkd->bqd", mixed, p["out"]["kernel"], precision) + p["out"]["bias"]


def dense(x, p, precision):
    return einsum("bsd,df->bsf", x, p["kernel"], precision) + p["bias"]


def forward(params, x, model: dict, precision: str = "f32"):
    p = params["params"]
    width = model["ninp"]
    h = p["Embed_0"]["embedding"][x] * math.sqrt(float(width))
    h = h + jnp.asarray(positions(x.shape[1], width))[None]
    for i in range(model["nlayers"]):
        q = p[f"EncoderLayer_{i}"]
        h = layer_norm(h + attention(h, q["attn"], precision), q["LayerNorm_0"])
        ff = dense(jnp.maximum(dense(h, q["Dense_0"], precision), 0.0), q["Dense_1"], precision)
        h = layer_norm(h + ff, q["LayerNorm_1"])
    return dense(h, p["Dense_0"], precision)


def param_shapes(model: dict):
    """The parameter tree's shapes (float32), for a run that has no program
    to ask."""
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    d, heads, ff, vocab = model["ninp"], model["nhead"], model["nhid"], model["vocab_size"]
    head = d // heads

    def proj():
        return {"kernel": f32(d, heads, head), "bias": f32(heads, head)}

    def norm():
        return {"scale": f32(d), "bias": f32(d)}

    p = {"Embed_0": {"embedding": f32(vocab, d)},
         "Dense_0": {"kernel": f32(d, vocab), "bias": f32(vocab)}}
    for i in range(model["nlayers"]):
        p[f"EncoderLayer_{i}"] = {
            "attn": {"query": proj(), "key": proj(), "value": proj(),
                     "out": {"kernel": f32(heads, head, d), "bias": f32(d)}},
            "LayerNorm_0": norm(), "LayerNorm_1": norm(),
            "Dense_0": {"kernel": f32(d, ff), "bias": f32(ff)},
            "Dense_1": {"kernel": f32(ff, d), "bias": f32(d)},
        }
    return {"params": p}


def init_std(path: str, shape):
    """The draw of this family's leaves (``harness.make_weights``): the
    embedding at the paper's U[-0.1, 0.1] spread; projections and the
    feed-forward at 1/sqrt(fan-in) (in a transformer every kernel is a matrix,
    so the default rule's tenth for 2-D kernels would starve every layer);
    the decoder alone at a tenth of that, so that logits start near nought
    and the loss near ln V. Scales and biases keep the default rule."""
    if "embedding" in path:
        return 0.1 / math.sqrt(3.0)
    if "kernel" not in path:
        return None
    if "['out']" in path:
        fan_in = shape[0] * shape[1]
    else:
        fan_in = shape[0]
    gain = 0.1 if "EncoderLayer" not in path else 1.0
    return gain / math.sqrt(fan_in)
