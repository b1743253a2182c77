"""Transformer language model (reference: Net/Transformer.py).

Sinusoidal positional encoding + post-LN encoder stack (the torch
``nn.TransformerEncoderLayer`` convention the reference relies on) with a
causal mask, tied to the reference's hyperparameters at the call site:
emsize=200, nhead=2, nhid=200, nlayers=2, dropout=0.2, bptt=35
(dbs.py:337-343). Emits log-probabilities, matching the reference's
log_softmax output + F.nll_loss criterion (Net/Transformer.py:95,
dbs.py:372).

Layout is batch-major [B, T] (TPU-friendly), vs the reference's [T, B].
"""

from __future__ import annotations

import functools

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp

from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import axis_size


def sinusoidal_positions(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len, dtype=np.float32)[:, None]
    div = np.exp(np.arange(0, d_model, 2, dtype=np.float32) * (-np.log(10000.0) / d_model))
    pe = np.zeros((max_len, d_model), dtype=np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


class RingSelfAttention(nn.Module):
    """Causal multi-head self-attention over a SEQUENCE-SHARDED axis: the
    local [B, T_local] slice attends to the full global sequence via the
    ``ring_self_attention`` ppermute pipeline (parallel/ring.py). Must be
    applied inside a ``shard_map`` whose mesh carries ``axis_name``.

    Parameter tree (query/key/value/out DenseGenerals) is identical to
    ``nn.MultiHeadDotProductAttention``'s, so weights are interchangeable
    with the single-device model."""

    num_heads: int
    qkv_features: int
    axis_name: str

    @nn.compact
    def __call__(self, x):
        from dynamic_load_balance_distributeddnn_tpu.parallel.ring import (
            ring_self_attention,
        )

        h = self.num_heads
        hd = self.qkv_features // h
        dense = functools.partial(nn.DenseGeneral, features=(h, hd), axis=-1)
        q = dense(name="query")(x)  # [B, T_local, H, hd]
        k = dense(name="key")(x)
        v = dense(name="value")(x)
        o = ring_self_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            axis_name=self.axis_name,
            causal=True,
        ).transpose(0, 2, 1, 3)
        return nn.DenseGeneral(
            features=self.qkv_features, axis=(-2, -1), name="out"
        )(o)


class UlyssesSelfAttention(nn.Module):
    """Causal multi-head self-attention over a SEQUENCE-SHARDED axis via
    head all-to-all (parallel/ulysses.py): each device ends up with the FULL
    sequence for a head subset. Must be applied inside a ``shard_map`` whose
    mesh carries ``axis_name``. Same param layout as ``RingSelfAttention`` /
    ``nn.MultiHeadDotProductAttention`` — weights are interchangeable."""

    num_heads: int
    qkv_features: int
    axis_name: str

    @nn.compact
    def __call__(self, x):
        from dynamic_load_balance_distributeddnn_tpu.parallel.ulysses import (
            ulysses_self_attention,
        )

        h = self.num_heads
        hd = self.qkv_features // h
        dense = functools.partial(nn.DenseGeneral, features=(h, hd), axis=-1)
        q = dense(name="query")(x)  # [B, T_local, H, hd]
        k = dense(name="key")(x)
        v = dense(name="value")(x)
        o = ulysses_self_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            axis_name=self.axis_name,
            causal=True,
        ).transpose(0, 2, 1, 3)
        return nn.DenseGeneral(
            features=self.qkv_features, axis=(-2, -1), name="out"
        )(o)


class EncoderLayer(nn.Module):
    """Post-LN transformer encoder layer (torch convention)."""

    d_model: int
    nhead: int
    d_ff: int
    dropout: float
    seq_axis: str = ""  # non-empty: sequence parallelism over this sharded axis
    sp_mode: str = "ring"  # "ring" (ppermute pipeline) | "ulysses" (head a2a)

    @nn.compact
    def __call__(self, x, mask, train: bool):
        # all variants share the scope name "attn" and the same
        # query/key/value/out param layout, so weights are interchangeable
        # across single-device and sequence-parallel modes
        if self.seq_axis and self.sp_mode == "ulysses":
            attn = UlyssesSelfAttention(
                self.nhead, self.d_model, self.seq_axis, name="attn"
            )(x)
        elif self.seq_axis:
            attn = RingSelfAttention(
                self.nhead, self.d_model, self.seq_axis, name="attn"
            )(x)
        else:
            attn = nn.MultiHeadDotProductAttention(
                num_heads=self.nhead,
                qkv_features=self.d_model,
                dropout_rate=self.dropout,
                deterministic=not train,
                name="attn",
            )(x, x, mask=mask)
        attn = nn.Dropout(self.dropout, deterministic=not train)(attn)
        x = nn.LayerNorm()(x + attn)

        ff = nn.Dense(self.d_ff)(x)
        ff = nn.relu(ff)
        ff = nn.Dropout(self.dropout, deterministic=not train)(ff)
        ff = nn.Dense(self.d_model)(ff)
        ff = nn.Dropout(self.dropout, deterministic=not train)(ff)
        return nn.LayerNorm()(x + ff)


class TransformerLM(nn.Module):
    ntoken: int = 2000
    ninp: int = 200
    nhead: int = 2
    nhid: int = 200
    nlayers: int = 2
    dropout: float = 0.2
    max_len: int = 5000
    seq_axis: str = ""  # non-empty: sequence-parallel mode — tokens arrive as
                        # the local shard of a T-sharded global sequence (call
                        # inside shard_map); attention parallelizes over this
                        # axis and positions are offset by the shard index
    sp_mode: str = "ring"  # "ring" (ppermute KV pipeline, parallel/ring.py) |
                           # "ulysses" (head all-to-all, parallel/ulysses.py)

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        # tokens: [B, T] int32 -> log-probs [B, T, ntoken]
        b, t = tokens.shape
        # symmetric U[-0.1, 0.1] like the reference (Net/Transformer.py:77-78);
        # flax's initializers.uniform(s) is U[0, s) and would bias every
        # embedding positive
        def embed_init(key, shape, dtype=jnp.float32):
            return jax.random.uniform(key, shape, dtype, -0.1, 0.1)

        x = nn.Embed(self.ntoken, self.ninp, embedding_init=embed_init)(tokens)
        x = x * jnp.sqrt(float(self.ninp))
        if self.seq_axis:
            # sequence-parallel: this shard holds global positions
            # [idx*t, (idx+1)*t) — offset the positional encoding accordingly
            # seq_axis is a caller-injected flax field (the SP engines pass
            # the live mesh axis at construction) — deliberately dynamic,
            # guarded by the `if self.seq_axis` gate above
            n_shards = axis_size(self.seq_axis)  # graftlint: disable=G014
            pe = jnp.asarray(
                sinusoidal_positions(min(self.max_len, n_shards * t), self.ninp)
            )
            off = jax.lax.axis_index(self.seq_axis) * t  # graftlint: disable=G014
            x = x + jax.lax.dynamic_slice(
                pe, (off, 0), (t, self.ninp)
            )[None, :, :]
        else:
            # trace-time constant; folded by XLA, never a trainable parameter
            pe = jnp.asarray(
                sinusoidal_positions(min(self.max_len, max(t, 1)), self.ninp)
            )
            x = x + pe[None, :t, :]
        x = nn.Dropout(self.dropout, deterministic=not train)(x)

        causal = None if self.seq_axis else nn.make_causal_mask(tokens)
        for _ in range(self.nlayers):
            x = EncoderLayer(
                self.ninp,
                self.nhead,
                self.nhid,
                self.dropout,
                self.seq_axis,
                self.sp_mode,
            )(x, causal, train)
        # Raw logits; the loss layer applies softmax cross-entropy, which on
        # logits equals the reference's NLLLoss-on-log_softmax composition
        # (dbs.py:371-372) and lets the fused Pallas xent kernel take the
        # vocab-sized reduction.
        return nn.Dense(self.ntoken)(x)
