"""Plain layers that the reference models share, from the papers'
equations: dense and MLP layers, the Bochner time encoding, masked
multi-head attention over materialized neighborhoods, a GRU cell, the
link decoder and the loss.

Everything is dense ``jax.numpy``, computed at ``dtype`` (float32 for the
reference, bfloat16 for its control); callers set the matmul precision.
Rows with no valid neighbor get a zero attention output before the output
projection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MASKED = -1e30


def dense(p, x):
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y


def mlp(p, x):
    n = len(p)
    for i in range(n):
        x = dense(p[f"layer_{i}"], x)
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def time_code(p, dt, dtype):
    """Bochner time encoding of ``dt`` (any shape) -> (..., d_time)."""
    return jnp.cos(dt.astype(dtype)[..., None] * p["w"] + p["b"])


def attend(p, q_in, kv, mask, heads: int):
    """q_in: (n, dq); kv: (n, K, dkv); mask: (n, K) -> (n, d)."""
    n, k = mask.shape
    q = dense(p["q"], q_in)
    d = q.shape[-1]
    q = q.reshape(n, heads, d // heads)
    key = dense(p["k"], kv).reshape(n, k, heads, d // heads)
    val = dense(p["v"], kv).reshape(n, k, heads, d // heads)
    s = jnp.einsum("nhd,nkhd->nhk", q, key) / jnp.sqrt(
        jnp.asarray(d // heads, q.dtype))
    s = jnp.where(mask[:, None, :], s, jnp.asarray(MASKED, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(mask.any(-1)[:, None, None], w, 0)
    out = jnp.einsum("nhk,nkhd->nhd", w, val).reshape(n, d)
    return dense(p["o"], out)


def rows(table, ids):
    """``table[ids]`` with zero rows for ids < 0."""
    return jnp.where((ids >= 0)[..., None], table[jnp.maximum(ids, 0)], 0)


def gru(p, x, h):
    z = jax.nn.sigmoid(dense(p["wz"], x) + dense(p["uz"], h))
    r = jax.nn.sigmoid(dense(p["wr"], x) + dense(p["ur"], h))
    c = jnp.tanh(dense(p["wh"], x) + dense(p["uh"], r * h))
    return (1 - z) * h + z * c


def link_logits(params, h, b: int):
    """Positive (B,) and negative (B, Nn) logits of stacked seeds
    ``[src | dst | negatives]``."""
    p = params["decoder"]["mlp"]
    h_src, h_dst, h_neg = h[:b], h[b:2 * b], h[2 * b:].reshape(b, -1, h.shape[-1])
    pos = mlp(p, jnp.concatenate([h_src, h_dst], -1))[:, 0]
    src = jnp.broadcast_to(h_src[:, None, :], h_neg.shape)
    neg = mlp(p, jnp.concatenate([src, h_neg], -1))[..., 0]
    return pos, neg


def bce(pos, neg, mask):
    """Mean binary cross-entropy over the valid rows' positive and
    negative terms."""
    m = mask.astype(jnp.float32)
    pos, neg = pos.astype(jnp.float32), neg.astype(jnp.float32)
    total = -(jax.nn.log_sigmoid(pos) * m).sum() - (
        jax.nn.log_sigmoid(-neg) * m[:, None]).sum()
    return total / jnp.maximum(m.sum() * (1 + neg.shape[1]), 1.0)
