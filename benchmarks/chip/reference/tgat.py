"""Plain TGAT (Xu et al. 2020, arXiv:2002.07962).

Each layer embeds a query node at its query time by multi-head attention
over its temporal neighbors, whose keys and values are ``[neighbor
embedding || edge features || cos(dt * w + b)]``; the attention output and
the node's own embedding go through a two-layer ReLU MLP. Layer 0 embeds
raw node embeddings, layer 1 the layer-0 embeddings of the hop-1
neighbors (whose own neighborhoods are the hop-2 block). A link is scored
by an MLP over ``[h_u || h_v]``. TGAT keeps no state between batches.
"""

from __future__ import annotations

import jax.numpy as jnp

from .. import weights
from .layers import attend, mlp, rows, time_code

HOPS = 2


def layout(kw: dict, num_nodes: int, d_edge: int) -> dict:
    """Leaf shapes of the weights."""
    d, d_t = kw["d_model"], kw["d_time"]
    tree = weights.base_layout(d, d_t, num_nodes)
    for layer in range(kw["num_layers"]):
        tree[f"attn_{layer}"] = weights.attention(d + d_t, d + d_edge + d_t, d)
        tree[f"merge_{layer}"] = weights.mlp([2 * d, d, d])
    return tree


def init_state(kw: dict, num_nodes: int, dtype):
    return None


def update(params, state, ev, dtype):
    return state


def tgat_layer(params, layer: int, heads: int, h_q, t_q, nbr, edges, dtype):
    """One TGAT layer. ``nbr``: dict of (n, K) ``ids``/``times``/``eids``/
    ``mask`` and (n, K, d) ``h`` (neighbor inputs); ``edges``: (E, d_edge)
    edge features."""
    tp = params["time"]
    zero = jnp.zeros(t_q.shape, dtype)
    q_in = jnp.concatenate([h_q, time_code(tp, zero, dtype)], -1)
    dt = (t_q[:, None] - nbr["times"])
    kv = jnp.concatenate([nbr["h"], rows(edges, nbr["eids"]),
                          time_code(tp, dt, dtype)], -1)
    att = attend(params[f"attn_{layer}"], q_in, kv, nbr["mask"], heads)
    return mlp(params[f"merge_{layer}"], jnp.concatenate([att, h_q], -1))


def embed(params, heads: int, g, state, dtype, block: int = 0):
    """Two-layer TGAT embeddings of the seeds in ``g`` (see
    ``check.batch_inputs``): hop-1 block ``g["n1"]`` (S, K), hop-2 block
    ``g["n2"]`` (S*K, K), edge features ``g["edges"]``. ``block`` > 0
    embeds the hop-1 frontier that many rows at a time."""
    emb, edges = params["nodes"]["emb"], g["edges"]
    n1, n2 = g["n1"], g["n2"]
    s, k = n1["ids"].shape

    def frontier(ids, times, n2_part):
        nb = dict(n2_part, h=rows(emb, n2_part["ids"]))
        return tgat_layer(params, 0, heads, rows(emb, ids), times, nb, edges,
                          dtype)

    f_ids, f_t = n1["ids"].reshape(-1), n1["times"].reshape(-1)
    if block and s * k > block:
        parts = []
        for lo in range(0, s * k, block):
            sl = slice(lo, lo + block)
            parts.append(frontier(f_ids[sl], f_t[sl],
                                  {key: v[sl] for key, v in n2.items()}))
        h_f = jnp.concatenate(parts)
    else:
        h_f = frontier(f_ids, f_t, n2)
    h0 = rows(emb, g["seeds"])
    h1 = tgat_layer(params, 0, heads, h0, g["times"],
                    dict(n1, h=rows(emb, n1["ids"])), edges, dtype)
    return tgat_layer(params, 1, heads, h1, g["times"],
                      dict(n1, h=h_f.reshape(s, k, -1)), edges, dtype)
