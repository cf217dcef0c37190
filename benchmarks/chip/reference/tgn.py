"""Plain TGN (Rossi et al. 2020, arXiv:2006.10637), as the program under
test formulates it (departures noted in PERF.md).

One attention layer over ``[memory || embedding]`` node inputs, then, after
the batch is scored, each endpoint's last message ``[m_u || m_v ||
cos(dt w + b) || e]`` updates its memory through a GRU. The memory enters
the loss as state: no gradient flows through it. The state is the memory
(N, d_memory) and each node's last update time (N,).
"""

from __future__ import annotations

import jax.numpy as jnp

from .. import weights
from .layers import attend, gru, mlp, rows, time_code

HOPS = 1


def layout(kw: dict, num_nodes: int, d_edge: int) -> dict:
    """Leaf shapes of the weights."""
    d, d_t, d_m = kw["d_model"], kw["d_time"], kw["d_memory"]
    d_msg = 2 * d_m + d_t + d_edge
    tree = weights.base_layout(d, d_t, num_nodes)
    tree["attn"] = weights.attention(d_m + d + d_t, d_m + d + d_edge + d_t, d)
    tree["merge"] = weights.mlp([d + d_m + d, d, d])
    tree["gru"] = {}
    for gate in "zrh":
        tree["gru"][f"w{gate}"] = weights.dense(d_msg, d_m)
        tree["gru"][f"u{gate}"] = weights.dense(d_m, d_m, bias=False)
    return tree


def init_state(kw: dict, num_nodes: int, dtype):
    return (jnp.zeros((num_nodes, kw["d_memory"]), dtype),
            jnp.zeros((num_nodes,), jnp.int32))


def embed(params, heads: int, g, state, dtype, block: int = 0):
    """One-layer TGN embeddings of the seeds over the memory in
    ``state``."""
    memory = state[0]
    emb, tp = params["nodes"]["emb"], params["time"]
    n1, seeds = g["n1"], g["seeds"]
    m_s, h_s = rows(memory, seeds), rows(emb, seeds)
    zero = jnp.zeros(seeds.shape, dtype)
    q_in = jnp.concatenate([m_s, h_s, time_code(tp, zero, dtype)], -1)
    dt = g["times"][:, None] - n1["times"]
    kv = jnp.concatenate([rows(memory, n1["ids"]), rows(emb, n1["ids"]),
                          rows(g["edges"], n1["eids"]),
                          time_code(tp, dt, dtype)], -1)
    att = attend(params["attn"], q_in, kv, n1["mask"], heads)
    return mlp(params["merge"], jnp.concatenate([att, m_s, h_s], -1))


def update(params, state, ev, dtype):
    """The state after the batch ``ev`` (``src``/``dst``/``time`` (B,),
    ``e`` (B, d_edge), ``mask`` (B,)): each node touched by a valid event
    takes its last message in the order ``[events as source; events as
    target]`` and GRU-updates its memory, and its last time is the
    message's."""
    memory, last = state
    b = ev["src"].shape[0]
    nodes = jnp.concatenate([ev["src"], ev["dst"]])
    other = jnp.concatenate([ev["dst"], ev["src"]])
    t = jnp.concatenate([ev["time"], ev["time"]])
    valid = jnp.concatenate([ev["mask"], ev["mask"]])
    n = memory.shape[0]
    order = jnp.where(valid, jnp.arange(2 * b), -1)
    pick = jnp.full((n,), -1, order.dtype).at[nodes].max(order)
    touched = pick >= 0
    pick = jnp.maximum(pick, 0)
    u, v = nodes[pick], other[pick]
    dt = t[pick] - last[u]
    msg = jnp.concatenate([memory[u], memory[v],
                           time_code(params["time"], dt, dtype),
                           jnp.concatenate([ev["e"], ev["e"]])[pick]], -1)
    new = gru(params["gru"], msg, memory)
    memory = jnp.where(touched[:, None], new, memory)
    last = jnp.where(touched, t[pick], last)
    return memory, last
