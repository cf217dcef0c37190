"""Work of one fused temporal-attention layer call, from its equations.

For each query (seed) ``s`` and each of its *valid* neighbor slots ``j``
(node ``u_j``, event ``e_j``, time ``t_j``):

    phi_j = cos((t_s - t_j) * w + b)                      d_time
    k_j   = K[u_j] + phi_j @ Wt_k + x[e_j] @ We_k         d = H * Dh
    v_j   = V[u_j] + phi_j @ Wt_v + x[e_j] @ We_v
    a_s   = softmax_j(q_s . k_j / sqrt(Dh)) per head
    out_s = sum_j a_sj v_j

``K``/``V`` are node-level rows projected outside the call; their
projection is the model's work, not the kernel's (see ``steps``).

Operations count each multiply and each add once, and each ``cos``,
``exp`` and divide once. Padded slots, padded queries and any recomputation
count nothing. The backward is counted as twice the forward's operations
(each product's gradient is two products of the same size).

Bytes are compulsory traffic: every distinct row the call reads, once, at
its logical width (float32 and int32, 4 bytes), and every output once. A
kernel that gathers a row twice, or pads a row, does more than this and
so shows a lower share; none can do less.
"""

from __future__ import annotations

import dataclasses

import numpy as np

WORD = 4  # bytes of a float32 or int32


@dataclasses.dataclass(frozen=True)
class Dims:
    """Widths of one fused layer call."""

    d: int        # H * Dh, the k/v/q width
    heads: int
    d_time: int
    d_edge: int   # 0 when the layer takes no edge features
    k: int        # neighbor slots per query


@dataclasses.dataclass(frozen=True)
class Call:
    """What one call touches, counted from its ids (see ``touched``)."""

    queries: int      # valid queries
    slots: int        # valid (query, neighbor) slots
    node_rows: int    # distinct K/V table rows read
    edge_rows: int    # distinct edge-feature rows read
    buffer_rows: int  # distinct (K, 3) neighbor-list rows read


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def seconds(self, peak: dict) -> float:
        """The least time a chip with ``peak`` needs for this work: the
        larger of its compute bound and its memory bound."""
        return max(self.ops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def touched(query_ids, nbr_ids, nbr_eids, nbr_mask, rows_are_slots=False):
    """Count one call's work inputs from its ids: ``query_ids`` (Q,) with
    -1 for padded queries, and (Q, K) neighbor ids, edge ids and validity.
    ``rows_are_slots``: each valid slot reads its own table row (the final
    hop of two-layer TGAT, whose rows are computed per seed)."""
    query_ids = np.asarray(query_ids)
    mask = np.asarray(nbr_mask, bool) & (query_ids >= 0)[:, None]
    slots = int(mask.sum())
    eids = np.asarray(nbr_eids)[mask]
    node_rows = slots if rows_are_slots else int(
        np.unique(np.asarray(nbr_ids)[mask]).size)
    valid_q = query_ids[query_ids >= 0]
    return Call(
        queries=int(valid_q.size),
        slots=slots,
        node_rows=node_rows,
        edge_rows=int(np.unique(eids[eids >= 0]).size),
        buffer_rows=int(query_ids.size if rows_are_slots
                        else np.unique(valid_q).size),
    )


def _weights_bytes(dm: Dims) -> int:
    return WORD * (2 * dm.d_time * dm.d + 2 * dm.d_edge * dm.d
                   + 2 * dm.d_time)


def forward(dm: Dims, c: Call) -> Work:
    per_slot = (3 * dm.d_time + 4 * dm.d * (dm.d_time + dm.d_edge)
                + 8 * dm.d + 3 * dm.heads)
    ops = c.slots * per_slot + c.queries * dm.d
    nbytes = WORD * (
        2 * c.queries * dm.d              # q in, out
        + 2 * c.queries                   # query id and time
        + 3 * dm.k * c.buffer_rows        # neighbor lists (id, time, edge)
        + 2 * dm.d * c.node_rows          # K and V rows
        + dm.d_edge * c.edge_rows)        # edge-feature rows
    return Work(float(ops), float(nbytes + _weights_bytes(dm)))


def backward(dm: Dims, c: Call) -> Work:
    """Gradients of q, the K/V rows and the weights, from the forward's
    operands and the output gradient (flash-style: nothing is stored)."""
    fwd = forward(dm, c)
    extra = WORD * (c.queries * dm.d           # dq (the out grad replaces out)
                    + 2 * dm.d * c.node_rows)  # dK, dV rows
    return Work(2 * fwd.ops, fwd.bytes + extra + _weights_bytes(dm))
