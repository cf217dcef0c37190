"""Work of one TGAT step beyond its first attention call: the hop-2
frontier and the final hop (both fused calls), the two layers' equations
and the decoder."""

from __future__ import annotations

import numpy as np

from . import attention as att
from .steps import decoder, layer

# The batch arrays a step's work is counted from.
KEYS = ("seed_nodes", "batch_mask", "nbr_ids", "nbr_eids", "nbr_mask",
        "nbr2_ids", "nbr2_eids", "nbr2_mask")


def num_params(kw: dict, num_nodes: int, d_edge: int) -> int:
    d, d_t = kw["d_model"], kw["d_time"]
    per = ((d + d_t) * d + d) + 2 * ((d + d_edge + d_t) * d + d) \
        + (d * d + d) + (2 * d * d + d) + (d * d + d)
    return num_nodes * d + 2 * d_t + (2 * d * d + d) + (d + 1) \
        + kw["num_layers"] * per


def forward(kw: dict, d_edge: int, b, arrays: dict):
    """``(further kernel calls, forward operations, state update
    operations)`` of the step whose shared counts are ``b``
    (``steps.step_work``)."""
    d, d_t, heads = kw["d_model"], kw["d_time"], kw["num_heads"]
    n1 = b.n1
    front = np.where(np.asarray(n1[2], bool) & b.valid_seed[:, None],
                     np.asarray(n1[0]), -1).reshape(-1)
    calls = [att.touched(front, arrays["nbr2_ids"], arrays["nbr2_eids"],
                         arrays["nbr2_mask"]),
             att.touched(b.q_ids, *n1, rows_are_slots=True)]
    d_q, d_kv = d + d_t, d + d_edge + d_t
    fwd = (layer(calls[0].queries, calls[0].slots, d_q, d_kv, d, d_t,
                 heads, 2 * d)
           + layer(b.n_q, b.slots1, d_q, d_kv, d, d_t, heads, 2 * d)
           + layer(b.n_q, b.slots1, d_q, d_kv, d, d_t, heads, 2 * d)
           + decoder(b.pairs, d))
    return calls, fwd, 0.0
