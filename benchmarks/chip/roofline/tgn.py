"""Work of one TGN step beyond its one attention call: the layer's
equations, the decoder, and the memory update of every node the batch
touches (forward only: no gradient flows through it)."""

from __future__ import annotations

import numpy as np

from .steps import decoder, dense, layer

# The batch arrays a step's work is counted from.
KEYS = ("seed_nodes", "batch_mask", "src", "dst", "nbr_ids", "nbr_eids",
        "nbr_mask")


def num_params(kw: dict, num_nodes: int, d_edge: int) -> int:
    d, d_t, d_m = kw["d_model"], kw["d_time"], kw["d_memory"]
    d_msg = 2 * d_m + d_t + d_edge
    attn = ((d_m + d + d_t) * d + d) + 2 * ((d_m + d + d_edge + d_t) * d + d) \
        + (d * d + d)
    merge = ((2 * d + d_m) * d + d) + (d * d + d)
    gru = 3 * ((d_msg * d_m + d_m) + d_m * d_m)
    return num_nodes * d + 2 * d_t + (2 * d * d + d) + (d + 1) \
        + attn + merge + gru


def forward(kw: dict, d_edge: int, b, arrays: dict):
    """``(further kernel calls, forward operations, state update
    operations)`` of the step whose shared counts are ``b``
    (``steps.step_work``)."""
    d, d_t, heads, d_m = (kw["d_model"], kw["d_time"], kw["num_heads"],
                          kw["d_memory"])
    fwd = (layer(b.n_q, b.slots1, d_m + d + d_t, d_m + d + d_edge + d_t, d,
                 d_t, heads, 2 * d + d_m)
           + decoder(b.pairs, d))
    src = np.asarray(arrays["src"])[b.mask]
    dst = np.asarray(arrays["dst"])[b.mask]
    touched_nodes = float(np.unique(np.concatenate([src, dst])).size)
    d_msg = 2 * d_m + d_t + d_edge
    update = touched_nodes * (3 * d_t + 3 * (dense(1, d_msg, d_m)
                                             + 2.0 * d_m * d_m)
                              + 10 * d_m)
    return [], fwd, update
