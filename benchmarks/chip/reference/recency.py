"""Plain recency neighborhoods, straight from the event stream.

The K most recent events of a node, among the events revealed so far, are
its temporal neighborhood: TGAT's and TGN's "most recent neighbors"
sampler. Each event ``(u, v, t)`` is revealed to both endpoints, ``u``
first, and events are revealed in stream order, a batch at a time, after
the batch is scored (predict, then reveal).
"""

from __future__ import annotations

import numpy as np


class Recency:
    """Answers "the K latest events of node u among the first n events"."""

    def __init__(self, src, dst, t, k: int):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        t = np.asarray(t, np.int64)
        e = len(src)
        eid = np.arange(e, dtype=np.int64)
        node = np.concatenate([src, dst])
        other = np.concatenate([dst, src])
        # Reveal order: event by event, the source's copy before the target's.
        order_key = np.concatenate([2 * eid, 2 * eid + 1])
        idx = np.lexsort((order_key, node))
        self._span = 2 * e + 2
        self._key = node[idx] * self._span + order_key[idx]
        self._node = node[idx]
        self._other = other[idx]
        self._eid = np.concatenate([eid, eid])[idx]
        self._t = t[self._eid]
        self.k = k

    def sample(self, nodes, revealed: int):
        """Neighborhoods of ``nodes`` (ids < 0 have none) once the first
        ``revealed`` events are known. Returns ``(ids, times, eids, mask)``,
        each ``(len(nodes), K)``; empty slots hold -1 / 0 / -1 / False."""
        nodes = np.asarray(nodes, np.int64)
        safe = np.maximum(nodes, 0)
        first = np.searchsorted(self._key, safe * self._span)
        end = np.searchsorted(self._key, safe * self._span + 2 * revealed)
        end = np.where(nodes >= 0, end, first)
        pos = end[:, None] - self.k + np.arange(self.k)[None, :]
        mask = pos >= first[:, None]
        pos = np.where(mask, pos, 0)
        ids = np.where(mask, self._other[pos], -1)
        times = np.where(mask, self._t[pos], 0)
        eids = np.where(mask, self._eid[pos], -1)
        return ids, times, eids, mask
