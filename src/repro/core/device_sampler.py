"""Device-resident recency sampling (the `device_sampling=True` pipeline).

``DeviceRecencySampler`` is the JAX twin of ``RecencySampler``: the per-node
circular buffers (``ids/times/eids`` plus ``cursor``/``count``) live on the
accelerator as a pytree of ``int32`` arrays, and both ``update`` and
``sample`` are jit-compiled pure functions over that pytree. On non-CPU
backends the state argument is donated, so the buffers are updated in place
— no host round-trip and no reallocation per batch.

State layout (chosen from scatter microbenchmarks — XLA scatter cost is per
index row, so the three value channels share one scatter):

  ``buf``: (N+1, K, 3) int32 — channels = (neighbor id, time, edge id)
  ``cc``:  (N+1, 2)    int32 — columns  = (cursor, count)

Row ``N`` is a write sink for dropped/padded events and is never read.
``state_dict`` still speaks the canonical ``ids/times/eids/cursor/count``
contract shared with the host sampler, so checkpoints are interchangeable.

Slot assignment replaces the host-numpy argsort trick with an on-device
segment-cumsum scheme (fixed shapes, one XLA compilation per batch shape):

  1. sort a single fused integer key ``node * m + stream_pos`` — this both
     groups by node and keeps each node's events in stream (= time) order;
  2. per-element sequence number ``seq`` within its node group via a running
     max over group-start positions (cummax = segment cumsum of ones), and
     group multiplicity via a reverse running min over group ends — no
     second scatter;
  3. only the *last K* events of each node survive (sequential semantics
     under wraparound) and every survivor maps to a distinct
     ``(node, (cursor + seq) % K)`` cell, so the packed scatter has no
     meaningful duplicate targets (collisions are confined to the sink row)
     and is bit-deterministic.

Outputs are bit-identical to ``SequentialRecencySampler`` (see
``tests/test_sampler.py`` property tests), including cursor wraparound when
one batch carries more than K events for a node, and duplicate timestamps.

**Multi-device sharding** (``mesh=`` + ``docs/sharding.md``): the buffer is
partitioned row-wise by node id over a 1-D ``jax.sharding.Mesh`` — shard
``s`` owns nodes ``[s*per, (s+1)*per)`` with ``per = ceil(N/shards)`` plus
its *own local sink row*, so the packed global layout is
``(shards*(per+1), K, 3)``. ``update`` and ``sample`` run through
``shard_map``: updates stay shard-local (each shard scatters only the
events of nodes it owns; everything else lands in its local sink), and
``sample`` combines per-shard masked gathers with a single ``psum`` —
exactly one shard owns each seed, so the sum is the owner's value and the
results are bit-identical to the single-device path (property-tested under
``--xla_force_host_platform_device_count=8``). ``state_dict`` always emits
the canonical host layout (sinks and padding stripped), so checkpoints
reshard transparently across mesh sizes in both directions.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sampler import NeighborBlock

_SCATTER_KW = dict(unique_indices=True, mode="promise_in_bounds")


def as_int32(a, name: str):
    """Narrow host arrays to int32, loudly rejecting values that would wrap
    (device sampler state is int32; silent truncation would corrupt parity
    with the int64 host samplers). Device arrays pass through untouched —
    no synchronization on hot paths. Shared by both device samplers."""
    if not isinstance(a, jax.Array):
        a = np.asarray(a)
        if a.dtype.itemsize > 4 and a.size and (
                a.max() >= 2**31 or a.min() < -(2**31)):
            raise ValueError(
                f"{name} exceeds int32 range; rescale (e.g. coarser time "
                f"granularity / epoch-relative timestamps) before "
                f"device sampling"
            )
    return jnp.asarray(a, jnp.int32)


def _event_stream(src, dst, t, eids, valid, *, directed: bool):
    """Flatten a batch into the (nodes, ok, vals) insertion stream.

    Directed: one stream position per event (src gets dst). Undirected:
    interleaved src/dst copies (event i -> stream positions 2i, 2i+1) so
    the flattened stream preserves exact sequential insertion order.
    """
    if directed:
        return src, valid, jnp.stack([dst, t, eids], axis=-1)  # (m, 3)
    nodes = jnp.stack([src, dst], 1).reshape(-1)
    ok = jnp.stack([valid, valid], 1).reshape(-1)
    vals = jnp.stack([
        jnp.stack([dst, src], 1).reshape(-1),
        jnp.stack([t, t], 1).reshape(-1),
        jnp.stack([eids, eids], 1).reshape(-1),
    ], axis=-1)
    return nodes, ok, vals


def _insert_stream(state, nodes, ok, vals, *, k: int):
    """Scatter an insertion stream into the circular buffers. Pure/jit.

    ``state``'s last row is the write sink for dropped events (``ok`` False
    or routed off-shard by the sharded caller); results per surviving row
    match sequential insertion exactly. Shared by the single-device update
    (sink = global row N) and the per-shard ``shard_map`` body (sink = the
    shard's local sink row).
    """
    sink = state["cc"].shape[0] - 1  # last row: write target for drops
    m = nodes.shape[0]
    nodes = jnp.where(ok, nodes, sink)
    idx = jnp.arange(m, dtype=jnp.int32)

    # One fused sort key: groups by node, stream order within the group.
    if (sink + 1) * m < 2**31:
        key = nodes * m + idx
        skey = jax.lax.sort(key)
        sn = skey // m
        pos = skey % m
    else:
        # Huge graphs: the fused int32 key would overflow (and int64 is
        # unavailable without jax_enable_x64), so use a stable two-operand
        # sort keyed on the node id with the stream position carried along.
        sn, pos = jax.lax.sort((nodes, idx), is_stable=True, num_keys=1)

    group_start = jnp.concatenate([jnp.ones(1, bool), sn[1:] != sn[:-1]])
    group_end = jnp.concatenate([sn[1:] != sn[:-1], jnp.ones(1, bool)])
    # Segment cumsum of ones: seq[i] = i - (position of i's group head);
    # multiplicity = (position past my group's tail) - head. Both via scans.
    head = jax.lax.associative_scan(
        jnp.maximum, jnp.where(group_start, idx, -1)
    )
    seq = idx - head
    tail = jax.lax.associative_scan(
        jnp.minimum, jnp.where(group_end, idx + 1, m), reverse=True
    )
    mult = tail - head

    # Sequential semantics under wraparound: only the last K events per node
    # are visible afterwards. Earlier ones go to the sink row, where slot
    # collisions are harmless (never read); surviving targets are unique ->
    # the scatter is bit-deterministic.
    survives = (seq >= mult - k) & (sn != sink)
    tgt = jnp.where(survives, sn, sink)
    cur = state["cc"][sn, 0]
    slots = jnp.where(survives, (cur + seq) % k, idx % k)
    buf = state["buf"].at[tgt, slots].set(vals[pos], **_SCATTER_KW)

    # Cursor/count advance by per-node multiplicity; one write per group
    # (group heads), the rest land in the sink row.
    chead = group_start & (sn != sink)
    ctgt = jnp.where(chead, sn, sink)
    ccv = jnp.stack([
        (cur + mult) % k,
        jnp.minimum(state["cc"][sn, 1] + mult, k),
    ], axis=-1)
    cc = state["cc"].at[ctgt].set(ccv, **_SCATTER_KW)
    return {"buf": buf, "cc": cc}


def _update_impl(state, src, dst, t, eids, valid, *, k: int, directed: bool):
    """Insert a time-ordered batch into the circular buffers. Pure/jit."""
    nodes, ok, vals = _event_stream(src, dst, t, eids, valid,
                                    directed=directed)
    return _insert_stream(state, nodes, ok, vals, k=k)


@partial(jax.jit, static_argnames=("k", "directed"), donate_argnums=(0,))
def _update_donated(state, src, dst, t, eids, valid, *, k, directed):
    return _update_impl(state, src, dst, t, eids, valid, k=k, directed=directed)


@partial(jax.jit, static_argnames=("k", "directed"))
def _update_copying(state, src, dst, t, eids, valid, *, k, directed):
    return _update_impl(state, src, dst, t, eids, valid, k=k, directed=directed)


def _update(state, src, dst, t, eids, valid, *, k: int, directed: bool,
            retain: bool = False):
    """Jit'd buffer insert; donates the state on backends that support
    aliasing (donation is a no-op that warns on CPU). Resolved per call so
    importing this module never initializes the JAX backend.

    ``retain=True`` forces the copying variant even off-CPU so references to
    the *pre-update* buffer stay valid — required when the packed buffer is
    exposed to the model step (the fused-attention path reads the state as
    it was when the batch was sampled, predict-then-reveal)."""
    fn = (_update_copying
          if retain or jax.default_backend() == "cpu" else _update_donated)
    return fn(state, src, dst, t, eids, valid, k=k, directed=directed)


def _gather_rows(state, rows_idx, *, k: int):
    """Per-row circular-buffer gather: (rows (B, K, 3), cc (B, 2))."""
    cc = state["cc"][rows_idx]  # (B, 2) — one gather for cursor and count
    offs = jnp.arange(1, k + 1, dtype=jnp.int32)[None, :]
    raw = cc[:, :1] - offs  # in [-k, k-1]: cheap wrap instead of generic mod
    slots = jnp.where(raw < 0, raw + k, raw)
    return state["buf"][rows_idx[:, None], slots], cc


def _finish_sample(rows, cc, *, k: int):
    """Mask gathered rows by per-seed count -> (ids, times, eids, mask)."""
    mask = jnp.arange(k, dtype=jnp.int32)[None, :] < cc[:, 1:]
    ids = jnp.where(mask, rows[..., 0], -1)
    times = jnp.where(mask, rows[..., 1], 0)
    eids = jnp.where(mask, rows[..., 2], -1)
    return ids, times, eids, mask


@partial(jax.jit, static_argnames=("k",))
def _sample(state, seeds, *, k: int):
    """Gather the K most recent neighbors per seed, most-recent-first."""
    rows, cc = _gather_rows(state, seeds, k=k)
    return _finish_sample(rows, cc, k=k)


class DeviceRecencySampler:
    """JAX device-resident most-recent-K temporal neighbor sampler.

    Drop-in twin of ``RecencySampler``; state lives on ``device`` (default:
    first JAX device) and ``update``/``sample`` run jit-compiled. ``update``
    accepts an optional ``valid`` mask so padded fixed-shape batches compile
    exactly once.

    With ``mesh`` (a 1-D ``jax.sharding.Mesh``; see
    ``repro.distributed.sharding.make_node_mesh``) the buffers are
    partitioned row-wise by node id over ``mesh_axis`` and both paths run
    through ``shard_map`` — shard-local scatters for ``update``, a
    psum-combined masked gather for ``sample`` — with outputs bit-identical
    to the single-device path. See the module docstring and
    ``docs/sharding.md`` for the layout and the per-shard sink-row policy.
    """

    def __init__(self, num_nodes: int, k: int, directed: bool = False,
                 device=None, retain_state: bool = False, mesh=None,
                 mesh_axis: str = "data"):
        if k <= 0:
            raise ValueError("k must be positive")
        self.num_nodes = int(num_nodes)
        self.k = int(k)
        self.directed = directed
        self.retain_state = retain_state
        self._mesh = mesh
        self._mesh_axis = mesh_axis
        if mesh is not None:
            from repro.distributed.sharding import (
                node_rows_per_shard,
                replicated_sharding,
                row_sharding,
            )

            if device is not None:
                raise ValueError(
                    "pass either device= or mesh=, not both — a sharded "
                    "sampler's state is placed by the mesh's row sharding "
                    "(docs/sharding.md)"
                )
            if mesh_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no axis {mesh_axis!r}; axes are "
                    f"{mesh.axis_names}"
                )
            self._shards = int(mesh.shape[mesh_axis])
            self._per = node_rows_per_shard(self.num_nodes, self._shards)
            self._row_sharding = row_sharding(mesh, mesh_axis)
            self._replicated = replicated_sharding(mesh)
            self._make_sharded_fns()
            self._device = None
        else:
            self._device = device or jax.devices()[0]
        self.reset_state()

    # -- sharded-path machinery ------------------------------------------
    def _make_sharded_fns(self) -> None:
        """Build the per-instance jitted ``shard_map`` update/sample.

        Each shard owns node rows ``[s*per, (s+1)*per)`` plus a local sink
        at local row ``per``; the replicated batch is remapped so owned
        events scatter locally and everything else drops into the local
        sink. ``sample`` gathers per shard, zeroes non-owned rows, and
        psum-combines — exactly one shard owns each seed.
        """
        from jax.sharding import PartitionSpec as P

        mesh, axis = self._mesh, self._mesh_axis
        per, k, directed = self._per, self.k, self.directed
        state_specs = {"buf": P(axis), "cc": P(axis)}
        rep = P()

        def update_body(state, src, dst, t, eids, valid):
            lo = jax.lax.axis_index(axis).astype(jnp.int32) * per
            nodes, ok, vals = _event_stream(src, dst, t, eids, valid,
                                            directed=directed)
            owned = ok & (nodes >= lo) & (nodes < lo + per)
            local = jnp.where(owned, nodes - lo, per)
            return _insert_stream(state, local, owned, vals, k=k)

        def sample_body(state, seeds):
            lo = jax.lax.axis_index(axis).astype(jnp.int32) * per
            owned = (seeds >= lo) & (seeds < lo + per)
            rows, cc = _gather_rows(
                state, jnp.where(owned, seeds - lo, per), k=k)
            rows = jnp.where(owned[:, None, None], rows, 0)
            cc = jnp.where(owned[:, None], cc, 0)
            return (jax.lax.psum(rows, axis), jax.lax.psum(cc, axis))

        upd = jax.shard_map(update_body, mesh=mesh,
                            in_specs=(state_specs, rep, rep, rep, rep, rep),
                            out_specs=state_specs, check_vma=False)
        smp = jax.shard_map(sample_body, mesh=mesh,
                            in_specs=(state_specs, rep),
                            out_specs=(rep, rep), check_vma=False)
        self._sharded_update_donated = jax.jit(upd, donate_argnums=(0,))
        self._sharded_update_copying = jax.jit(upd)
        self._sharded_sample = jax.jit(
            lambda state, seeds: _finish_sample(*smp(state, seeds), k=k))

    def _install_canonical(self, buf: Optional[np.ndarray],
                           cc: Optional[np.ndarray]) -> None:
        """Place canonical ``(N, K, 3)``/``(N, 2)`` host state onto the
        target device(s) (``None`` = empty buffers, sharded mode only —
        the single-device reset builds its empty state directly on device
        and never calls this with ``None``): single-device appends the
        global sink row N; sharded mode materializes each shard's block —
        its node rows plus its local sink row — directly on its device via
        ``jax.make_array_from_callback``, so peak host memory beyond the
        given canonical arrays is one shard's block, never the padded
        global layout (the buffer may not fit one host by design)."""
        n, k = self.num_nodes, self.k
        if self._mesh is None:
            sink_buf = np.zeros((1, k, 3), np.int32)
            sink_buf[..., 0] = -1
            sink_buf[..., 2] = -1
            full_buf = np.concatenate([buf, sink_buf])
            full_cc = np.concatenate([cc, np.zeros((1, 2), np.int32)])
            self.state = jax.device_put(
                {"buf": jnp.asarray(full_buf), "cc": jnp.asarray(full_cc)},
                self._device,
            )
            return
        s, per = self._shards, self._per
        rows_local = per + 1

        def _shard_rows(index):
            """Global row slice -> (shard's first global node id, its
            owned-node count)."""
            shard = (index[0].start or 0) // rows_local
            lo = shard * per
            return lo, max(min(lo + per, n) - lo, 0)

        def cb_buf(index):
            lo, owned = _shard_rows(index)
            out = np.zeros((rows_local, k, 3), np.int32)
            out[..., 0] = -1
            out[..., 2] = -1
            if buf is not None:
                out[:owned] = buf[lo:lo + owned]
            return out

        def cb_cc(index):
            lo, owned = _shard_rows(index)
            out = np.zeros((rows_local, 2), np.int32)
            if cc is not None:
                out[:owned] = cc[lo:lo + owned]
            return out

        self.state = {
            "buf": jax.make_array_from_callback(
                (s * rows_local, k, 3), self._row_sharding, cb_buf),
            "cc": jax.make_array_from_callback(
                (s * rows_local, 2), self._row_sharding, cb_cc),
        }

    def reset_state(self) -> None:
        """Reallocate empty buffers on the target device(s): ids/eids -1,
        times 0, cursor/count 0 (the packed ``(N+1, K, 3)`` + ``(N+1, 2)``
        layout described in the module docstring; sharded mode uses the
        ``(shards*(per+1), ...)`` per-shard-sink layout)."""
        n, k = self.num_nodes, self.k
        if self._mesh is None:
            # Build on device directly — no host-RAM copy of the buffer.
            empty = jnp.stack([
                jnp.full((n + 1, k), -1, jnp.int32),   # neighbor ids
                jnp.zeros((n + 1, k), jnp.int32),      # times
                jnp.full((n + 1, k), -1, jnp.int32),   # edge ids
            ], axis=-1)
            self.state = jax.device_put(
                {"buf": empty, "cc": jnp.zeros((n + 1, 2), jnp.int32)},
                self._device,
            )
            return
        # Sharded: per-shard empty blocks, no full-size host allocation.
        self._install_canonical(None, None)

    @property
    def buffer_ids(self):
        """(rows, K) neighbor-id rows — the fused attention kernel's input.
        Single-device rows = N+1 (global sink last); sharded rows =
        shards*(per+1) with a local sink at local row ``per`` of each shard
        block (see ``rows_per_shard`` / ``docs/sharding.md``)."""
        return self.state["buf"][..., 0]

    @property
    def packed_buffer(self):
        """Packed rows (id, time, edge id) — what ``fused_temporal_layer``
        consumes. Construct the sampler with ``retain_state=True`` if you
        hold on to this across ``update`` calls on a donating (non-CPU)
        backend. Single-device: ``(N+1, K, 3)`` with the global sink at row
        N. Sharded: the ``(shards*(per+1), K, 3)`` per-shard-sink layout,
        ``P(mesh_axis)``-sharded — node ids are *not* direct row indices;
        consume it through ``fused_temporal_layer_sharded`` inside a
        shard_map over ``mesh_axis`` (each shard addresses its block with
        seed-lo-offset local ids; see ``docs/sharding.md``)."""
        return self.state["buf"]

    @property
    def rows_per_shard(self) -> Optional[int]:
        """Node rows owned per shard (``ceil(N/shards)``) in sharded mode;
        ``None`` on a single-device sampler. Each shard's local block in
        ``packed_buffer`` is ``rows_per_shard + 1`` rows (sink last)."""
        return self._per if self._mesh is not None else None

    # ------------------------------------------------------------------
    _as_i32 = staticmethod(as_int32)

    def update(self, src, dst, t, eids=None, valid=None) -> None:
        """Insert a time-ordered batch of edges into the circular buffers.

        ``src``/``dst``/``t`` are (B,) host or device int arrays; ``eids``
        defaults to -1 (no edge-feature association); ``valid`` is an
        optional (B,) bool mask so fixed-shape padded batches compile once
        (invalid rows are routed to the sink row N and never read).
        """
        src = self._as_i32(src, "src")
        if src.shape[0] == 0:
            return
        if eids is None:
            eids = jnp.full(src.shape, -1, jnp.int32)
        else:
            eids = self._as_i32(eids, "eids")
        if valid is None:
            valid = jnp.ones(src.shape, bool)
        dst = self._as_i32(dst, "dst")
        t = self._as_i32(t, "t")
        valid = jnp.asarray(valid, bool)
        if self._mesh is not None:
            # Replicate the batch over the mesh (host arrays and arrays
            # committed to a single device alike), then run the shard_map
            # update — scatters stay shard-local.
            src, dst, t, eids, valid = jax.device_put(
                (src, dst, t, eids, valid), self._replicated)
            fn = (self._sharded_update_copying
                  if self.retain_state or jax.default_backend() == "cpu"
                  else self._sharded_update_donated)
            self.state = fn(self.state, src, dst, t, eids, valid)
            return
        self.state = _update(
            self.state, src, dst, t, eids, valid,
            k=self.k, directed=self.directed, retain=self.retain_state,
        )

    def sample(self, seeds, query_t=None) -> NeighborBlock:
        """Gather each seed's (up to) K most recent neighbors on device.

        Returns a fixed-shape ``NeighborBlock`` of (B, K) device arrays,
        most-recent-first, padded with -1 ids / 0 times where a seed has
        fewer than K past neighbors. ``query_t`` (B,) optionally masks
        neighbors newer than each seed's query time (defensive — recency
        state only ever holds past events).
        """
        seeds = jnp.asarray(seeds, jnp.int32)
        if self._mesh is not None:
            seeds = jax.device_put(seeds, self._replicated)
            ids, times, eids, mask = self._sharded_sample(self.state, seeds)
        else:
            ids, times, eids, mask = _sample(self.state, seeds, k=self.k)
        if query_t is not None:
            qt = jnp.asarray(query_t, jnp.int32)[:, None]
            keep = mask & (times <= qt)
            ids = jnp.where(keep, ids, -1)
            times = jnp.where(keep, times, 0)
            eids = jnp.where(keep, eids, -1)
            mask = keep
        return NeighborBlock(ids, times, eids, mask)

    # -- checkpoint contract (shared with RecencySampler) ----------------
    def state_dict(self) -> dict:
        """Canonical host-numpy state ``{ids, times, eids, cursor, count}``
        (int64, sink row(s) and shard padding stripped) — loads into either
        recency sampler, at any mesh size (resharding happens on load)."""
        n, k = self.num_nodes, self.k
        host = jax.device_get(self.state)
        if self._mesh is None:
            buf, cc = host["buf"][:-1], host["cc"][:-1]
        else:
            # Strip each shard's local sink row, re-concatenate the node
            # rows in id order, and drop the last shard's padding rows.
            s, per = self._shards, self._per
            buf = host["buf"].reshape(s, per + 1, k, 3)[:, :per]
            buf = buf.reshape(s * per, k, 3)[:n]
            cc = host["cc"].reshape(s, per + 1, 2)[:, :per]
            cc = cc.reshape(s * per, 2)[:n]
        return {
            "ids": buf[..., 0].astype(np.int64),
            "times": buf[..., 1].astype(np.int64),
            "eids": buf[..., 2].astype(np.int64),
            "cursor": cc[:, 0].astype(np.int64),
            "count": cc[:, 1].astype(np.int64),
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore buffers saved by either recency sampler at any mesh
        size (the canonical host layout is re-packed for this sampler's
        sink/shard layout and placed on the target device(s))."""
        buf = np.stack([
            np.asarray(state["ids"]),
            np.asarray(state["times"]),
            np.asarray(state["eids"]),
        ], axis=-1).astype(np.int32)
        cc = np.stack([np.asarray(state["cursor"]),
                       np.asarray(state["count"])], axis=-1).astype(np.int32)
        self._install_canonical(buf, cc)
