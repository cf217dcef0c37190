"""Device-resident uniform temporal neighbor sampling.

``DeviceUniformSampler`` is the JAX twin of ``UniformSampler``: the
CSR-by-time adjacency lives on the accelerator, built with JAX segment ops
(one ``segment_sum`` for the per-node degree counts + a stable composite-key
sort), and sampling is a single jitted global ``searchsorted`` over the
fused ``(node, time-rank)`` key — the same vectorization trick the device
recency sampler's update uses (see ``core/device_sampler.py``), ported to
the static-adjacency case:

  * ``rank(t)`` maps raw timestamps through the unique-time table, so the
    composite key ``node * (num_times + 1) + rank(t)`` is immune to raw
    timestamp magnitude and globally sorted (the adjacency is node-major
    with times ascending within each node);
  * per query, the count of neighbors strictly before ``query_t`` is
    ``searchsorted(keys, seed * base + rank(query_t)) - indptr[seed]`` —
    one vectorized search for the whole (B,) seed batch, no per-seed loop;
  * K draws per seed are taken uniformly (with replacement) from that
    prefix with a counter-derived ``jax.random`` key, so epochs are
    reproducible and ``reset_state`` replays them.

``state_dict``/``load_state_dict`` speak the same canonical host-numpy
contract as the host sampler (``adj_nbr/adj_t/adj_e/indptr/counter``), so
checkpoints are interchangeable between the two — mirroring the
``RecencySampler``/``DeviceRecencySampler`` pairing, which makes the two
sampler families drop-in swappable inside ``RECIPE_TGB_LINK``.

**Multi-device sharding** (``mesh=`` + ``docs/sharding.md``): the CSR is
split on node boundaries over the mesh's node axis — by default shard
``s`` owns nodes ``[s*per, (s+1)*per)`` (``partition="rows"``); with
``partition="degree"`` the cuts fall at cumulative-degree quantiles
instead, equalizing per-shard edge counts on skewed graphs (see
``_shard_bounds``). Each shard holds exactly its nodes' adjacency slice,
padded to the max per-shard edge count with int32-max keys so the local
``searchsorted`` stays correct. The sharded build runs host-side
(``_host_csr``, a stable numpy sort bit-identical to the jitted build)
and each shard's slice is materialized directly on its device, so the
full adjacency never exists on any single device. ``sample`` runs through ``shard_map``:
each shard counts/gathers only for the seeds it owns and two ``psum``s
combine the results (valid-prefix lengths first — so the replicated
uniform draws see the same bounds as the single-device path — then the
gathered rows). Draws are bit-identical to the single-device sampler at
any shard count; ``state_dict`` always reassembles the canonical host CSR,
so checkpoints reshard across mesh sizes in both directions.

**Program names.** The build compiles as ``jit_sampler_build`` and every
sample variant as ``jit_sampler_sample``, the recency sampler's names
(``core/device_sampler.py``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_sampler import as_int32
from repro.core.sampler import NeighborBlock, csr_from_state

_I32_MAX = np.int32(2**31 - 1)


def sampler_build(nodes, nbrs, times, eids, *, num_nodes: int):
    """Sort the doubled edge list into node-major/time-ascending CSR order
    and compute per-node extents with segment ops. Pure/jit."""
    m = nodes.shape[0]
    # Unique-time table (padded to fixed size with int32 max so searchsorted
    # stays correct for any in-range query).
    tvals = jnp.unique(times, size=m, fill_value=_I32_MAX)
    tranks = jnp.searchsorted(tvals, times).astype(jnp.int32)
    num_t = jnp.searchsorted(tvals, _I32_MAX).astype(jnp.int32)
    base = num_t + 1
    # Stable sort on the (node, time-rank) composite key: groups by node,
    # time-ascending within the node, original order on exact ties — the
    # same layout numpy's lexsort((times, nodes)) produces on the host.
    key = nodes * base + tranks
    order = jnp.argsort(key, stable=True)
    counts = jax.ops.segment_sum(jnp.ones(m, jnp.int32), nodes,
                                 num_segments=num_nodes)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    return {
        "adj_nbr": nbrs[order],
        "adj_t": times[order],
        "adj_e": eids[order],
        "adj_key": key[order],
        "indptr": indptr,
        "tvals": tvals,
        "base": base,
    }


_build = jax.jit(sampler_build, static_argnames=("num_nodes",))


def sampler_sample(adj, seeds, query_t, rng_key, *, k: int):
    """Uniform K-with-replacement draws from each seed's strict-past prefix.

    One global ``searchsorted`` on the composite key yields every seed's
    valid-prefix length at once; seeds with an empty prefix come back fully
    masked.
    """
    qranks = jnp.searchsorted(adj["tvals"], query_t, side="left")
    qranks = qranks.astype(jnp.int32)
    starts = adj["indptr"][seeds]
    ends = jnp.searchsorted(adj["adj_key"], seeds * adj["base"] + qranks,
                            side="left").astype(jnp.int32)
    n_valid = ends - starts
    has = n_valid > 0
    B = seeds.shape[0]
    draw = jax.random.randint(rng_key, (B, k), 0,
                              jnp.maximum(n_valid, 1)[:, None], jnp.int32)
    idx = jnp.minimum(starts[:, None] + draw, adj["adj_nbr"].shape[0] - 1)
    ids = jnp.where(has[:, None], adj["adj_nbr"][idx], -1)
    times = jnp.where(has[:, None], adj["adj_t"][idx], 0)
    eids = jnp.where(has[:, None], adj["adj_e"][idx], -1)
    mask = jnp.broadcast_to(has[:, None], (B, k))
    return ids, times, eids, mask


_sample = jax.jit(sampler_sample, static_argnames=("k",))


class DeviceUniformSampler:
    """JAX device-resident uniform temporal neighbor sampler.

    Drop-in twin of ``UniformSampler``: ``build`` once per storage slice,
    then ``sample(seeds, query_t)`` draws K past neighbors per seed
    uniformly with replacement, entirely on ``device`` (default: first JAX
    device). Sampling uses a counter-derived PRNG key per call, so runs are
    reproducible and ``reset_state`` rewinds an epoch exactly.
    """

    def __init__(self, num_nodes: int, k: int, seed: int = 0, device=None,
                 checkpoint_adjacency: bool = True, mesh=None,
                 mesh_axis: str = "data", partition: str = "rows"):
        if k <= 0:
            raise ValueError("k must be positive")
        if partition not in ("rows", "degree"):
            raise ValueError(
                f"partition must be 'rows' or 'degree', got {partition!r}")
        self.num_nodes = int(num_nodes)
        self.k = int(k)
        self._seed = int(seed)
        self._counter = 0
        self._adj = None
        self.checkpoint_adjacency = bool(checkpoint_adjacency)
        self._mesh = mesh
        self._mesh_axis = mesh_axis
        self.partition = partition
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
            self._device = None
        else:
            self._device = device or jax.devices()[0]

    # ------------------------------------------------------------------
    _as_i32 = staticmethod(as_int32)

    def build(self, src, dst, t, eids: Optional[np.ndarray] = None) -> None:
        """Build the device CSR-by-time adjacency for an edge storage slice.

        Each undirected event contributes both (src -> dst) and
        (dst -> src) entries. ``eids`` defaults to the event index, matching
        the ``EdgeFeatureLookupHook`` convention. Sharded samplers build on
        the host and place per-shard slices directly (``_host_csr`` +
        ``_shard_adjacency``), so the global adjacency never materializes
        on a single device — it may not fit one HBM by design.
        """
        if eids is None:
            eids = np.arange(len(np.asarray(src)), dtype=np.int64)
        if self._mesh is not None:
            src = self._host_i64(src, "src")
            dst = self._host_i64(dst, "dst")
            t2 = np.concatenate([self._host_i64(t, "t")] * 2)
            es = np.concatenate([self._host_i64(eids, "eids")] * 2)
            self._shard_adjacency(self._host_csr(
                np.concatenate([src, dst]), np.concatenate([dst, src]),
                t2, es))
            return
        nodes = jnp.concatenate([self._as_i32(src, "src"),
                                 self._as_i32(dst, "dst")])
        nbrs = jnp.concatenate([self._as_i32(dst, "dst"),
                                self._as_i32(src, "src")])
        times = jnp.concatenate([self._as_i32(t, "t")] * 2)
        es = jnp.concatenate([self._as_i32(eids, "eids")] * 2)
        adj = _build(nodes, nbrs, times, eids=es, num_nodes=self.num_nodes)
        # One host sync at build time (once per split) to verify the fused
        # int32 key cannot have overflowed: num_nodes * base must fit.
        base = int(adj["base"])
        if self.num_nodes * base >= 2**31:
            raise ValueError(
                f"composite key range num_nodes*({base}) exceeds int32; use "
                f"the host UniformSampler for this graph"
            )
        self._adj = jax.device_put(adj, self._device)

    def build_from_store(self, store, chunk_size: int = 1 << 20,
                         scratch_dir: Optional[str] = None) -> None:
        """Build the CSR from an ``EventStore`` via the streaming two-pass
        build (``repro.storage.streaming_csr``): degree count, then
        chunked fill — O(chunk) host-resident beyond the adjacency itself,
        which ``scratch_dir`` parks in disk-backed memmaps. Sharded
        samplers hand the streamed CSR straight to ``_shard_adjacency``
        (the same ``partition="rows"``/``"degree"`` boundary cut as
        ``build``), so each shard's padded slice goes host-scratch ->
        device with no full-size host copy; single-device samplers place
        the already-sorted arrays directly, skipping the device re-sort.
        Layout matches ``build`` bit-identically whenever no two distinct
        events share a ``(node, timestamp)`` pair (``repro/storage/csr.py``).
        """
        from repro.storage.csr import streaming_csr

        t_hi = store.time_span[1]
        if store.num_edge_events >= 2**30 or t_hi >= 2**31:
            raise ValueError(
                "stream exceeds the device sampler's int32 range "
                "(indptr/timestamps); use the host UniformSampler")
        csr = streaming_csr(store, num_nodes=self.num_nodes,
                            chunk_size=chunk_size, scratch_dir=scratch_dir)
        base = int(csr["base"])
        if self.num_nodes * base >= 2**31:
            raise ValueError(
                f"composite key range num_nodes*({base}) exceeds int32; use "
                f"the host UniformSampler for this graph"
            )
        if self._mesh is not None:
            self._shard_adjacency(csr)
            return
        adj = {
            "adj_nbr": self._as_i32(csr["adj_nbr"], "adj_nbr"),
            "adj_t": self._as_i32(csr["adj_t"], "adj_t"),
            "adj_e": self._as_i32(csr["adj_e"], "adj_e"),
            "adj_key": self._as_i32(csr["adj_key"], "adj_key"),
            "indptr": self._as_i32(csr["indptr"], "indptr"),
            "tvals": self._as_i32(csr["tvals"], "tvals"),
            "base": jnp.int32(base),
        }
        self._adj = jax.device_put(adj, self._device)

    @staticmethod
    def _host_i64(a, name: str) -> np.ndarray:
        """Host int64 view of an input array with the same int32-range
        guard as ``as_int32`` (the sharded arrays are narrowed to int32 at
        placement time, so out-of-range values must fail loudly here)."""
        a = np.asarray(jax.device_get(a)).astype(np.int64)
        if a.size and (a.max() >= 2**31 or a.min() < -(2**31)):
            raise ValueError(
                f"{name} exceeds int32 range; rescale (e.g. coarser time "
                f"granularity / epoch-relative timestamps) before "
                f"device sampling"
            )
        return a

    def _host_csr(self, nodes, nbrs, times, eids) -> dict:
        """Canonical node-major/time-ascending CSR built host-side with
        numpy — bit-identical layout to the jitted ``_build`` (both are
        stable sorts on the same (node, time-rank) composite key; see
        ``tests/test_sampler.py::test_device_uniform_adjacency_matches_host_csr``)
        — used by the sharded path so no device ever holds the full
        adjacency."""
        order = np.lexsort((times, nodes))
        nodes, nbrs = nodes[order], nbrs[order]
        times, eids = times[order], eids[order]
        counts = np.bincount(nodes, minlength=self.num_nodes)
        indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        tvals = np.unique(times)
        base = len(tvals) + 1
        if self.num_nodes * base >= 2**31:
            raise ValueError(
                f"composite key range num_nodes*({base}) exceeds int32; use "
                f"the host UniformSampler for this graph"
            )
        key = nodes * base + np.searchsorted(tvals, times)
        return {"adj_nbr": nbrs, "adj_t": times, "adj_e": eids,
                "adj_key": key, "indptr": indptr, "tvals": tvals,
                "base": base}

    def _shard_bounds(self, indptr: np.ndarray) -> np.ndarray:
        """Per-shard node boundaries ``bounds`` (s+1,): shard ``i`` owns
        nodes ``[bounds[i], bounds[i+1])``.

        ``partition="rows"`` (default) keeps the equal-row-count split of
        ``node_rows_per_shard`` — shard ``i`` owns ``[i*per, (i+1)*per)``.
        ``partition="degree"`` cuts at the cumulative-degree quantiles
        instead (``searchsorted`` on the global indptr), so each shard
        holds roughly ``E/s`` adjacency entries — on skewed graphs this
        shrinks the max per-shard edge padding ``L`` (and with it every
        shard's CSR allocation) relative to the equal-rows split, at the
        cost of variable per-shard node counts (local indptr is padded to
        the max). Both splits draw identically: the prefix-length psum and
        the replicated draws do not depend on where the cuts fall.
        """
        s, n = self._shards, self.num_nodes
        if self.partition == "degree":
            total = int(indptr[n])
            targets = (np.arange(1, s, dtype=np.int64) * total) // s
            cuts = np.searchsorted(indptr[: n + 1], targets)
            bounds = np.concatenate([[0], cuts, [n]]).astype(np.int64)
            return np.maximum.accumulate(bounds)
        per = self._per
        return np.minimum(np.arange(s + 1, dtype=np.int64) * per, n)

    def _shard_adjacency(self, host: dict) -> None:
        """Split the host CSR on node boundaries and place it row-sharded.

        Shard ``i`` owns nodes ``[bounds[i], bounds[i+1])`` (see
        ``_shard_bounds`` for the equal-rows vs degree-balanced cut); its
        adjacency slice (a contiguous, still globally-sorted run of the
        node-major arrays) is padded to the max per-shard edge count ``L``
        — keys with int32 max so a local ``searchsorted`` never lands in
        padding, values with 0 (never read: gathers are masked by
        ownership and prefix length). Local ``indptr`` is rebased per
        shard and padded to the max per-shard node count (clamping at the
        shard's upper bound, so padding entries read as zero-degree).
        Each shard's padded slice is materialized directly on its device
        via ``jax.make_array_from_callback`` — no device (and no extra
        host copy) ever holds the padded global layout.
        """
        s, n = self._shards, self.num_nodes
        indptr = np.asarray(host["indptr"], np.int64)
        bounds = self._shard_bounds(indptr)
        node_lo, node_hi = bounds[:-1], bounds[1:]
        rows = max(int((node_hi - node_lo).max()), 1)
        off = indptr[node_lo]
        counts = indptr[node_hi] - off
        L = max(int(counts.max()), 1)

        def edge_cb(src, fill):
            def cb(index):
                i = (index[0].start or 0) // L
                out = np.full((L,), fill, np.int32)
                out[: counts[i]] = src[off[i]: off[i] + counts[i]]
                return out

            return jax.make_array_from_callback((s * L,),
                                                self._row_sharding, cb)

        def indptr_cb(index):
            i = (index[0].start or 0) // (rows + 1)
            nodes = np.minimum(node_lo[i] + np.arange(rows + 1), node_hi[i])
            return (indptr[nodes] - off[i]).astype(np.int32)

        self._adj = {
            "adj_nbr": edge_cb(np.asarray(host["adj_nbr"]), 0),
            "adj_t": edge_cb(np.asarray(host["adj_t"]), 0),
            "adj_e": edge_cb(np.asarray(host["adj_e"]), 0),
            "adj_key": edge_cb(np.asarray(host["adj_key"]), _I32_MAX),
            "indptr": jax.make_array_from_callback(
                (s * (rows + 1),), self._row_sharding, indptr_cb),
            "bounds": jax.device_put(jnp.asarray(bounds, jnp.int32),
                                     self._replicated),
            "tvals": jax.device_put(jnp.asarray(host["tvals"], jnp.int32),
                                    self._replicated),
            "base": jax.device_put(jnp.asarray(host["base"], jnp.int32),
                                   self._replicated),
        }
        self._host_indptr = indptr
        self._shard_counts = counts
        self._L = L
        self._make_sharded_sample()

    def _make_sharded_sample(self) -> None:
        """Build the per-instance jitted ``shard_map`` sample (see the
        module docstring for the two-psum combine)."""
        from jax.sharding import PartitionSpec as P

        mesh, axis = self._mesh, self._mesh_axis
        k, L = self.k, self._L
        adj_specs = {"adj_nbr": P(axis), "adj_t": P(axis), "adj_e": P(axis),
                     "adj_key": P(axis), "indptr": P(axis), "bounds": P(),
                     "tvals": P(), "base": P()}
        rep = P()

        def sample_body(adj, seeds, query_t, rng_key):
            i = jax.lax.axis_index(axis)
            lo, hi = adj["bounds"][i], adj["bounds"][i + 1]
            owned = (seeds >= lo) & (seeds < hi)
            qranks = jnp.searchsorted(adj["tvals"], query_t,
                                      side="left").astype(jnp.int32)
            starts = adj["indptr"][jnp.where(owned, seeds - lo, 0)]
            ends = jnp.searchsorted(
                adj["adj_key"], seeds * adj["base"] + qranks,
                side="left").astype(jnp.int32)
            # psum 1: every seed's valid-prefix length (owner's count).
            n_valid = jax.lax.psum(jnp.where(owned, ends - starts, 0), axis)
            # Replicated draws: same key/shape/bounds as the single-device
            # path, so the drawn offsets are bit-identical.
            draw = jax.random.randint(rng_key, (seeds.shape[0], k), 0,
                                      jnp.maximum(n_valid, 1)[:, None],
                                      jnp.int32)
            idx = jnp.minimum(starts[:, None] + draw, L - 1)
            rows = jnp.stack([adj["adj_nbr"][idx], adj["adj_t"][idx],
                              adj["adj_e"][idx]], axis=-1)
            # psum 2: the owner's gathered (id, time, eid) rows.
            rows = jax.lax.psum(
                jnp.where(owned[:, None, None], rows, 0), axis)
            return rows, n_valid

        smp = jax.shard_map(sample_body, mesh=mesh,
                            in_specs=(adj_specs, rep, rep, rep),
                            out_specs=(rep, rep), check_vma=False)

        def sampler_sample(adj, seeds, query_t, rng_key):
            rows, n_valid = smp(adj, seeds, query_t, rng_key)
            has = n_valid > 0
            ids = jnp.where(has[:, None], rows[..., 0], -1)
            times = jnp.where(has[:, None], rows[..., 1], 0)
            eids = jnp.where(has[:, None], rows[..., 2], -1)
            mask = jnp.broadcast_to(has[:, None], (seeds.shape[0], k))
            return ids, times, eids, mask

        self._sharded_sample = jax.jit(sampler_sample)

    @property
    def _built(self) -> bool:
        return self._adj is not None

    def reset_state(self) -> None:
        """Rewind the draw counter (start of an epoch); keeps the built
        adjacency — it is a pure function of the storage slice."""
        self._counter = 0

    def sample(self, seeds, query_t) -> NeighborBlock:
        """Draw K uniform past neighbors per seed, strictly before
        ``query_t``. Returns a fixed-shape device ``NeighborBlock``."""
        if not self._built:
            raise RuntimeError("DeviceUniformSampler.build() must be called first")
        seeds = jnp.asarray(seeds, jnp.int32)
        query_t = self._as_i32(query_t, "query_t")
        rng_key = jax.random.fold_in(jax.random.PRNGKey(self._seed),
                                     self._counter)
        self._counter += 1
        if self._mesh is not None:
            seeds, query_t, rng_key = jax.device_put(
                (seeds, query_t, rng_key), self._replicated)
            ids, times, eids, mask = self._sharded_sample(
                self._adj, seeds, query_t, rng_key)
        else:
            ids, times, eids, mask = _sample(self._adj, seeds, query_t,
                                             rng_key, k=self.k)
        return NeighborBlock(ids, times, eids, mask)

    # -- checkpoint contract (shared with UniformSampler) ----------------
    def state_dict(self) -> dict:
        """Canonical host-numpy state: the CSR arrays plus the draw counter.
        Loads into either uniform sampler, at any mesh size (sharded
        samplers reassemble the canonical node-major CSR first; resharding
        happens on load). Self-contained restore at an O(E) checkpoint cost
        — see ``UniformSampler.state_dict``. With
        ``checkpoint_adjacency=False``, counter-only: the restoring side
        rebuilds the CSR from storage via ``build(...)``."""
        if not self._built or not self.checkpoint_adjacency:
            return {"counter": np.int64(self._counter)}
        if self._mesh is None:
            host = jax.device_get(self._adj)
            nbr, t, e = host["adj_nbr"], host["adj_t"], host["adj_e"]
            indptr = host["indptr"]
        else:
            # Strip each shard's padding tail and re-concatenate the
            # node-major runs; the global indptr was kept at shard time.
            host = jax.device_get(
                {k: self._adj[k] for k in ("adj_nbr", "adj_t", "adj_e")})
            s, L, counts = self._shards, self._L, self._shard_counts
            nbr, t, e = (
                np.concatenate(
                    [host[k].reshape(s, L)[i, : counts[i]] for i in range(s)])
                for k in ("adj_nbr", "adj_t", "adj_e"))
            indptr = self._host_indptr
        return {
            "adj_nbr": nbr.astype(np.int64),
            "adj_t": t.astype(np.int64),
            "adj_e": e.astype(np.int64),
            "indptr": indptr.astype(np.int64),
            "counter": np.int64(self._counter),
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore from either sampler's ``state_dict`` at any mesh size;
        the derived composite-key/time-rank arrays are rebuilt on device
        and re-split over this sampler's mesh (if any)."""
        self._counter = int(state["counter"])
        if "adj_nbr" not in state:
            return
        nodes, nbrs, times, eids = csr_from_state(state, self.num_nodes)
        if self._mesh is not None:
            self._shard_adjacency(self._host_csr(
                self._host_i64(nodes, "nodes"),
                self._host_i64(nbrs, "adj_nbr"),
                self._host_i64(times, "adj_t"),
                self._host_i64(eids, "adj_e")))
            return
        adj = _build(
            self._as_i32(nodes, "nodes"),
            self._as_i32(nbrs, "adj_nbr"),
            self._as_i32(times, "adj_t"),
            eids=self._as_i32(eids, "adj_e"),
            num_nodes=self.num_nodes,
        )
        self._adj = jax.device_put(adj, self._device)
