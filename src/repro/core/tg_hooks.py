"""Concrete hook library (paper Table 2): neighbor sampling, negative edge
construction, TGB-style evaluation negatives, device transfer, padding, and
analytics (density-of-states estimation).

All hooks produce fixed-shape tensors (padded + masked) so the jitted model
steps compile exactly once per shape. Sampling hooks come in two flavors:

  * ``RecencyNeighborHook``       — host numpy circular buffers (the seed
                                    implementation; parity oracle).
  * ``DeviceRecencyNeighborHook`` — the ``device_sampling=True`` pipeline:
                                    buffers live on the accelerator as a JAX
                                    pytree (``DeviceRecencySampler``) and
                                    both the batch insert and the K-recent
                                    gather run jit-compiled on device, so
                                    neighbor tensors are born device-resident
                                    and never cross PCIe.

The uniform samplers pair the same way: ``UniformNeighborHook`` (host CSR)
and ``DeviceUniformNeighborHook`` (device CSR + jitted composite-key
searchsorted). Hook ordering/contracts and the checkpoint story live in
``docs/architecture.md``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.core.batch import Batch
from repro.core.device_sampler import DeviceRecencySampler
from repro.core.hooks import Hook
from repro.core.negatives import NegativeEdgeSampler
from repro.core.sampler import RecencySampler, UniformSampler


def _jnp():
    """Lazy ``jax.numpy`` accessor for array-module dispatch in hooks that
    serve both host (numpy) and device (JAX) sampler twins."""
    import jax.numpy as jnp

    return jnp


from collections import OrderedDict  # noqa: E402

_EDGE_TABLE_CACHE: OrderedDict = OrderedDict()
_EDGE_TABLE_CACHE_MAX = 8


def device_edge_table(feats, sharding=None):
    """Device-resident ``float32`` view of an edge-feature storage array,
    cached by storage identity.

    Epoch resets and mesh re-stagings rebuild hook pipelines over the
    *same* host storage array; re-transferring the full ``(E, d)`` table
    each time is pure waste (ROADMAP "TPU memory niceties"). The cache key
    is ``(id(storage), shape, dtype, sharding)`` and each entry pins the
    source array — its ``id`` cannot be recycled while the entry lives, so
    a hit is guaranteed to be the same storage — with a small FIFO bound
    keeping the pin set tiny. JAX arrays pass through (re-placed only when
    a ``sharding`` is requested).
    """
    import jax
    import jax.numpy as jnp

    if isinstance(feats, jax.Array):
        return feats if sharding is None else jax.device_put(feats, sharding)
    arr = np.asarray(feats)
    key = (id(feats), arr.shape, arr.dtype.str, sharding)
    entry = _EDGE_TABLE_CACHE.get(key)
    if entry is not None and entry[0] is feats:
        _EDGE_TABLE_CACHE.move_to_end(key)
        return entry[1]
    table = jnp.asarray(arr, jnp.float32)
    if sharding is not None:
        table = jax.device_put(table, sharding)
    _EDGE_TABLE_CACHE[key] = (feats, table)
    while len(_EDGE_TABLE_CACHE) > _EDGE_TABLE_CACHE_MAX:
        _EDGE_TABLE_CACHE.popitem(last=False)
    return table


class NegativeEdgeHook(Hook):
    """Produces ``neg``: (B, num_negatives) corrupted destinations."""

    def __init__(self, num_nodes: int, num_negatives: int = 1,
                 strategy: str = "random", seed: int = 0,
                 dst_pool: Optional[np.ndarray] = None):
        super().__init__(requires={"src", "dst", "time"}, produces={"neg"})
        self._sampler = NegativeEdgeSampler(
            num_nodes, strategy=strategy, num_negatives=num_negatives,
            seed=seed, dst_pool=dst_pool,
        )

    def reset_state(self) -> None:
        """Reset the negative sampler's RNG and observed-destination pool."""
        self._sampler.reset_state()

    def __call__(self, batch: Batch) -> Batch:
        src, dst, t = batch["src"], batch["dst"], batch["time"]
        batch["neg"] = self._sampler.sample(src, dst, t)
        if "batch_mask" in batch:
            m = batch["batch_mask"]
            self._sampler.observe(src[m], dst[m])
        else:
            self._sampler.observe(src, dst)
        return batch


class TGBEvalNegativesHook(Hook):
    """One-vs-many evaluation negatives (TGB protocol).

    Deterministic per (seed, batch_counter) so every epoch ranks positives
    against the same negative sets. Produces ``neg``: (B, num_negatives).
    """

    def __init__(self, num_nodes: int, num_negatives: int = 100, seed: int = 0,
                 dst_pool: Optional[np.ndarray] = None):
        super().__init__(requires={"src", "dst", "time"}, produces={"neg"})
        self.num_negatives = num_negatives
        self._seed = seed
        self._counter = 0
        self._pool = (
            np.arange(num_nodes, dtype=np.int64) if dst_pool is None
            else np.asarray(dst_pool, dtype=np.int64)
        )

    def reset_state(self) -> None:
        """Rewind the per-batch counter so eval negatives replay exactly."""
        self._counter = 0

    def __call__(self, batch: Batch) -> Batch:
        rng = np.random.default_rng((self._seed, self._counter))
        self._counter += 1
        B = len(batch["src"])
        batch["neg"] = rng.choice(self._pool, size=(B, self.num_negatives)).astype(np.int64)
        return batch


class RecencyNeighborHook(Hook):
    """Temporal neighbor sampling from a recency circular buffer.

    Seeds are the batch's (src, dst[, neg...]) nodes at the batch query
    times. Produces hop-1 (and optionally hop-2) neighborhoods:

      seed_nodes (S,), seed_times (S,),
      nbr_ids/nbr_times/nbr_eids/nbr_mask (S, K)
      [hop2: nbr2_ids/... (S*K, K)]

    With ``dedup=True`` (the paper's batch-level de-duplication, §5.1), the
    unique (node) set is sampled once and results are gathered back to the
    full seed list — the key optimization for one-vs-many eval where the same
    src appears ``1+num_negatives`` times.

    The buffer is updated with the batch's positive edges *after* sampling
    (predict-then-reveal ordering).
    """

    def __init__(self, num_nodes: int, k: int, num_hops: int = 1,
                 include_negatives: bool = True, dedup: bool = True,
                 update_buffer: bool = True):
        if num_hops not in (1, 2):
            raise ValueError("num_hops must be 1 or 2")
        produces = {"seed_nodes", "seed_times", "nbr_ids", "nbr_times",
                    "nbr_eids", "nbr_mask"}
        if num_hops == 2:
            produces |= {"nbr2_ids", "nbr2_times", "nbr2_eids", "nbr2_mask"}
        requires = {"src", "dst", "time"} | ({"neg"} if include_negatives else set())
        super().__init__(requires=requires, produces=produces)
        self.sampler = RecencySampler(num_nodes, k)
        self.k = k
        self.num_hops = num_hops
        self.include_negatives = include_negatives
        self.dedup = dedup
        self.update_buffer = update_buffer

    def reset_state(self) -> None:
        """Clear the host circular buffers (start of an epoch)."""
        self.sampler.reset_state()

    def state_dict(self) -> dict:
        """Checkpoint the sampler buffers (shared host/device contract)."""
        return self.sampler.state_dict()

    def load_state_dict(self, state: dict) -> None:
        """Restore sampler buffers saved by either recency sampler."""
        self.sampler.load_state_dict(state)

    def _seeds(self, batch: Batch):
        src, dst, t = batch["src"], batch["dst"], batch["time"]
        seeds = [src, dst]
        times = [t, t]
        if self.include_negatives and "neg" in batch:
            neg = batch["neg"]  # (B, Nneg)
            seeds.append(neg.reshape(-1))
            times.append(np.repeat(t, neg.shape[1]))
        return np.concatenate(seeds), np.concatenate(times)

    def __call__(self, batch: Batch) -> Batch:
        seed_nodes, seed_times = self._seeds(batch)

        if self.dedup:
            # Batch-level de-duplication: sample once per unique node. Within
            # a batch all queries share the batch time frontier, so one sample
            # per node is exact (buffer state is fixed during sampling).
            uniq, inverse = np.unique(seed_nodes, return_inverse=True)
            blk = self.sampler.sample(uniq)
            sel = inverse
            nbr_ids, nbr_times = blk.nbr_ids[sel], blk.nbr_times[sel]
            nbr_eids, nbr_mask = blk.nbr_eids[sel], blk.mask[sel]
        else:
            blk = self.sampler.sample(seed_nodes)
            nbr_ids, nbr_times = blk.nbr_ids, blk.nbr_times
            nbr_eids, nbr_mask = blk.nbr_eids, blk.mask

        batch["seed_nodes"], batch["seed_times"] = seed_nodes, seed_times
        batch["nbr_ids"], batch["nbr_times"] = nbr_ids, nbr_times
        batch["nbr_eids"], batch["nbr_mask"] = nbr_eids, nbr_mask

        if self.num_hops == 2:
            flat = nbr_ids.reshape(-1)
            safe = np.where(flat >= 0, flat, 0)
            if self.dedup:
                uniq2, inv2 = np.unique(safe, return_inverse=True)
                blk2 = self.sampler.sample(uniq2)
                ids2, t2 = blk2.nbr_ids[inv2], blk2.nbr_times[inv2]
                e2, m2 = blk2.nbr_eids[inv2], blk2.mask[inv2]
            else:
                blk2 = self.sampler.sample(safe)
                ids2, t2, e2, m2 = blk2.nbr_ids, blk2.nbr_times, blk2.nbr_eids, blk2.mask
            pad = (flat < 0)[:, None]
            batch["nbr2_ids"] = np.where(pad, -1, ids2)
            batch["nbr2_times"] = np.where(pad, 0, t2)
            batch["nbr2_eids"] = np.where(pad, -1, e2)
            batch["nbr2_mask"] = np.where(pad, False, m2)

        if self.update_buffer:
            eids = batch.meta.get("eids")
            src, dst, t = batch["src"], batch["dst"], batch["time"]
            if "batch_mask" in batch:  # exclude padded events from state
                m = batch["batch_mask"]
                src, dst, t = src[m], dst[m], t[m]
                eids = None if eids is None else eids[m[: len(eids)]]
            self.sampler.update(src, dst, t, eids)
        return batch


class DeviceRecencyNeighborHook(Hook):
    """Device-resident temporal neighbor sampling (``device_sampling=True``).

    Same contract as ``RecencyNeighborHook`` (hop-1/hop-2 neighborhoods,
    predict-then-reveal buffer updates), but backed by
    ``DeviceRecencySampler``: state stays on the accelerator as a packed
    ``(N+1, K, 3)`` buffer (channels = neighbor id / time / edge id, row N
    the write sink) and both ``update`` and ``sample`` are jit-compiled. The
    produced neighbor tensors are JAX device arrays — the downstream
    ``DeviceTransferHook`` passes them through untouched.

    With ``expose_buffer=True`` (the default) each batch also carries:

      * ``nbr_buf``         — the packed buffer *as sampled*, i.e. the
        pre-update snapshot (JAX arrays are immutable, so stashing the
        reference before the update is a zero-copy snapshot; the sampler is
        built with ``retain_state=True`` so donation never invalidates it).
        This is what the fused TGAT/TGN attention reads so the per-seed
        neighbor gather can happen inside the kernel.
      * ``edge_feat_table`` — the raw (E, d_edge) edge-feature storage (only
        when ``edge_feats`` is given), indexed in-kernel by the buffer's
        edge-id channel.

    Differences from the host hook, both deliberate:

      * no batch-level de-duplication — on device the K-recent lookup is a
        single gather, so sampling all ``(2 + num_negatives) * B`` seeds
        directly is cheaper than a host ``np.unique`` round-trip and keeps
        shapes fixed (one XLA compilation per activation key);
      * buffer updates consume the full padded batch plus ``batch_mask`` as
        a validity mask instead of slicing, again for fixed shapes.

    With ``mesh`` the sampler state is partitioned row-wise by node id
    over the mesh's node axis and update/sample run through ``shard_map``
    — same outputs, state scales past one device's HBM. ``expose_buffer``
    defaults off there (the sharded packed layout interleaves per-shard
    sink rows); pass ``expose_buffer=True`` to carry the *sharded* buffer
    on each batch for the shard-aware fused attention path
    (``fused_temporal_layer_sharded``); see ``docs/sharding.md``.
    """

    def __init__(self, num_nodes: int, k: int, num_hops: int = 1,
                 include_negatives: bool = True, update_buffer: bool = True,
                 device=None, expose_buffer: Optional[bool] = None,
                 edge_feats=None, mesh=None, mesh_axis: str = "data"):
        if num_hops not in (1, 2):
            raise ValueError("num_hops must be 1 or 2")
        if mesh is not None and expose_buffer is None:
            # Auto under a mesh: keep the buffer private. The sharded
            # packed layout interleaves per-shard sink rows, so only the
            # shard-aware fused path (``fused_temporal_layer_sharded``
            # inside a shard_map over the node axis) can consume it —
            # pipelines that want it must opt in with expose_buffer=True
            # (CTDGLinkPipeline does when the fused path is enabled; see
            # docs/sharding.md).
            expose_buffer = False
        if expose_buffer is None:
            # Auto: expose wherever a consumer can exist. The fused model
            # path engages on TPU (and in CPU parity tests, where the
            # update already copies); on GPU nothing reads ``nbr_buf`` and
            # exposing it would force retain_state copies instead of the
            # donated in-place buffer update — skip it there. The recipe/
            # trainer can pass an explicit value (e.g. False for models
            # without a fused path).
            import jax

            expose_buffer = jax.default_backend() != "gpu"
        produces = {"seed_nodes", "seed_times", "nbr_ids", "nbr_times",
                    "nbr_eids", "nbr_mask"}
        if num_hops == 2:
            produces |= {"nbr2_ids", "nbr2_times", "nbr2_eids", "nbr2_mask"}
        if expose_buffer:
            produces |= {"nbr_buf"}
            if edge_feats is not None:
                produces |= {"edge_feat_table"}
        requires = {"src", "dst", "time"} | ({"neg"} if include_negatives else set())
        # Shared checkpoint key with the host twin: the sampler state_dicts
        # are interchangeable, so HookManager checkpoint keys must match
        # across device_sampling pipeline flavors (display name stays
        # accurate for diagnostics).
        super().__init__(requires=requires, produces=produces,
                         state_key="RecencyNeighborHook")
        self.sampler = DeviceRecencySampler(num_nodes, k, device=device,
                                            retain_state=expose_buffer,
                                            mesh=mesh, mesh_axis=mesh_axis)
        self.k = k
        self.num_hops = num_hops
        self.include_negatives = include_negatives
        self.update_buffer = update_buffer
        self.expose_buffer = expose_buffer
        self._edge_table = None
        if expose_buffer and edge_feats is not None:
            sh = None
            if mesh is not None:
                # Replicate the table over the whole mesh up front so the
                # sharded steps never re-stage it per invocation.
                from repro.distributed.sharding import replicated_sharding

                sh = replicated_sharding(mesh)
            self._edge_table = device_edge_table(edge_feats, sharding=sh)

    def reset_state(self) -> None:
        """Clear the on-device circular buffers (start of an epoch)."""
        self.sampler.reset_state()

    def state_dict(self) -> dict:
        """Checkpoint the sampler buffers (shared host/device contract)."""
        return self.sampler.state_dict()

    def load_state_dict(self, state: dict) -> None:
        """Restore sampler buffers saved by either recency sampler."""
        self.sampler.load_state_dict(state)

    def __call__(self, batch: Batch) -> Batch:
        """Sample hop-1/2 neighborhoods, expose the pre-update buffer, then
        reveal the batch's positive edges to the sampler."""
        import jax.numpy as jnp

        src, dst, t = batch["src"], batch["dst"], batch["time"]
        if self.expose_buffer:
            # Pre-update snapshot: the state the neighborhoods below are
            # sampled from (predict-then-reveal).
            batch["nbr_buf"] = self.sampler.packed_buffer
            if self._edge_table is not None:
                batch["edge_feat_table"] = self._edge_table
        seeds = [np.asarray(src), np.asarray(dst)]
        times = [np.asarray(t), np.asarray(t)]
        if self.include_negatives and "neg" in batch:
            neg = np.asarray(batch["neg"])  # (B, Nneg)
            seeds.append(neg.reshape(-1))
            times.append(np.repeat(np.asarray(t), neg.shape[1]))
        seed_nodes = np.concatenate(seeds).astype(np.int64)
        seed_times = np.concatenate(times).astype(np.int64)

        blk = self.sampler.sample(seed_nodes)
        batch["seed_nodes"], batch["seed_times"] = seed_nodes, seed_times
        batch["nbr_ids"], batch["nbr_times"] = blk.nbr_ids, blk.nbr_times
        batch["nbr_eids"], batch["nbr_mask"] = blk.nbr_eids, blk.mask

        if self.num_hops == 2:
            flat = blk.nbr_ids.reshape(-1)
            safe = jnp.where(flat >= 0, flat, 0)
            blk2 = self.sampler.sample(safe)
            pad = (flat < 0)[:, None]
            batch["nbr2_ids"] = jnp.where(pad, -1, blk2.nbr_ids)
            batch["nbr2_times"] = jnp.where(pad, 0, blk2.nbr_times)
            batch["nbr2_eids"] = jnp.where(pad, -1, blk2.nbr_eids)
            batch["nbr2_mask"] = jnp.where(pad, False, blk2.mask)

        if self.update_buffer:
            eids = batch.meta.get("eids")
            n = len(np.asarray(src))
            if eids is None:
                eids_full = np.full(n, -1, dtype=np.int64)
            else:
                eids_full = np.full(n, -1, dtype=np.int64)
                eids_full[: len(eids)] = eids
            valid = np.asarray(batch["batch_mask"]) if "batch_mask" in batch \
                else np.ones(n, bool)
            self.sampler.update(np.asarray(src), np.asarray(dst),
                                np.asarray(t), eids_full, valid=valid)
        return batch


class UniformNeighborHook(Hook):
    """Uniform temporal neighbor sampling (requires a pre-built adjacency).

    Seeds are the batch's (src, dst[, neg...]) nodes queried at the batch
    event times; each seed draws K uniform neighbors from its strict past
    (``t < query_t``), so a once-per-split ``build`` over the full stream
    leaks nothing. Stateless across batches except for the reproducible
    draw counter (checkpointed via ``state_dict``).

    With ``num_hops=2`` the hop-1 frontier is sampled recursively: each
    sampled neighbor becomes a hop-2 seed queried at its *own* interaction
    time (strict ``t < t_hop1``, the TGAT temporal-causality convention),
    producing ``nbr2_*`` blocks aligned with the flattened hop-1 frontier —
    rows whose hop-1 slot is padding come back fully masked. The ``S*K``
    frontier is deduplicated at the batch level before the adjacency
    binary search (inside ``UniformSampler.sample``: duplicate
    ``(node, time)`` pairs — ubiquitous in one-vs-many eval shapes, where
    negatives share the positives' neighbors — collapse to one searchsorted
    key each), bit-identically to the direct search.
    """

    def __init__(self, num_nodes: int, k: int, include_negatives: bool = False,
                 seed: int = 0, num_hops: int = 1,
                 checkpoint_adjacency: bool = True):
        if num_hops not in (1, 2):
            raise ValueError("num_hops must be 1 or 2")
        requires = {"src", "dst", "time"} | ({"neg"} if include_negatives else set())
        produces = {"seed_nodes", "seed_times", "nbr_ids", "nbr_times",
                    "nbr_eids", "nbr_mask"}
        if num_hops == 2:
            produces |= {"nbr2_ids", "nbr2_times", "nbr2_eids", "nbr2_mask"}
        super().__init__(requires=requires, produces=produces)
        self.sampler = UniformSampler(num_nodes, k, seed=seed,
                                      checkpoint_adjacency=checkpoint_adjacency)
        self.num_hops = num_hops
        self.include_negatives = include_negatives

    def build(self, src, dst, t, eids=None) -> "UniformNeighborHook":
        """Build the sampler's CSR-by-time adjacency; returns self."""
        self.sampler.build(src, dst, t, eids)
        return self

    def build_from_store(self, store, **kwargs) -> "UniformNeighborHook":
        """Build the adjacency from an ``EventStore`` via the streaming
        two-pass build (O(chunk) resident — ``repro.storage.streaming_csr``);
        returns self. Works for both the host and device hook (each
        sampler implements ``build_from_store``)."""
        self.sampler.build_from_store(store, **kwargs)
        return self

    def reset_state(self) -> None:
        """Rewind the sampler's draw counter (epochs replay exactly)."""
        self.sampler.reset_state()

    def state_dict(self) -> dict:
        """Checkpoint the sampler (shared host/device uniform contract)."""
        return self.sampler.state_dict()

    def load_state_dict(self, state: dict) -> None:
        """Restore state saved by either uniform sampler."""
        self.sampler.load_state_dict(state)

    def __call__(self, batch: Batch) -> Batch:
        """Sample hop-1 uniform temporal neighborhoods for the batch."""
        src, dst, t = batch["src"], batch["dst"], batch["time"]
        seeds = [src, dst]
        times = [t, t]
        if self.include_negatives and "neg" in batch:
            neg = batch["neg"]
            seeds.append(neg.reshape(-1))
            times.append(np.repeat(t, neg.shape[1]))
        seed_nodes, seed_times = np.concatenate(seeds), np.concatenate(times)
        blk = self.sampler.sample(seed_nodes, seed_times)
        batch["seed_nodes"], batch["seed_times"] = seed_nodes, seed_times
        batch["nbr_ids"], batch["nbr_times"] = blk.nbr_ids, blk.nbr_times
        batch["nbr_eids"], batch["nbr_mask"] = blk.nbr_eids, blk.mask

        if self.num_hops == 2:
            # Recursive frontier: hop-1 neighbors become hop-2 seeds queried
            # at their own interaction times (strict past, leak-free).
            xp = np if isinstance(blk.nbr_ids, np.ndarray) else _jnp()
            flat_ids = blk.nbr_ids.reshape(-1)
            flat_t = blk.nbr_times.reshape(-1)
            invalid = flat_ids < 0
            safe = xp.where(invalid, 0, flat_ids)
            blk2 = self.sampler.sample(safe, xp.where(invalid, 0, flat_t))
            pad = invalid[:, None]
            batch["nbr2_ids"] = xp.where(pad, -1, blk2.nbr_ids)
            batch["nbr2_times"] = xp.where(pad, 0, blk2.nbr_times)
            batch["nbr2_eids"] = xp.where(pad, -1, blk2.nbr_eids)
            batch["nbr2_mask"] = xp.where(pad, False, blk2.mask)
        return batch


class DeviceUniformNeighborHook(UniformNeighborHook):
    """Device-resident uniform temporal neighbor sampling
    (``device_sampling=True`` + ``sampler="uniform"``).

    Same contract and seed assembly as ``UniformNeighborHook`` (including
    the ``num_hops=2`` recursive frontier) but backed by
    ``DeviceUniformSampler``: the CSR-by-time adjacency lives on the
    accelerator and sampling is one jitted composite-key ``searchsorted``
    over the whole seed batch — the produced neighbor tensors are born
    device-resident, mirroring ``DeviceRecencyNeighborHook``. With
    ``mesh`` the CSR is split on node boundaries over the mesh and
    sampling runs through ``shard_map`` (see ``docs/sharding.md``).
    """

    def __init__(self, num_nodes: int, k: int, include_negatives: bool = False,
                 seed: int = 0, device=None, num_hops: int = 1,
                 checkpoint_adjacency: bool = True, mesh=None,
                 mesh_axis: str = "data", partition: str = "rows"):
        from repro.core.device_uniform import DeviceUniformSampler

        super().__init__(num_nodes, k, include_negatives=include_negatives,
                         seed=seed, num_hops=num_hops)
        self.sampler = DeviceUniformSampler(
            num_nodes, k, seed=seed, device=device,
            checkpoint_adjacency=checkpoint_adjacency, mesh=mesh,
            mesh_axis=mesh_axis, partition=partition)
        # Shared checkpoint key with the host twin (see
        # DeviceRecencyNeighborHook): state_dicts are interchangeable.
        self.state_key = "UniformNeighborHook"


class SnapshotNegativeHook(Hook):
    """Per-snapshot negative destinations for the DTDG link recipe.

    Produces ``neg``: (capacity, num_negatives) int32 corrupted destinations
    for the batch's (predicted) snapshot. Draws are a pure function of
    ``(seed, num_negatives, snapshot row)`` via
    ``core.negatives.snapshot_negatives`` — the same function the
    scan-compiled epoch uses to pre-draw every snapshot at once — so the
    hook path and the scanned path are bit-identical (see ``docs/dtdg.md``).

    The snapshot row comes from ``batch.meta['snapshot_row']`` when present
    (how ``SnapshotLinkTrainer`` drives the hook — resume correctness then
    follows from the trainer's checkpointed snapshot cursor plus the
    row-pure draws). For standalone recipe use without row metadata, an
    internal cursor advances one row per call; ``seek(row)`` positions it
    and ``state_dict`` checkpoints it.
    """

    def __init__(self, num_nodes: int, capacity: int, num_negatives: int = 1,
                 seed: int = 0):
        super().__init__(requires={"src"}, produces={"neg"})
        self.num_nodes = int(num_nodes)
        self.capacity = int(capacity)
        self.num_negatives = int(num_negatives)
        self._seed = int(seed)
        self._cursor = 0

    def seek(self, row: int) -> None:
        """Position the cursor at snapshot ``row`` (split boundaries)."""
        self._cursor = int(row)

    def reset_state(self) -> None:
        """Rewind the snapshot cursor (start of an epoch)."""
        self._cursor = 0

    def state_dict(self) -> dict:
        """Checkpoint the snapshot cursor (draws are cursor-derived)."""
        return {"cursor": np.int64(self._cursor)}

    def load_state_dict(self, state: dict) -> None:
        """Restore the snapshot cursor."""
        self._cursor = int(state["cursor"])

    def __call__(self, batch: Batch) -> Batch:
        """Attach this snapshot's deterministic negative draws."""
        from repro.core.negatives import snapshot_negatives

        row = int(batch.meta.get("snapshot_row", self._cursor))
        batch["neg"] = snapshot_negatives(
            self._seed, self.num_nodes, self.capacity, self.num_negatives,
            [row],
        )[0]
        self._cursor = row + 1
        return batch


class EdgeFeatureLookupHook(Hook):
    """Produces ``<prefix>_feats``: gather stored edge features for sampled
    neighbor edge ids (zeros where padded / featureless). Named
    ``EdgeFeatureLookupHook[<prefix>]``, so the hop-1 and hop-2 lookups
    have spans of their own."""

    def __init__(self, edge_feats: Optional[np.ndarray], feat_dim: int,
                 prefix: str = "nbr"):
        super().__init__(
            requires={f"{prefix}_eids"}, produces={f"{prefix}_feats"},
            name=f"EdgeFeatureLookupHook[{prefix}]",
        )
        self._feats = edge_feats
        self._dim = feat_dim
        self._prefix = prefix

    def __call__(self, batch: Batch) -> Batch:
        eids = batch[f"{self._prefix}_eids"]
        if isinstance(eids, np.ndarray):
            out = np.zeros(eids.shape + (self._dim,), dtype=np.float32)
            if self._feats is not None:
                ok = eids >= 0
                out[ok] = self._feats[eids[ok]]
        else:  # device-resident eids (device-sampling pipeline): jnp gather
            import jax.numpy as jnp

            if self._feats is None:
                out = jnp.zeros(eids.shape + (self._dim,), jnp.float32)
            else:
                if not hasattr(self, "_feats_dev"):
                    self._feats_dev = device_edge_table(self._feats)
                safe = jnp.maximum(eids, 0)
                out = jnp.where((eids >= 0)[..., None],
                                self._feats_dev[safe], 0.0)
        batch[f"{self._prefix}_feats"] = out
        return batch


class PadBatchHook(Hook):
    """Pads event tensors to a fixed batch size and emits ``batch_mask`` so
    every training step has identical shapes (one XLA compilation)."""

    PADDABLE = ("src", "dst", "time", "neg", "edge_feats", "labels")

    def __init__(self, batch_size: int):
        super().__init__(requires={"src"}, produces={"batch_mask"})
        self.batch_size = batch_size

    def __call__(self, batch: Batch) -> Batch:
        n = len(batch["src"])
        pad = self.batch_size - n
        if pad < 0:
            raise ValueError(f"batch of {n} exceeds fixed size {self.batch_size}")
        mask = np.zeros(self.batch_size, dtype=bool)
        mask[:n] = True
        for key in self.PADDABLE:
            if key in batch:
                v = batch[key]
                widths = [(0, pad)] + [(0, 0)] * (v.ndim - 1)
                batch[key] = np.pad(v, widths)
        batch["batch_mask"] = mask
        return batch


def stage_batch(batch: Batch, device=None, pool=None) -> Batch:
    """Ship every host numpy attribute of ``batch`` to ``device`` (int64
    narrowed to int32 for the jitted models); arrays already on device pass
    through. ``device`` may be a concrete device or any
    ``jax.sharding.Sharding`` (the sharded sampling pipeline passes the
    mesh-replicated ``NamedSharding``). Shared by ``DeviceTransferHook``
    and ``PrefetchLoader`` so the transfer/narrowing policy lives in one
    place.

    ``pool`` (a ``core.loader._HostStagingPool``) routes each array through
    a reusable host staging buffer first, and — off CPU only — issues the
    transfer with ``donate=True`` so the runtime may recycle the staged
    source immediately (on CPU, donation zero-copy aliases the source, so a
    reused buffer must never be donated)."""
    import jax

    dev = device or jax.devices()[0]
    donate = pool is not None and jax.default_backend() != "cpu"
    for key in list(batch.keys()):
        v = batch[key]
        if isinstance(v, np.ndarray):
            if pool is not None:
                v = pool.stage(key, v)
            elif v.dtype == np.int64:
                v = v.astype(np.int32)
            batch[key] = jax.device_put(v, dev, donate=donate)
            if pool is not None:
                # Let the slot's next rewrite wait for this transfer.
                pool.note(key, batch[key])
    return batch


class DeviceTransferHook(Hook):
    """Moves all array attributes to a JAX device (paper Table 2: R=∅, P=∅).

    Register last; ordering among contract-free hooks follows registration.
    """

    def __init__(self, device=None):
        super().__init__(requires=set(), produces=set())
        self._device = device

    def __call__(self, batch: Batch) -> Batch:
        return stage_batch(batch, self._device)


class DOSEstimateHook(Hook):
    """Analytics: spectral density-of-states estimate of the batch's
    interaction graph via Hutchinson moment estimation (paper Fig. 3 recipe).

    Produces ``dos``: (num_moments,) Chebyshev moment estimates of the
    normalized adjacency spectrum.
    """

    def __init__(self, num_nodes: int, num_moments: int = 10, num_probes: int = 4,
                 seed: int = 0):
        super().__init__(requires={"src", "dst"}, produces={"dos"})
        self.num_nodes = num_nodes
        self.num_moments = num_moments
        self.num_probes = num_probes
        self._rng = np.random.default_rng(seed)

    def reset_state(self) -> None:
        """Stateless across epochs (probe RNG deliberately persists)."""
        pass

    def __call__(self, batch: Batch) -> Batch:
        src, dst = batch["src"], batch["dst"]
        nodes = np.unique(np.concatenate([src, dst]))
        n = len(nodes)
        if n == 0:
            batch["dos"] = np.zeros(self.num_moments, dtype=np.float32)
            return batch
        remap = {int(u): i for i, u in enumerate(nodes)}
        r = np.array([remap[int(u)] for u in src])
        c = np.array([remap[int(u)] for u in dst])
        deg = np.bincount(np.concatenate([r, c]), minlength=n).astype(np.float64)
        dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))

        def matvec(x):
            y = np.zeros_like(x)
            w = dinv[r] * dinv[c]
            np.add.at(y, r, w[:, None] * x[c])
            np.add.at(y, c, w[:, None] * x[r])
            return y

        z = self._rng.choice([-1.0, 1.0], size=(n, self.num_probes))
        tkm1, tk = z, matvec(z)
        moments = [float((z * tkm1).sum() / (n * self.num_probes)),
                   float((z * tk).sum() / (n * self.num_probes))]
        for _ in range(self.num_moments - 2):
            tkp1 = 2.0 * matvec(tk) - tkm1
            moments.append(float((z * tkp1).sum() / (n * self.num_probes)))
            tkm1, tk = tk, tkp1
        batch["dos"] = np.asarray(moments[: self.num_moments], dtype=np.float32)
        return batch
