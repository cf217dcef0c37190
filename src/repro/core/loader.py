"""Unified CTDG/DTDG data loading (paper Defs. 3.3-3.4, Fig. 2).

``DGDataLoader`` iterates a ``DGraph`` view either

  * **by events** (CTDG): fixed event-count batches under the event-ordered
    granularity, or
  * **by time** (DTDG): fixed wall-clock windows of the view's (coarser)
    granularity — batches are snapshots ``G|_[t_i, t_i + tau_hat)``; empty
    windows can be emitted or skipped.

Each batch is materialized from storage, passed through the ``HookManager``
pipeline, and returned as a ``Batch``.

``PrefetchLoader`` overlaps batch preparation with device compute: a
background thread runs the inner loader (materialization + the full hook
pipeline) and stages each batch's arrays onto the device with
``jax.device_put`` while the jitted train step consumes the previous batch.
A bounded queue (default depth 2 = double buffering) provides back-pressure
so at most ``prefetch`` prepared batches are in flight; hook state stays
correct because the hook pipeline still executes strictly sequentially, just
one batch ahead of the consumer. This is the loader half of the
``SamplerSpec(device=True)`` pipeline in ``train.loop``. The staging
model is documented in ``docs/architecture.md``.

``snapshot_tensor`` is the DTDG counterpart of loading: instead of
iterating host batches, it tensorizes the whole discretized stream once
into the device-resident ``SnapshotTensor`` view (padded ``(T, capacity)``
src/dst/mask arrays) that the scan-compiled snapshot trainer consumes —
see ``docs/dtdg.md``.
"""

from __future__ import annotations

import queue
import threading
from functools import partial
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import Batch
from repro.core.graph import DGData, DGraph, SnapshotTensor
from repro.core.granularity import TimeDelta
from repro.core.hooks import HookManager


class DGDataLoader:
    """Iterate a ``DGraph`` view as hook-processed ``Batch``es.

    CTDG mode (``batch_size``): fixed event-count batches in stream order.
    DTDG mode (``batch_unit``): fixed time windows (snapshots) of a real-
    time granularity coarser-or-equal to the view's native unit. Each
    materialized batch is passed through ``hook_manager`` (when given)
    before being yielded. See ``docs/architecture.md``.
    """

    def __init__(
        self,
        dg: DGraph,
        hook_manager: Optional[HookManager] = None,
        batch_size: Optional[int] = 200,
        batch_unit: Optional[TimeDelta | str] = None,
        drop_last: bool = False,
        emit_empty: bool = False,
        window_ticks: int = 1,
        on_batch=None,
    ):
        """Iterate ``dg``.

        Exactly one of ``batch_size`` (iterate-by-events) or ``batch_unit``
        (iterate-by-time) must be set. ``window_ticks`` scales the time
        window (e.g. unit='h', window_ticks=6 -> 6-hour snapshots).
        ``on_batch`` (no-arg callable) runs after each batch has been
        hook-processed and handed off — the storage layer passes
        ``MmapStore.release`` here so an epoch over a memory-mapped
        stream keeps O(window) resident pages (``docs/storage.md``);
        hooks copy everything they keep, so dropped pages are safe.
        """
        if (batch_size is None) == (batch_unit is None):
            raise ValueError("set exactly one of batch_size / batch_unit")
        self.dg = dg
        self.manager = hook_manager
        self.on_batch = on_batch
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.emit_empty = emit_empty
        self.window_ticks = window_ticks
        if batch_unit is not None:
            unit = TimeDelta.coerce(batch_unit)
            native = dg.data.granularity
            if native.is_event_ordered:
                raise ValueError(
                    "iterate-by-time requires a real-time native granularity; "
                    "this graph is event-ordered (paper §3)"
                )
            if not unit.is_coarser_or_equal(native):
                raise ValueError(f"batch unit {unit} must be >= native {native}")
            self.batch_unit = unit
            self._ticks = unit.ticks_per(native) * window_ticks
        else:
            self.batch_unit = None
            self._ticks = None

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        """Number of batches (event batches or time windows) to be yielded;
        for time iteration this is an upper bound when windows can be
        empty and ``emit_empty=False``."""
        if self.batch_size is not None:
            n = self.dg.num_edge_events
            full, rem = divmod(n, self.batch_size)
            return full if (self.drop_last or rem == 0) else full + 1
        span = self.dg.t_hi - self.dg.t_lo
        return int(np.ceil(span / self._ticks))

    def __iter__(self) -> Iterator[Batch]:
        if self.batch_size is not None:
            yield from self._iter_events()
        else:
            yield from self._iter_time()

    # -- CTDG: fixed event count ----------------------------------------
    def _iter_events(self) -> Iterator[Batch]:
        lo, hi = self.dg.edge_slice()
        for start in range(lo, hi, self.batch_size):
            stop = min(start + self.batch_size, hi)
            if self.drop_last and stop - start < self.batch_size:
                break
            batch = self._materialize(start, stop)
            yield self._run_hooks(batch)
            if self.on_batch is not None:
                self.on_batch()

    # -- DTDG: fixed time window ------------------------------------------
    def _iter_time(self) -> Iterator[Batch]:
        data = self.dg.data
        t = self.dg.t_lo
        while t < self.dg.t_hi:
            t_next = min(t + self._ticks, self.dg.t_hi)
            lo, hi = data.edge_range(t, t_next)
            if hi > lo or self.emit_empty:
                batch = self._materialize(lo, hi, window=(t, t_next))
                yield self._run_hooks(batch)
                if self.on_batch is not None:
                    self.on_batch()
            t = t_next

    # ------------------------------------------------------------------
    def _materialize(self, lo: int, hi: int, window=None) -> Batch:
        raw = self.dg.materialize(lo, hi)
        meta = {
            # Global event ids (sliced splits carry their root offset), so
            # eid-keyed edge-feature lookups are correct on any split.
            "eids": np.arange(lo, hi, dtype=np.int64)
            + getattr(self.dg.data, "eid_offset", 0),
            "window": window,
            "granularity": self.batch_unit or self.dg.granularity,
        }
        return Batch(raw, meta)

    def _run_hooks(self, batch: Batch) -> Batch:
        if self.manager is None:
            return batch
        return self.manager.execute(batch)


@partial(jax.jit, static_argnames=("num_rows", "capacity"))
def _tensorize_snapshots(usrc, udst, uct, count, *, num_rows: int,
                         capacity: int):
    """Scatter tick-major discretized events into ``(T, capacity)`` grids.

    Inputs are the padded outputs of ``discretize_edges_padded`` with
    ``uct`` already shifted to **zero-based** row ticks (the caller
    subtracts the first tick on staging, so huge absolute ticks can never
    overflow the int32 arithmetic here; padding keeps a large sentinel
    beyond ``count``, so the array stays globally sorted and the per-row
    extents come from one ``searchsorted``). Events beyond a row's
    ``capacity`` are dropped by the scatter's out-of-bounds semantics;
    callers size ``capacity`` to the max row count to make that impossible
    by construction.
    """
    g = usrc.shape[0]
    idx = jnp.arange(g, dtype=jnp.int32)
    valid = idx < count
    starts = jnp.searchsorted(
        uct, jnp.arange(num_rows, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    row = jnp.clip(uct, 0, num_rows - 1)
    pos = idx - starts[row]
    ok = valid & (pos < capacity)
    flat = jnp.where(ok, row * capacity + pos, num_rows * capacity)
    grid = lambda fill, dtype: jnp.full(num_rows * capacity, fill, dtype)
    src_g = grid(0, jnp.int32).at[flat].set(usrc)
    dst_g = grid(0, jnp.int32).at[flat].set(udst)
    mask_g = grid(False, bool).at[flat].set(ok)
    bounds = jnp.concatenate([starts, count[None].astype(jnp.int32)])
    counts = jnp.clip(jnp.diff(bounds), 0, capacity)
    shape = (num_rows, capacity)
    return (src_g.reshape(shape), dst_g.reshape(shape),
            mask_g.reshape(shape), counts)


def snapshot_tensor(
    data: DGData,
    granularity: TimeDelta | str,
    capacity: Optional[int] = None,
    device=None,
) -> SnapshotTensor:
    """Tensorize a stream into the device-resident ``SnapshotTensor`` view.

    One jitted ``discretize_edges_padded`` call collapses duplicate
    ``(tick, src, dst)`` classes at the target granularity, then one jitted
    scatter (``_tensorize_snapshots``) lays them out as padded
    ``(T, capacity)`` src/dst/mask device arrays. The only host syncs are
    build-time bookkeeping (valid count + per-row extents to choose the
    capacity); after this, a DTDG epoch touches no host arrays at all.

    ``capacity`` defaults to the max per-snapshot edge count rounded up to
    a power of two (one XLA compilation across granularities that land in
    the same bucket); passing a smaller value deterministically drops each
    oversized snapshot's tail.
    """
    from repro.core.discretize import (
        _coarse_ticks,
        _host_ticks,
        discretize_edges_padded,
        jax_discretize_supported,
    )

    unit = TimeDelta.coerce(granularity)
    k = _coarse_ticks(data, unit)
    e = data.num_edge_events
    span = data.time_span
    t0, t_end = span[0] // k, span[1] // k
    num_rows = max(int(t_end - t0) + 1, 1)

    if e and jax_discretize_supported(data, k, edges_only=True):
        t_staged, k_dev = _host_ticks(data.edge_t, k)
        usrc, udst, uct, _, count = discretize_edges_padded(
            jnp.asarray(data.src), jnp.asarray(data.dst),
            jnp.asarray(t_staged), jnp.zeros((e, 0), jnp.float32),
            k=k_dev, reduce="first", capacity=e, feat_dim=0,
        )
        # Zero-base the row ticks for the scatter (t0 >= 0, so the padded
        # int32-max sentinel shifts without wrapping and stays largest).
        uct = uct - np.int32(t0)
    else:  # int32 guard tripped (or empty stream): host numpy fallback
        disc = data.discretize(unit, reduce="first", backend="numpy")
        usrc = jnp.asarray(disc.src, jnp.int32)
        udst = jnp.asarray(disc.dst, jnp.int32)
        # Shift in int64 on host: absolute ticks can exceed int32 (that is
        # exactly why this branch runs), relative ones cannot.
        uct = jnp.asarray(disc.edge_t - t0, jnp.int32)
        count = jnp.asarray(disc.num_edge_events, jnp.int32)

    g = int(count)
    row_counts = np.bincount(
        np.asarray(uct[:g], dtype=np.int64), minlength=num_rows
    )
    if capacity is None:
        capacity = int(2 ** np.ceil(np.log2(max(row_counts.max(), 1))))
    src_g, dst_g, mask_g, counts = _tensorize_snapshots(
        usrc, udst, uct, count, num_rows=num_rows, capacity=int(capacity),
    )
    if device is not None:
        src_g, dst_g, mask_g, counts = jax.device_put(
            (src_g, dst_g, mask_g, counts), device)
    return SnapshotTensor(
        src=src_g, dst=dst_g, mask=mask_g, counts=counts,
        t0=int(t0), ticks=int(k), unit=unit, num_nodes=int(data.num_nodes),
    )


class _HostStagingPool:
    """Rotating reusable host staging buffers for ``PrefetchLoader``.

    Fresh numpy arrays from the hook pipeline live in pageable memory, so
    on GPU backends every ``jax.device_put`` pays a pageable->pinned copy
    inside the driver before the H2D DMA can overlap compute. Staging each
    batch into a small set of *reused* host buffers (one per batch key,
    rotated round-robin across ``depth`` slots) keeps the source addresses
    stable — the runtime's transfer machinery can keep them registered —
    and lets the transfer be issued with ``donate=True`` (the staged array
    is never read again by the producer).

    ``depth`` bounds how soon a slot can be rewritten (only after ``depth``
    newer batches were staged), and rewriting additionally blocks on the
    device array last transferred from that slot (``note`` /
    ``block_until_ready`` — normally a no-op that far behind the queue's
    back-pressure, but it makes reuse-before-DMA-completion impossible by
    construction rather than by timing). Rotation is explicit (``advance``
    once per batch) so every array of one batch shares a slot generation.
    """

    def __init__(self, depth: int):
        if depth < 2:
            raise ValueError("staging depth must be >= 2")
        self.depth = depth
        self._slot = 0
        self._bufs = {}
        self._pending = {}
        # XLA's CPU client zero-copies 64-byte-aligned host buffers into
        # device arrays, which would alias a reused slot straight into an
        # already-yielded batch. Deliberately misaligned slots force a real
        # copy there; on accelerators device memory is separate, so
        # alignment is kept for the H2D DMA's sake.
        import jax

        self._misalign = jax.default_backend() == "cpu"

    def _alloc(self, shape, dtype: np.dtype) -> np.ndarray:
        n = int(np.prod(shape))
        if not self._misalign:
            return np.empty(shape, dtype)
        extra = max(64 // max(dtype.itemsize, 1), 1)
        raw = np.empty(n + extra, dtype)
        for k in range(extra):
            if (raw.ctypes.data + k * dtype.itemsize) % 64:
                return raw[k:k + n].reshape(shape)
        return raw[:n].reshape(shape)  # unreachable: a window this wide
        # always contains a misaligned element address

    def advance(self) -> None:
        """Rotate to the next slot generation (call once per batch)."""
        self._slot = (self._slot + 1) % self.depth

    def stage(self, key: str, arr: np.ndarray) -> np.ndarray:
        """Copy ``arr`` into this slot's reusable buffer for ``key``
        (int64 narrowed to int32, matching ``DeviceTransferHook``),
        waiting out any still-pending transfer from the same slot first."""
        dtype = np.dtype(np.int32) if arr.dtype == np.int64 else arr.dtype
        k = (key, self._slot)
        pending = self._pending.pop(k, None)
        if pending is not None:
            pending.block_until_ready()
        buf = self._bufs.get(k)
        if buf is None or buf.shape != arr.shape or buf.dtype != dtype:
            buf = self._alloc(arr.shape, dtype)
            self._bufs[k] = buf
        np.copyto(buf, arr, casting="unsafe")
        return buf

    def note(self, key: str, device_array) -> None:
        """Record the device array transferred from this slot's ``key``
        buffer, so the slot's next rewrite can block on its completion."""
        self._pending[(key, self._slot)] = device_array


class PrefetchLoader:
    """Double-buffered device-staging wrapper around any batch iterable.

    While the consumer (the jitted train/eval step) is busy with batch ``i``,
    a daemon thread prepares batch ``i+1``: it pulls from ``inner`` (which
    runs the hook pipeline) and eagerly ships every numpy array to ``device``
    via ``jax.device_put`` (int64 narrowed to int32, matching
    ``DeviceTransferHook``). Arrays already on device pass through untouched,
    so it composes with device-resident hooks.

    ``device`` may also be a ``jax.sharding.Sharding`` — the mesh-sharded
    sampling pipeline passes the mesh-replicated ``NamedSharding`` here so
    prefetched batches land on the same device set as the ``shard_map``
    sampler state and the replicated model step (see ``docs/sharding.md``).

    ``telemetry`` (a ``repro.obs.Telemetry``; disabled default) makes the
    queue dynamics observable (``docs/observability.md``): a
    ``loader/stage`` span around each producer-side H2D staging (the hooks
    run before it, in ``inner``), a ``loader/wait`` span around each
    consumer-side wait for the next batch, ``loader/producer_stall`` /
    ``loader/consumer_stall`` counters (bounded-queue full on put / empty
    on get), a ``loader/queue_depth`` gauge sampled at each dequeue, and a
    ``loader/batches`` counter.

    ``staging`` enables the reusable host staging buffers
    (``_HostStagingPool``) so the H2D transfer reads from stable,
    re-registered addresses and can donate them; ``None`` (default)
    auto-enables this on GPU backends only — on CPU "transfer" is a local
    copy and staging would only add another one. Donation is never applied
    on CPU, where ``jax.device_put(..., donate=True)`` zero-copy *aliases*
    the source buffer and a reused slot would corrupt earlier batches.

    Exceptions raised in the producer are re-raised in the consumer **with
    the original traceback** (the exception instance travels through the
    queue, FIFO with the batches staged before it, so already-prepared
    batches are still delivered first and the error surfaces within one
    ``next()``). If the producer thread dies without delivering either the
    end-of-stream sentinel or an exception, the consumer raises
    ``RuntimeError`` instead of blocking forever. The producer thread exits
    promptly when the consumer stops iterating (``close``, or abandoning
    the iterator) because the bounded queue blocks with a timeout and
    checks a stop flag.
    """

    _END = object()
    _STOPPED = object()

    def __init__(self, inner, device=None, prefetch: int = 2,
                 staging: Optional[bool] = None, telemetry=None):
        if prefetch < 1:
            raise ValueError("prefetch depth must be >= 1")
        from repro.obs import NULL

        self.inner = inner
        self._device = device
        self.prefetch = prefetch
        self.telemetry = telemetry if telemetry is not None else NULL
        self._active: list = []  # live (stop, thread) pairs, for close()
        self._active_lock = threading.Lock()
        if staging is None:
            staging = jax.default_backend() == "gpu"
        self.staging = staging
        # depth > max batches in flight: `prefetch` queued + 1 being
        # consumed + 1 being produced.
        self._pool = _HostStagingPool(prefetch + 2) if staging else None

    def __len__(self) -> int:
        return len(self.inner)

    def _stage(self, batch: Batch) -> Batch:
        from repro.core.tg_hooks import stage_batch

        if self._pool is not None:
            self._pool.advance()
        return stage_batch(batch, self._device, pool=self._pool)

    def __iter__(self) -> Iterator[Batch]:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        tel = self.telemetry

        def put_or_stop(item) -> bool:
            """Bounded put that aborts when the consumer has left."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    # Back-pressure: the consumer is the bottleneck here.
                    tel.count("loader/producer_stall")
                    continue
            return False

        def produce():
            try:
                for batch in self.inner:
                    with tel.span("loader/stage"):
                        staged = self._stage(batch)
                    if not put_or_stop(staged):
                        return
                put_or_stop(self._END)
            except BaseException as e:  # surfaced on the consumer side
                put_or_stop(e)

        def take():
            """The next queued item, or ``_STOPPED`` once closed."""
            while True:
                try:
                    item = q.get(timeout=0.2)
                except queue.Empty:
                    if stop.is_set():  # close() mid-iteration: clean end
                        return self._STOPPED
                    if not thread.is_alive():
                        raise RuntimeError(
                            "PrefetchLoader producer thread died without "
                            "signalling end-of-stream or an error")
                    # Starvation: the producer is the bottleneck here.
                    tel.count("loader/consumer_stall")
                    continue
                # Closed: queued batches are dropped.
                return self._STOPPED if stop.is_set() else item

        thread = threading.Thread(target=produce, daemon=True)
        with self._active_lock:
            self._active.append((stop, thread))
        thread.start()
        try:
            while True:
                with tel.span("loader/wait"):
                    item = take()
                if item is self._STOPPED:
                    return
                if tel.enabled:
                    tel.gauge("loader/queue_depth", q.qsize())
                if item is self._END:
                    return
                if isinstance(item, BaseException):
                    # Re-raising the instance keeps the producer-side
                    # traceback (it rode along on __traceback__).
                    raise item
                tel.count("loader/batches")
                yield item
        finally:
            # Join the producer too: left running, it may still be inside a
            # jitted hook call when the interpreter shuts down.
            stop.set()
            if thread.is_alive():
                thread.join(timeout=5)
            with self._active_lock:
                self._active = [a for a in self._active if a[0] is not stop]

    def close(self) -> None:
        """Stop all producer threads spawned by active iterations and join
        them. Idempotent: safe to call repeatedly or with no iteration in
        flight; consumers still blocked in ``next()`` observe a clean end
        of iteration."""
        with self._active_lock:
            active = list(self._active)
        for stop, thread in active:
            stop.set()
        for stop, thread in active:
            thread.join(timeout=5)
