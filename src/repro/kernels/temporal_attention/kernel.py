"""Temporal neighbor attention Pallas TPU kernels.

The paper's profiling (Table 11) puts TGAT attention + sampling at ~28% of
epoch time. On TPU the hot loop is: for each seed node, attend its K most
recent neighbors (K = 10..32, padded). See ``docs/kernels.md`` for the full
memory-space layout and parity-testing story.

Layout. Every kernel works on 2-D, head-flattened rows and computes a
*block* of B seeds as one unit: the block's B*K neighbor rows form one
``(B*K, H*D)`` slab, with the B*K (seed, slot) pairs on sublanes and the
heads side by side on lanes. A ``(B*K, B)`` one-hot seed matrix maps
between per-seed and per-slot rows: the queries are spread over their
slots by one matmul, the masked softmax reduces over each seed's K rows
with it on the VPU, and the output sums the weighted values back per seed
with one transposed matmul. Per head, the scores are a lane reduction of
``k * (q * head_mask)``. Every MXU matmul is 2-D, at
``Precision.HIGHEST`` with f32 accumulation; the kernel body is the same
size whatever B is (no Python loop over seeds or slots).

``temporal_attention_kernel`` is the un-fused baseline: it consumes
pre-gathered k/v, flattened in the wrapper to ``(S*K, H*D)`` rows, and
attends a grid step's seeds as one block.

``fused_temporal_layer_kernel`` is the device-sampling variant (the layer-1
compute of TGAT/TGN when ``device_sampling=True``): instead of consuming
pre-gathered ``(S, K, H, D)`` k/v tensors, it takes the seed ids + query
times, the resident packed recency buffer (``(N+1, K, 3)`` rows of
``DeviceRecencySampler``) and *node-level* k/v tables, and performs the
neighbor gather inside the kernel. The edge-feature and Bochner
time-encoding terms of the TGAT key/value projections are folded in as
additive biases computed in VMEM:

  k[s, j] = k_table[nbr_j]                      # DMA'd node-level term
          + phi(t_s - t_j) @ Wt_k               # in-kernel time bias
          + edge_feats[eid_j] @ We_k            # DMA'd edge bias

so the fat ``(S, K, H, D)`` intermediates never exist in HBM. The k and v
tables travel as one ``(N, 1, 2*H*D)`` row table (one DMA per neighbor),
and the key and value slices of each bias projection as one
``(d, 2*H*D)`` matrix (one matmul per block). XLA gathers each seed's
packed buffer row up front (ids, times, eids: an ``(S, K, 3)`` int32 index
block); its ids reach SMEM as DMA source indices, -1 where a slot is
empty or its edge featureless (nothing is copied there), and its validity
/ time-delta columns reach VMEM as one ``(3, B*K)`` group per block. Seeds
may be negative (hop-2 frontier padding): their slots copy nothing and
their rows are masked out, so the 2-hop TGAT frontier can run through the
kernel unclamped.

Grid. A grid step holds a tile of ``nblk`` blocks of B seeds and walks
them through a 2-slot DMA pipeline: while block i is computed, block
i+1's neighbor-row copies (one per valid slot, issued from a rolled loop,
all in flight at once) land in the other slot. B comes from the VMEM the staging may
take (``_plan``): each staged row is a ``(1, width)`` tile that pads to 8
sublanes. ``fused_recency_attention_kernel`` (ids-only buffer, no bias
folding) is a thin wrapper over the same body.

``fused_temporal_layer_bwd_kernel`` is the flash-attention-style backward:
it re-stages every block through the same pipeline, recomputes the
attention weights in VMEM, and produces all input gradients without ever
materializing an (S, K, ·) tensor in HBM — dq as a blocked output, the
weight gradients (time/edge projections, Bochner parameters) as
VMEM-resident accumulators with one ``(·)ᵀ @ (·)`` matmul per block, and
dk/dv by per-row DMA read-modify-write into an ANY-space output aliased
to a zero-initialized operand (the TPU has no atomics; the grid is
sequential and each read-modify-write ends before the next begins, so
duplicate neighbor ids accumulate exactly).

The jnp oracles in ``ref.py`` remain the correctness references
(``interpret=True`` executes these kernel bodies on CPU for parity tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import telemetry

NEG_INF = -1e30
_HI = jax.lax.Precision.HIGHEST
_LANE = 128
_SUBLANE = 8
# VMEM for the 2-slot neighbor-row staging, both slots. v5e's default
# scoped VMEM is 16 MiB: 11 MiB land the rows, the rest holds the
# pipelined operand blocks, the backward's accumulators and the block's
# f32 intermediates (the forward and backward compile for a v5e at TGAT's
# and TGN's widths, ``tests/test_tpu_compile.py``).
_STAGE_BYTES = 11 << 20
# A staged (1, width) row pads to a whole (8, 128) f32 tile per 128 lanes
# (a (16, 128) bf16 one): 4 KiB either way.
_TILE_BYTES = 4096
# Seeds a grid step walks through the pipeline; only its first block's
# copies are not hidden behind compute.
_TILE_SEEDS = 128


def _mm(a, b, contract=((1,), (0,))):
    """f32 2-D matmul on the MXU at full precision (``contract`` as in
    ``lax.dot_general``: NN by default, ``((0,), (0,))`` for aᵀb,
    ``((1,), (1,))`` for abᵀ)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _head_masks(heads: int, hdim: int, width: int):
    """One (1, width) f32 lane mask per head, selecting that head's D lanes
    (lanes past H*D are padding and belong to no head)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [((lane >= h * hdim) & (lane < (h + 1) * hdim)).astype(jnp.float32)
            for h in range(heads)]


def _seed_onehot(bs: int, kbuf: int):
    """(B*K, B) f32: row ``r`` of a block belongs to its seed ``r // K``."""
    shape = (bs * kbuf, bs)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    first = jax.lax.broadcasted_iota(jnp.int32, shape, 1) * kbuf
    return ((row >= first) & (row < first + kbuf)).astype(jnp.float32)


def _per_seed(seg, x):
    """(B*K, 1) per-slot column -> (1, B) sums over each seed's slots."""
    return jnp.sum(seg * x, axis=0, keepdims=True)


def _per_slot(seg, y):
    """(1, B) per-seed row -> (B*K, 1): each slot gets its seed's value."""
    return jnp.sum(seg * y, axis=1, keepdims=True)


def _segment_softmax(s, valid, seg):
    """Softmax of a (B*K, 1) score column within each seed's K slots, with
    invalid slots excluded and a seed with none valid zeroed — identical
    to the oracle's ``softmax`` + ``where(mask.any(), ·, 0)``."""
    s = jnp.where(valid > 0, s, NEG_INF)
    top = jnp.max(jnp.where(seg > 0, s, NEG_INF), axis=0, keepdims=True)
    p = jnp.exp(s - _per_slot(seg, top))
    p = p / jnp.maximum(_per_slot(seg, _per_seed(seg, p)), 1e-30)
    return p * valid


def _spread(cols, masks):
    """Per-head (B*K, 1) columns -> one (B*K, width) array, each head's
    column on that head's lanes."""
    out = cols[0] * masks[0]
    for c, m in zip(cols[1:], masks[1:]):
        out = out + c * m
    return out


def _attention_probs(k, q_rows, valid, seg, masks):
    """Per head, the (B*K, 1) attention weights of a block; ``q_rows`` is
    the scaled query spread over its seed's slots."""
    return [_segment_softmax(jnp.sum(k * (q_rows * m), axis=1, keepdims=True),
                             valid, seg) for m in masks]


def _attend_block(q, k, v, valid, seg, masks):
    """A block's multi-head attention: q (B, width) scaled queries; k, v
    (B*K, width); valid (B*K, 1) f32 in {0, 1}. Returns (B, width)."""
    p = _attention_probs(k, _mm(seg, q), valid, seg, masks)
    return _mm(seg, _spread(p, masks) * v, ((0,), (0,)))


def _rows(i, n: int):
    """The ``i``-th run of ``n`` rows (``n`` sublane-aligned on the chip)."""
    return pl.ds(pl.multiple_of(i * n, n), n)


def _temporal_attention_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *,
                               scale: float, block_s: int, kbuf: int,
                               heads: int, hdim: int):
    o_ref[...] = _attend_block(
        q_ref[...].astype(jnp.float32) * scale,
        k_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
        mask_ref[...], _seed_onehot(block_s, kbuf),
        _head_masks(heads, hdim, heads * hdim)).astype(o_ref.dtype)


def temporal_attention_kernel(q, k, v, mask, *, block_s: int = 32,
                              scale: float | None = None,
                              interpret: bool = False):
    """q: (S, H, D); k, v: (S, K, H, D); mask: (S, K) -> (S, H, D)."""
    S, H, D = q.shape
    K = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    block_s = min(block_s, S)
    pad = (-S) % block_s
    q2 = jnp.pad(q.reshape(S, H * D), ((0, pad), (0, 0)))
    k2, v2 = (jnp.pad(x.reshape(S * K, H * D), ((0, pad * K), (0, 0)))
              for x in (k, v))
    m2 = jnp.pad(mask.astype(jnp.float32).reshape(S * K, 1),
                 ((0, pad * K), (0, 0)))
    ns = (S + pad) // block_s
    rows = pl.BlockSpec((block_s * K, H * D), lambda i: (i, 0))

    out = pl.pallas_call(
        functools.partial(_temporal_attention_kernel, scale=scale,
                          block_s=block_s, kbuf=K, heads=H, hdim=D),
        grid=(ns,),
        in_specs=[
            pl.BlockSpec((block_s, H * D), lambda i: (i, 0)),
            rows, rows,
            pl.BlockSpec((block_s * K, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, H * D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S + pad, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(q2, k2, v2, m2)
    return out[:S].reshape(S, H, D)


def _round_up(n: int, m: int = _LANE) -> int:
    return -(-n // m) * m


def _pad2(x, rows: int, cols: int):
    """Zero-pad a 2-D array up to (rows, cols)."""
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _row_table(x, width: int):
    """(R, d) -> (R, 1, width), zero-padded on lanes: one whole (1, width)
    tile per index, which a single-row DMA out of HBM may copy on TPU (a
    row of an (R, d) array tiled (8, 128) slices a tile and is refused)."""
    return _pad2(x, x.shape[0], width).reshape(x.shape[0], 1, width)


def _kv_pair(a, b, rows: int, width: int):
    """Two (R, d) arrays -> one (rows, 2*width) f32 array, ``a`` on the
    first ``width`` lanes and ``b`` on the next, each zero-padded."""
    return jnp.concatenate([_pad2(x.astype(jnp.float32), rows, width)
                            for x in (a, b)], axis=1)


def _plan(S: int, K: int, width: int, block_s, name: str) -> dict:
    """Seeds per block B, blocks per grid step and seed padding of one call.

    B is ``block_s`` when given (tests), else as many seeds as the staging
    budget holds: 2 slots x K rows x ``width`` lanes, each row a padded
    tile — rounded down to a multiple of 8, at least 8. B never exceeds S
    rounded up to 8, so small calls do not pad to a large block. A grid
    step then takes up to ``_TILE_SEEDS`` seeds in whole blocks, spread so
    the padding stays under one block per step. Records the choice in
    every enabled ``repro.obs`` telemetry (this runs as the wrapper traces,
    once per call shape).
    """
    if block_s is None:
        per_seed = 2 * K * (width // _LANE) * _TILE_BYTES
        block_s = max(_SUBLANE,
                      _STAGE_BYTES // per_seed // _SUBLANE * _SUBLANE)
    bs = min(block_s, _round_up(S, _SUBLANE))
    blocks = -(-S // bs)
    steps = -(-blocks // max(1, _TILE_SEEDS // bs))
    nblk = -(-blocks // steps)
    pad = steps * nblk * bs - S
    telemetry.note(name, seeds=S, slots=K, block=bs, tile=nblk * bs,
                   pad_share=pad / (S + pad))
    return dict(block_s=bs, nblk=nblk, ns=steps, pad=pad)


def _layer_inputs(q, k_table, v_table, seeds, seed_times, buf, time_w,
                  time_b, wt_k, wt_v, edge_feats, we_k, we_v, block_s,
                  name):
    """Assemble the fused forward/backward pallas_call inputs.

    XLA gathers each seed's packed buffer row up front — an (S, K, 3)
    int32 index block, not the fat (S, K, H, D) features — and splits it
    into the DMA indices (``idx``: (S, 2K) neighbor and edge ids, -1 for
    an invalid slot or a featureless edge, blocked into SMEM) and the
    vector columns (``cols``: one (3, B*K) f32 group per block of slot
    validity, query/neighbor time delta and edge validity, blocked into
    VMEM). The node tables become one ``_row_table`` of k | v rows in
    ANY/HBM, each half lane-padded to a multiple of 128, and the
    edge-feature storage another; q is lane-padded to the same H*D width
    and the key | value slices of each weight group are paired the same
    way, with zeros the attention never reads (the head masks cover the
    real lanes).

    Returns ``(lead, rest, meta)``: the blocked seed-axis operands
    ``[idx, cols, q]`` and the grid-invariant ``[kv, time group, edge
    group]``, each a list of ``(operand, BlockSpec)``, and the static
    sizes the wrappers need.
    """
    S, H, D = q.shape
    HD = H * D
    HDp = _round_up(HD)
    K = buf.shape[1]
    DEp = 0 if we_k is None else _round_up(edge_feats.shape[1])
    m = _plan(S, K, 2 * HDp + DEp, block_s, name)
    bs, pad = m["block_s"], m["pad"]
    tile = m["nblk"] * bs

    seeds = seeds.astype(jnp.int32)
    seed_times = (jnp.zeros_like(seeds) if seed_times is None
                  else seed_times.astype(jnp.int32))
    rows = buf.astype(jnp.int32)[jnp.maximum(seeds, 0)]         # (S, K, 3)
    ids, eids = rows[..., 0], rows[..., 2]
    valid = (ids >= 0) & (seeds >= 0)[:, None]  # seed < 0: hop-2 padding
    has_edge = valid & (eids >= 0)
    dt = seed_times[:, None] - rows[..., 1]   # int32 first, as time_encode
    # One (3, B*K) row group per block, each column padded and cut into
    # blocks before stacking: no (S, K, ·) float array in HBM, and no
    # 3-lane array padded to 128 lanes.
    cols = jnp.stack([jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
                      .reshape(-1, bs * K) for x in (valid, dt, has_edge)],
                     axis=1)
    # DMA source rows; -1 where there is nothing to copy or scatter.
    idx = jnp.concatenate([jnp.where(valid, ids, -1),
                           jnp.where(has_edge, eids, -1)], 1)

    blocked = lambda shp: pl.BlockSpec(shp, lambda i: (i, 0))  # noqa: E731
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)  # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    lead = [
        (jnp.pad(idx, ((0, pad), (0, 0)), constant_values=-1),
         pl.BlockSpec((tile, 2 * K), lambda i: (i, 0),
                      memory_space=pltpu.SMEM)),
        (cols, pl.BlockSpec((m["nblk"], 3, bs * K), lambda i: (i, 0, 0))),
        (_pad2(q.reshape(S, HD), S + pad, HDp), blocked((tile, HDp))),
    ]
    N = k_table.shape[0]
    kv = _kv_pair(k_table.reshape(N, HD), v_table.reshape(N, HD), N, HDp)
    rest = [(kv.reshape(N, 1, 2 * HDp), hbm)]
    if wt_k is not None:
        d_time = wt_k.shape[0]
        tw = time_w.reshape(1, -1).astype(jnp.float32)
        tb = time_b.reshape(1, -1).astype(jnp.float32)
        wt = _kv_pair(wt_k.reshape(d_time, HD), wt_v.reshape(d_time, HD),
                      d_time, HDp)
        rest += [(a, full(a)) for a in (tw, tb, wt)]
    if we_k is not None:
        d_edge = edge_feats.shape[1]
        we = _kv_pair(we_k.reshape(d_edge, HD), we_v.reshape(d_edge, HD),
                      DEp, HDp)
        rest += [(_row_table(edge_feats, DEp), hbm), (we, full(we))]
    meta = dict(m, S=S, K=K, H=H, D=D, HDp=HDp, DEp=DEp, tile=tile,
                e_dtype=None if we_k is None else edge_feats.dtype)
    return lead, rest, meta


def _staging_scratch(meta, has_edge):
    """2-slot VMEM landing zones for a block's neighbor rows (k | v, and
    edge features), and their per-slot DMA semaphores. Each row lands in
    its own (1, width) tile, so a single-row DMA never slices a sublane
    tile (a (B*K, width) slab would be tiled (8, 128) and refuse it). An
    SMEM array counts the copies each slot's block issued, per table."""
    rows = meta["block_s"] * meta["K"]
    scratch = [pltpu.VMEM((2, rows, 1, 2 * meta["HDp"]), jnp.float32)]
    if has_edge:
        scratch.append(pltpu.VMEM((2, rows, 1, meta["DEp"]), meta["e_dtype"]))
    return scratch, [pltpu.SemaphoreType.DMA((2,))] * len(scratch) + [
        pltpu.SMEM((2, len(scratch)), jnp.int32)]   # copies issued per slot


def _unpack_tables(it, has_time: bool, has_edge: bool) -> dict:
    """Pop the grid-invariant refs in ``_layer_inputs``' ``rest`` order."""
    t = {"kv": next(it)}                    # (N, 1, 2*H*D) ANY k | v rows
    if has_time:
        t.update(tw=next(it), tb=next(it),  # (1, d_time) Bochner params
                 wt=next(it))               # (d_time, 2*H*D) time proj
    if has_edge:
        t.update(ef=next(it),               # (E, 1, d_edge) ANY features
                 we=next(it))               # (d_edge, 2*H*D) edge proj
    return t


def _for_slots(i, bs: int, kbuf: int, body, carry=0):
    """Run ``body(j, r, kk, carry)`` over block ``i``'s B*K (seed, slot)
    pairs in two rolled loops: ``j`` the seed's row in the tile, ``r`` the
    pair's row in the block, ``kk`` the slot. Returns the final carry."""
    def seed(b, c):
        return jax.lax.fori_loop(
            0, kbuf, lambda kk, c: body(i * bs + b, b * kbuf + kk, kk, c), c)

    return jax.lax.fori_loop(0, bs, seed, carry)


def _make_stager(idx_ref, t, kv_scr, e_scr, sems, counts, *, bs: int,
                 kbuf: int, has_edge: bool):
    """Build the double-buffered per-block DMA staging closures.

    Shared by the forward and backward kernel bodies: both walk the same
    blocks and need the same staged rows (the B*K neighbor k | v rows, and
    optionally the B*K edge-feature rows) in 2-slot scratch. ``idx_ref``
    holds the tile's neighbor ids (columns ``[0, K)``) and edge ids
    (``[K, 2K)``) in SMEM, so every source index is known before any copy
    starts. A slot whose id is -1 (an empty or padding slot, a featureless
    edge) copies nothing; the caller zeroes what its stale row holds.

    Returns ``(stage, wait)``: ``stage(i)`` issues block i's row copies
    into slot ``i % 2`` from a rolled loop, all in flight at once, and
    counts them in ``counts``; ``wait(i)`` blocks until they have all
    landed, one wait per copy.
    """
    tables = [(t["kv"], kv_scr, 0)]
    if has_edge:
        tables.append((t["ef"], e_scr, kbuf))

    def stage(i):
        sl = i % 2

        def start(j, r, kk, issued):
            out = []
            for (src, scr, col), sem, n in zip(tables, sems, issued):
                row = idx_ref[j, col + kk]

                @pl.when(row >= 0)
                def _():
                    pltpu.make_async_copy(src.at[row], scr.at[sl, r],
                                          sem.at[sl]).start()

                out.append(n + jnp.where(row >= 0, 1, 0))
            return tuple(out)

        issued = _for_slots(i, bs, kbuf, start, (jnp.int32(0),) * len(tables))
        for tab, n in enumerate(issued):
            counts[sl, tab] = n

    def wait(i):
        sl = i % 2
        for tab, ((src, scr, _), sem) in enumerate(zip(tables, sems)):
            one = pltpu.make_async_copy(src.at[0], scr.at[sl, 0], sem.at[sl])

            def step(_, c, one=one):
                one.wait()
                return c

            jax.lax.fori_loop(0, counts[sl, tab], step, 0)

    return stage, wait


def _block_kv(i, cols_ref, t, kv_scr, e_scr, *, rows: int, has_time: bool,
              has_edge: bool):
    """Rebuild block i's biased (B*K, 2*H*D) k | v rows from staged scratch.

    Shared by the forward (to attend) and the backward (to recompute the
    attention weights flash-style). Returns ``(valid, kv, phi, theta, dt,
    e)``: the (B*K, 1) slot validity, the keys | values, the Bochner
    encoding ``phi = cos(theta)`` of the (B*K, 1) query/neighbor time
    deltas ``dt``, and the zeroed edge-feature rows ``e`` (the backward
    reuses the last four for the weight gradients).
    """
    sl = i % 2
    c = cols_ref[i].T                   # (B*K, 3): valid, dt, edge valid
    valid = c[:, 0:1]
    slab = lambda scr: scr[sl].reshape(rows, scr.shape[3]  # noqa: E731
                                       ).astype(jnp.float32)
    kv = jnp.where(valid > 0, slab(kv_scr), 0.0)  # uncopied rows are stale
    phi = theta = dt = e = None
    if has_time:
        # The Bochner encoding phi = cos(dt * w + b) on the VPU, then one
        # (B*K, d_time) @ (d_time, 2*H*D) bias matmul on the MXU.
        dt = c[:, 1:2]
        theta = dt * t["tw"][...] + t["tb"][...]
        phi = jnp.cos(theta)
        kv = kv + _mm(phi, t["wt"][...])
    if has_edge:
        e = jnp.where(c[:, 2:3] > 0, slab(e_scr), 0.0)
        kv = kv + _mm(e, t["we"][...])
    return valid, kv, phi, theta, dt, e


def _fused_layer_kernel(idx_ref, cols_ref, q_ref, *refs, scale: float,
                        block_s: int, nblk: int, kbuf: int, heads: int,
                        hdim: int, has_time: bool, has_edge: bool):
    """Double-buffered fused gather + bias-fold + attention body.

    ``idx_ref`` (tile, 2K) SMEM DMA indices, ``cols_ref`` (nblk, 3, B*K)
    VMEM slot columns and ``q_ref`` (tile, H*D) VMEM queries are this grid
    step's seeds, ``nblk`` blocks of ``block_s``; ``refs`` unpacks (in
    order) the grid-invariant tables and weights (``_unpack_tables``), the
    (tile, H*D) output and the staging scratch from ``_staging_scratch``.
    """
    it = iter(refs)
    t = _unpack_tables(it, has_time, has_edge)
    o_ref = next(it)
    kv_scr = next(it)                       # (2, B*K, 1, 2*H*D) VMEM
    e_scr = next(it) if has_edge else None  # (2, B*K, 1, d_edge) VMEM
    *sems, counts = it                      # staging semaphores, counts

    hdp = q_ref.shape[-1]
    masks = _head_masks(heads, hdim, hdp)
    seg = _seed_onehot(block_s, kbuf)
    stage, wait = _make_stager(idx_ref, t, kv_scr, e_scr, sems, counts,
                               bs=block_s, kbuf=kbuf, has_edge=has_edge)

    # Prologue: stage block 0; the loop then overlaps block i+1's copies
    # with block i's compute (classic 2-slot software pipeline).
    stage(0)

    def per_block(i, carry):
        @pl.when(i + 1 < nblk)
        def _():
            stage(i + 1)

        wait(i)
        valid, kv, *_ = _block_kv(i, cols_ref, t, kv_scr, e_scr,
                                  rows=block_s * kbuf, has_time=has_time,
                                  has_edge=has_edge)
        seeds = _rows(i, block_s)
        q = q_ref[seeds, :].astype(jnp.float32) * scale
        o_ref[seeds, :] = _attend_block(q, kv[:, :hdp], kv[:, hdp:], valid,
                                        seg, masks).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, nblk, per_block, 0)


def fused_temporal_layer_kernel(
    q, k_table, v_table, seeds, seed_times, buf, *,
    time_w=None, time_b=None, wt_k=None, wt_v=None,
    edge_feats=None, we_k=None, we_v=None,
    block_s: int | None = None, scale: float | None = None,
    interpret: bool = False,
):
    """Fused neighbor-gather + bias-fold + attention over the packed buffer.

    q: (S, H, D) seed queries; k_table, v_table: (N, H, D) node-level
    projected keys/values (stay in HBM); seeds/seed_times: (S,) int32;
    buf: (Nb, K, 3) packed circular buffer (channels = neighbor id, time,
    edge id; -1 id = empty slot) — ``DeviceRecencySampler.state["buf"]``.
    Seeds may be negative (hop-2 frontier padding): those rows produce zero
    output.

    Optional bias folds (both on or both off per group):
      time_w/time_b: (d_time,) Bochner parameters, wt_k/wt_v:
        (d_time, H*D) time-encoding slices of the key/value projections;
      edge_feats: (E, d_edge) edge-feature storage (stays in HBM), we_k /
        we_v: (d_edge, H*D) edge-feature slices of the projections.

    ``block_s`` overrides the seeds per block (tests); by default it is
    derived from the widths and the staging budget (``_plan``).

    Returns (S, H, D). The gathered k/v exist only as 2-slot (B*K, 2*H*D)
    VMEM scratch, never in HBM; per-block DMAs are double-buffered.
    """
    has_time = wt_k is not None
    has_edge = we_k is not None
    lead, rest, m = _layer_inputs(q, k_table, v_table, seeds, seed_times,
                                  buf, time_w, time_b, wt_k, wt_v,
                                  edge_feats, we_k, we_v, block_s,
                                  "kernel/attention_forward")
    S, H, D, HDp = m["S"], m["H"], m["D"], m["HDp"]
    scratch, sems = _staging_scratch(m, has_edge)
    operands, in_specs = zip(*(lead + rest))
    out = pl.pallas_call(
        functools.partial(
            _fused_layer_kernel, scale=1.0 / np.sqrt(D) if scale is None
            else scale, block_s=m["block_s"], nblk=m["nblk"], kbuf=m["K"],
            heads=H, hdim=D, has_time=has_time, has_edge=has_edge,
        ),
        grid=(m["ns"],),
        in_specs=list(in_specs),
        out_specs=pl.BlockSpec((m["tile"], HDp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S + m["pad"], HDp), q.dtype),
        scratch_shapes=scratch + sems,
        interpret=interpret,
    )(*operands)
    return out[:S, :H * D].reshape(S, H, D)


def _scatter_rows(i, idx_ref, dkv_rows, dkv_hbm, cell, sem, *, bs: int,
                  kbuf: int):
    """Add block i's dk | dv rows into the aliased table gradient.

    One DMA read-modify-write per valid slot (the TPU has no atomics):
    read the row, add, write it back, each finished before the next row
    starts. With the sequential grid, every update reaches HBM exactly,
    duplicate ids within a block and across blocks included.
    """
    def rmw(j, r, kk, c):
        row = idx_ref[j, kk]

        @pl.when(row >= 0)
        def _():
            read = pltpu.make_async_copy(dkv_hbm.at[row], cell, sem)
            read.start()
            read.wait()
            cell[...] = cell[...] + dkv_rows[pl.ds(r, 1), :]
            write = pltpu.make_async_copy(cell, dkv_hbm.at[row], sem)
            write.start()
            write.wait()

        return c

    _for_slots(i, bs, kbuf, rmw)


def _fused_layer_bwd_kernel(idx_ref, cols_ref, q_ref, g_ref, *refs,
                            scale: float, block_s: int, nblk: int, kbuf: int,
                            heads: int, hdim: int, has_time: bool,
                            has_edge: bool):
    """Flash-style backward body: restage, recompute attention, accumulate.

    Per block, the neighborhoods are re-staged through the same
    double-buffered DMA pipeline as the forward, the biased k/v and
    attention weights are recomputed in VMEM, and the chain rule is
    applied per head on the block's (B*K, ·) rows:

      dv   = p ⊗ g              ds = p * (dp - Σ_k p·dp)     dp = g · v
      dq   = (ds · k) * scale   dk = ds ⊗ (q * scale)

    dq writes to a blocked output; the dk | dv rows are scattered into the
    zero-initialized ANY-space table gradient by ``_scatter_rows``; the
    weight gradients (time/edge projection slices and Bochner parameters)
    live in VMEM-resident accumulator outputs initialized at program 0,
    each updated by one matmul or reduction per block.
    """
    it = iter(refs)
    t = _unpack_tables(it, has_time, has_edge)
    next(it)                             # zeros operand (aliased → dkv_hbm)
    dq_ref = next(it)                    # (tile, H*D) VMEM blocked output
    dkv_hbm = next(it)                   # (N, 1, 2*H*D) f32 ANY (aliased)
    dtw_ref = dtb_ref = dwt_ref = dwe_ref = None
    if has_time:
        dtw_ref = next(it)               # (1, d_time) resident accumulator
        dtb_ref = next(it)
        dwt_ref = next(it)               # (d_time, 2*H*D) resident
    if has_edge:
        dwe_ref = next(it)               # (d_edge, 2*H*D) resident
    kv_scr = next(it)                    # (2, B*K, 1, 2*H*D) VMEM
    e_scr = next(it) if has_edge else None
    sems = [next(it) for _ in range(2 if has_edge else 1)]
    counts = next(it)                    # SMEM copies issued per slot
    dkv_rows = next(it)                  # (B*K, 2*H*D) f32 — the block's
    cell = next(it)                      # (1, 2*H*D) f32 RMW cell
    sem = next(it)                       # DMA — the RMW's read and write

    hdp = q_ref.shape[-1]
    masks = _head_masks(heads, hdim, hdp)
    seg = _seed_onehot(block_s, kbuf)
    rows = block_s * kbuf

    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref in (dtw_ref, dtb_ref, dwt_ref, dwe_ref):
            if ref is not None:
                ref[...] = jnp.zeros_like(ref)

    stage, wait = _make_stager(idx_ref, t, kv_scr, e_scr, sems, counts,
                               bs=block_s, kbuf=kbuf, has_edge=has_edge)
    stage(0)

    def per_block(i, carry):
        @pl.when(i + 1 < nblk)
        def _():
            stage(i + 1)

        wait(i)
        valid, kv, phi, theta, dt, e = _block_kv(
            i, cols_ref, t, kv_scr, e_scr, rows=rows, has_time=has_time,
            has_edge=has_edge)
        k, v = kv[:, :hdp], kv[:, hdp:]
        seeds = _rows(i, block_s)
        q_rows = _mm(seg, q_ref[seeds, :].astype(jnp.float32) * scale)
        g_rows = _mm(seg, g_ref[seeds, :].astype(jnp.float32))

        probs = _attention_probs(k, q_rows, valid, seg, masks)
        ds = []
        for p, m in zip(probs, masks):
            dp = jnp.sum(v * (g_rows * m), axis=1, keepdims=True)
            ds.append(p * (dp - _per_slot(seg, _per_seed(seg, p * dp))))
        ds = _spread(ds, masks)                                  # (B*K, HD)
        dq_ref[seeds, :] = (_mm(seg, ds * k, ((0,), (0,))) * scale
                            ).astype(dq_ref.dtype)

        # p is exactly 0 on masked slots (exp underflows at -1e30), but the
        # explicit zeroing keeps padding rows provably inert.
        dkv_rows[:, :hdp] = ds * q_rows * valid                  # ds ⊗ q
        dkv_rows[:, hdp:] = _spread(probs, masks) * g_rows * valid  # p ⊗ g
        dkv = dkv_rows[...]

        if has_time:
            dwt_ref[...] += _mm(phi, dkv, ((0,), (0,)))
            dphi = _mm(dkv, t["wt"][...], ((1,), (1,)))          # (B*K, dt)
            dtheta = -jnp.sin(theta) * dphi
            dtw_ref[...] += jnp.sum(dtheta * dt, axis=0, keepdims=True)
            dtb_ref[...] += jnp.sum(dtheta, axis=0, keepdims=True)
        if has_edge:
            dwe_ref[...] += _mm(e, dkv, ((0,), (0,)))  # e already eid-zeroed

        _scatter_rows(i, idx_ref, dkv_rows, dkv_hbm, cell, sem, bs=block_s,
                      kbuf=kbuf)
        return carry

    jax.lax.fori_loop(0, nblk, per_block, 0)


def fused_temporal_layer_bwd_kernel(
    g, q, k_table, v_table, seeds, seed_times, buf, *,
    time_w=None, time_b=None, wt_k=None, wt_v=None,
    edge_feats=None, we_k=None, we_v=None,
    block_s: int | None = None, scale: float | None = None,
    interpret: bool = False,
):
    """Backward pass of ``fused_temporal_layer_kernel``, gather-free in HBM.

    g: (S, H, D) cotangent of the forward output; remaining arguments as in
    the forward. Returns a dict of f32 gradients in the kernel's internal
    layout — ``q`` (S, H*D), ``k_table``/``v_table`` (N, H*D), and, when
    the bias groups are present, ``time_w``/``time_b`` (1, d_time) and
    ``wt_k``/``wt_v``/``we_k``/``we_v`` (d, H*D) — the caller
    (``ops._fused_layer_bwd``) reshapes/casts them back to the primal
    shapes. ``edge_feats``, ``seeds``, ``seed_times`` and ``buf`` are
    non-differentiable.

    The grid is declared sequential ("arbitrary") so the per-row DMA
    read-modify-write scatter into the table gradient is race-free.
    """
    has_time = wt_k is not None
    has_edge = we_k is not None
    lead, rest, m = _layer_inputs(q, k_table, v_table, seeds, seed_times,
                                  buf, time_w, time_b, wt_k, wt_v,
                                  edge_feats, we_k, we_v, block_s,
                                  "kernel/attention_backward")
    S, H, D, HDp = m["S"], m["H"], m["D"], m["HDp"]
    HD, N = H * D, k_table.shape[0]
    blocked = pl.BlockSpec((m["tile"], HDp), lambda i: (i, 0))
    g2 = _pad2(g.reshape(S, HD), S + m["pad"], HDp)
    # A zero operand aliased to the k | v table gradient: the kernel
    # accumulates into it by DMA read-modify-write.
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands, in_specs = zip(*(lead + [(g2, blocked)] + rest + [
        (jnp.zeros((N, 1, 2 * HDp), jnp.float32), hbm)]))

    names = ["q", "kv"]
    out_shape = [
        jax.ShapeDtypeStruct((S + m["pad"], HDp), jnp.float32),
        jax.ShapeDtypeStruct((N, 1, 2 * HDp), jnp.float32),
    ]
    out_specs = [blocked, hbm]
    resident = lambda shp: pl.BlockSpec(shp, lambda i: (0, 0))  # noqa: E731
    groups = []
    if has_time:
        d_time = time_w.size
        groups += [("time_w", (1, d_time)), ("time_b", (1, d_time)),
                   ("wt", (d_time, 2 * HDp))]
    if has_edge:
        groups += [("we", (m["DEp"], 2 * HDp))]
    for name, shp in groups:
        names.append(name)
        out_shape.append(jax.ShapeDtypeStruct(shp, jnp.float32))
        out_specs.append(resident(shp))

    scratch, sems = _staging_scratch(m, has_edge)
    scratch += sems + [
        pltpu.VMEM((m["block_s"] * m["K"], 2 * HDp), jnp.float32),  # dkv_rows
        pltpu.VMEM((1, 2 * HDp), jnp.float32),                      # cell
        pltpu.SemaphoreType.DMA,                                    # sem
    ]
    outs = pl.pallas_call(
        functools.partial(
            _fused_layer_bwd_kernel, scale=1.0 / np.sqrt(D) if scale is None
            else scale, block_s=m["block_s"], nblk=m["nblk"], kbuf=m["K"],
            heads=H, hdim=D, has_time=has_time, has_edge=has_edge,
        ),
        grid=(m["ns"],),
        in_specs=list(in_specs),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases={len(operands) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    raw = dict(zip(names, outs))
    kv = raw.pop("kv").reshape(N, 2 * HDp)
    grads = {"q": raw.pop("q")[:S, :HD], "k_table": kv[:, :HD],
             "v_table": kv[:, HDp:HDp + HD]}
    if has_time:
        grads.update(time_w=raw["time_w"], time_b=raw["time_b"],
                     wt_k=raw["wt"][:, :HD], wt_v=raw["wt"][:, HDp:HDp + HD])
    if has_edge:
        d_edge = edge_feats.shape[1]
        grads.update(we_k=raw["we"][:d_edge, :HD],
                     we_v=raw["we"][:d_edge, HDp:HDp + HD])
    return grads


def fused_recency_attention_kernel(q, k_table, v_table, seeds, buf_ids, *,
                                   block_s: int | None = None,
                                   scale: float | None = None,
                                   interpret: bool = False):
    """Fused neighbor-gather + attention over the resident recency buffer.

    q: (S, H, D) seed queries; k_table, v_table: (N, H, D) node-level
    projected keys/values (stay in HBM); seeds: (S,) int32 node ids;
    buf_ids: (Nb, K) int32 circular-buffer neighbor ids (-1 = empty, rows
    indexed by node id — ``DeviceRecencySampler.buffer_ids``).
    Returns (S, H, D).

    Thin wrapper over ``fused_temporal_layer_kernel`` with the time/edge
    bias folds disabled (ids-only buffer): same blocked, double-buffered
    DMA body, no (S, K, H, D) HBM intermediate.
    """
    buf_ids = buf_ids.astype(jnp.int32)
    buf = jnp.stack(
        [buf_ids, jnp.zeros_like(buf_ids), jnp.full_like(buf_ids, -1)],
        axis=-1,
    )
    return fused_temporal_layer_kernel(
        q, k_table, v_table, seeds, None, buf,
        block_s=block_s, scale=scale, interpret=interpret,
    )
