"""Temporal neighbor attention Pallas TPU kernels.

The paper's profiling (Table 11) puts TGAT attention + sampling at ~28% of
epoch time. On TPU the hot loop is: for each seed node, attend its K most
recent neighbors (K = 10..32, padded). See ``docs/kernels.md`` for the full
memory-space layout and parity-testing story.

Layout. Every kernel works on 2-D, head-flattened rows: a seed's query is
one ``(1, H*D)`` row and its neighborhood one ``(K, H*D)`` slab, with K on
sublanes and the heads side by side on lanes. The wrappers reshape the
public ``(S, H, D)`` / ``(N, H, D)`` arrays to ``(S, H*D)`` / ``(N, H*D)``;
the fused kernels also pad H*D to a multiple of 128 lanes (zeros no head
reads), because a single-row DMA must move whole lane tiles. Attention
runs on the VPU:
per head, the scores are a lane reduction of ``k * (q * head_mask)`` and
the output a sublane reduction of ``p * v`` — exact f32 and free of the
batched contractions without a free lhs dimension (``"hd,khd->hk"``) that
Mosaic cannot lower. The only MXU work is the plain 2-D bias matmuls,
pinned to ``Precision.HIGHEST``.

``temporal_attention_kernel`` is the un-fused baseline: it consumes
pre-gathered ``(S, K, H, D)`` k/v tensors, tiles seeds into VMEM blocks and
walks the block's seeds with the same per-seed attention as the fused path.

Grid: (num_seed_blocks,) — embarrassingly parallel over seeds.
Blocks (VMEM):
  q:    (block_s, H*D)
  k/v:  (block_s, K, H*D)   — gathered neighbor features
  mask: (block_s, K, 1)
  o:    (block_s, H*D)

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

so the fat ``(S, K, H, D)`` intermediates never exist in HBM. XLA gathers
each seed's packed buffer row (ids, times, eids: an ``(S, K, 3)`` int32
index block) up front; its clamped ids reach SMEM as DMA source indices
and its validity / time-delta columns reach VMEM, blocked with the seeds.
Each neighbor's table/edge-feature row is then DMA'd from HBM into VMEM
scratch. Seeds may be negative (hop-2 frontier padding): the DMA index is
clamped and the whole row masked out, so the 2-hop TGAT frontier can run
through the kernel unclamped.

Per-seed DMAs are double-buffered: while seed ``j``'s neighborhood is being
reduced on the VPU/MXU, seed ``j+1``'s K neighbor-row copies (issued
back-to-back, all in flight at once) land in the other half of a 2-slot
scratch. ``fused_recency_attention_kernel`` (ids-only buffer, no bias
folding) is kept as a thin wrapper and runs through
the same double-buffered body.

``fused_temporal_layer_bwd_kernel`` is the flash-attention-style backward:
it re-stages every seed's neighborhood through the same double-buffered DMA
pipeline, recomputes the attention weights in VMEM, and produces all input
gradients without ever materializing an (S, K, ·) tensor in HBM — dq as a
blocked output, dk_table/dv_table by sequential per-row DMA
read-modify-write into ANY-space outputs aliased to zero-initialized
operands (the TPU has no atomics; the grid is sequential, so the
read-modify-write is race-free and handles duplicate neighbor ids exactly),
and the small weight gradients (time/edge projections, Bochner parameters)
as VMEM-resident accumulators that live across the whole grid.

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

NEG_INF = -1e30
_HI = jax.lax.Precision.HIGHEST


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


def _masked_softmax(s, valid):
    """Softmax over the K sublanes of an (K, 1) score column, with invalid
    slots excluded and a fully-invalid column zeroed — identical to the
    oracle's ``softmax`` + ``where(mask.any(), ·, 0)``."""
    s = jnp.where(valid > 0, s, NEG_INF)
    p = jnp.exp(s - s.max(axis=0, keepdims=True))
    p = p / jnp.maximum(p.sum(axis=0, keepdims=True), 1e-30)
    return p * valid.max(axis=0, keepdims=True)


def _attend(q, k, v, valid, masks):
    """One seed's multi-head attention on the VPU.

    q: (1, H*D) scaled query; k, v: (K, H*D); valid: (K, 1) f32 in {0, 1};
    masks: ``_head_masks``. Returns the (1, H*D) head-concatenated output.
    """
    out = jnp.zeros(q.shape, jnp.float32)
    for m in masks:
        p = _masked_softmax(jnp.sum(k * (q * m), axis=1, keepdims=True),
                            valid)
        out = out + jnp.sum(p * v, axis=0, keepdims=True) * m
    return out


def _temporal_attention_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *,
                               scale: float, block_s: int, heads: int,
                               hdim: int):
    masks = _head_masks(heads, hdim, heads * hdim)

    def per_seed(j, carry):
        q = q_ref[pl.ds(j, 1), :].astype(jnp.float32) * scale   # (1, H*D)
        o = _attend(q, k_ref[j].astype(jnp.float32),
                    v_ref[j].astype(jnp.float32), mask_ref[j], masks)
        o_ref[pl.ds(j, 1), :] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_s, per_seed, 0)


def temporal_attention_kernel(q, k, v, mask, *, block_s: int = 128,
                              scale: float | None = None,
                              interpret: bool = False):
    """q: (S, H, D); k, v: (S, K, H, D); mask: (S, K) -> (S, H, D)."""
    S, H, D = q.shape
    K = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    block_s = min(block_s, S)
    pad = (-S) % block_s
    q2 = jnp.pad(q.reshape(S, H * D), ((0, pad), (0, 0)))
    k2 = jnp.pad(k.reshape(S, K, H * D), ((0, pad), (0, 0), (0, 0)))
    v2 = jnp.pad(v.reshape(S, K, H * D), ((0, pad), (0, 0), (0, 0)))
    m2 = jnp.pad(mask.astype(jnp.float32)[..., None],
                 ((0, pad), (0, 0), (0, 0)))
    ns = (S + pad) // block_s

    out = pl.pallas_call(
        functools.partial(_temporal_attention_kernel, scale=scale,
                          block_s=block_s, heads=H, hdim=D),
        grid=(ns,),
        in_specs=[
            pl.BlockSpec((block_s, H * D), lambda i: (i, 0)),
            pl.BlockSpec((block_s, K, H * D), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_s, K, H * D), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_s, K, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, H * D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S + pad, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(q2, k2, v2, m2)
    return out[:S].reshape(S, H, D)


_LANE = 128


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


def _layer_inputs(q, k_table, v_table, seeds, seed_times, buf, time_w,
                  time_b, wt_k, wt_v, edge_feats, we_k, we_v, block_s):
    """Assemble the fused forward/backward pallas_call inputs.

    XLA gathers each seed's packed buffer row up front — an (S, K, 3)
    int32 index block, not the fat (S, K, H, D) features — and splits it
    into the DMA indices (``idx``: (S, 2K) clamped neighbor and edge ids,
    blocked into SMEM) and the vector columns (``cols``: (S, 3, K) f32 slot
    validity, query/neighbor time delta and edge validity, blocked into
    VMEM). The node tables and the edge-feature storage become
    ``_row_table``s in ANY/HBM, lane-padded to multiples of 128; q and
    the weight groups are lane-padded to the same H*D width with zeros,
    which the attention never reads (the head masks cover the real lanes).

    Returns ``(lead, rest, meta)``: the blocked seed-axis operands
    ``[idx, cols, q]`` and the grid-invariant ``[k, v, time group, edge
    group]``, each a list of ``(operand, BlockSpec)``, and the static
    sizes the wrappers need.
    """
    S, H, D = q.shape
    HD = H * D
    HDp = _round_up(HD)
    K = buf.shape[1]
    block_s = min(block_s, S)
    pad = (-S) % block_s

    seeds = seeds.astype(jnp.int32)
    seed_times = (jnp.zeros_like(seeds) if seed_times is None
                  else seed_times.astype(jnp.int32))
    rows = buf.astype(jnp.int32)[jnp.maximum(seeds, 0)]         # (S, K, 3)
    ids, eids = rows[..., 0], rows[..., 2]
    valid = (ids >= 0) & (seeds >= 0)[:, None]  # seed < 0: hop-2 padding
    dt = seed_times[:, None] - rows[..., 1]   # int32 first, as time_encode
    cols = jnp.stack([valid, dt, eids >= 0], axis=1).astype(jnp.float32)
    idx = jnp.concatenate([jnp.maximum(ids, 0), jnp.maximum(eids, 0)], 1)

    blocked = lambda shp: pl.BlockSpec(  # noqa: E731
        shp, lambda i: (i,) + (0,) * (len(shp) - 1))
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)  # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    lead = [
        (jnp.pad(idx, ((0, pad), (0, 0))),
         pl.BlockSpec((block_s, 2 * K), lambda i: (i, 0),
                      memory_space=pltpu.SMEM)),
        (jnp.pad(cols, ((0, pad), (0, 0), (0, 0))), blocked((block_s, 3, K))),
        (_pad2(q.reshape(S, HD), S + pad, HDp), blocked((block_s, HDp))),
    ]
    rest = [(_row_table(t.reshape(t.shape[0], HD), HDp), hbm)
            for t in (k_table, v_table)]
    if wt_k is not None:
        d_time = wt_k.shape[0]
        tw = time_w.reshape(1, -1).astype(jnp.float32)
        tb = time_b.reshape(1, -1).astype(jnp.float32)
        wtk, wtv = (_pad2(w.reshape(d_time, HD).astype(jnp.float32),
                          d_time, HDp) for w in (wt_k, wt_v))
        rest += [(a, full(a)) for a in (tw, tb, wtk, wtv)]
    DEp = 0
    if we_k is not None:
        d_edge = edge_feats.shape[1]
        DEp = _round_up(d_edge)
        wek, wev = (_pad2(w.reshape(d_edge, HD).astype(jnp.float32),
                          DEp, HDp) for w in (we_k, we_v))
        rest += [(_row_table(edge_feats, DEp), hbm), (wek, full(wek)),
                 (wev, full(wev))]
    meta = dict(S=S, pad=pad, K=K, H=H, D=D, HDp=HDp, DEp=DEp,
                block_s=block_s, ns=(S + pad) // block_s,
                kv_dtype=k_table.dtype,
                e_dtype=None if we_k is None else edge_feats.dtype)
    return lead, rest, meta


def _staging_scratch(meta, has_edge):
    """2-slot VMEM landing zones for the neighbor rows, and their per-slot
    DMA semaphores (k, v[, edge]). Each row lands in its own (1, width)
    tile, so a single-row DMA never slices a sublane tile (wider than 128
    lanes, a (K, width) slab would be tiled (8, 128) and refuse it)."""
    K, HDp = meta["K"], meta["HDp"]
    scratch = [pltpu.VMEM((2, K, 1, HDp), meta["kv_dtype"]),
               pltpu.VMEM((2, K, 1, HDp), meta["kv_dtype"])]
    if has_edge:
        scratch.append(pltpu.VMEM((2, K, 1, meta["DEp"]), meta["e_dtype"]))
    return scratch, [pltpu.SemaphoreType.DMA((2,))] * (3 if has_edge else 2)


def _unpack_tables(it, has_time: bool, has_edge: bool) -> dict:
    """Pop the grid-invariant refs in ``_layer_inputs``' ``rest`` order."""
    t = {"k": next(it), "v": next(it)}      # (N, 1, H*D) ANY node tables
    if has_time:
        t.update(tw=next(it), tb=next(it),  # (1, d_time) Bochner params
                 wtk=next(it), wtv=next(it))  # (d_time, H*D) time proj
    if has_edge:
        t.update(ef=next(it),               # (E, 1, d_edge) ANY features
                 wek=next(it), wev=next(it))  # (d_edge, H*D) edge proj
    return t


def _make_stager(idx_ref, t, k_scr, v_scr, e_scr, sems, *, kbuf: int,
                 has_edge: bool):
    """Build the double-buffered per-seed DMA staging closures.

    Shared by the forward and backward fused-layer kernel bodies: both walk
    the same seed blocks and need the same staged data (the K neighbor k/v
    table rows, and optionally the K edge-feature rows) in 2-slot scratch.
    ``idx_ref`` holds the block's clamped neighbor ids (columns ``[0, K)``)
    and edge ids (``[K, 2K)``) in SMEM, so every source index is known
    before any copy starts; padding slots copy row 0 and are masked out by
    the caller.

    Returns ``(stage, wait)``: ``stage(j)`` issues seed j's K row copies
    back-to-back into slot ``j % 2``, all in flight at once; ``wait(j)``
    blocks until they have all landed.
    """

    def copies(j, kk):
        sl = j % 2
        cps = [
            pltpu.make_async_copy(t["k"].at[idx_ref[j, kk]],
                                  k_scr.at[sl, kk], sems[0].at[sl]),
            pltpu.make_async_copy(t["v"].at[idx_ref[j, kk]],
                                  v_scr.at[sl, kk], sems[1].at[sl]),
        ]
        if has_edge:
            cps.append(pltpu.make_async_copy(
                t["ef"].at[idx_ref[j, kbuf + kk]], e_scr.at[sl, kk],
                sems[2].at[sl]))
        return cps

    def for_each_copy(j, action):
        def one(kk, c):
            for cp in copies(j, kk):
                action(cp)
            return c

        jax.lax.fori_loop(0, kbuf, one, 0)

    def stage(j):
        for_each_copy(j, lambda cp: cp.start())

    def wait(j):
        for_each_copy(j, lambda cp: cp.wait())

    return stage, wait


def _seed_kv(j, cols_ref, t, k_scr, v_scr, e_scr, *, has_time: bool,
             has_edge: bool):
    """Rebuild seed j's biased (K, H*D) keys/values from staged scratch.

    Shared by the forward (to attend) and the backward (to recompute the
    attention weights flash-style). Returns ``(valid, k, v, phi, theta,
    dt, e)``: the (K, 1) slot validity, the keys/values, the Bochner
    encoding ``phi = cos(theta)`` of the (K, 1) query/neighbor time deltas
    ``dt``, and the zeroed edge-feature rows ``e`` (the backward reuses the
    last four for the weight gradients).
    """
    sl = j % 2
    c = cols_ref[j].T                   # (K, 3): valid, dt, edge valid
    valid = c[:, 0:1]
    slab = lambda scr: scr[sl].reshape(scr.shape[1], scr.shape[3]  # noqa: E731
                                       ).astype(jnp.float32)
    k = slab(k_scr)
    v = slab(v_scr)
    phi = theta = dt = e = None
    if has_time:
        # The Bochner encoding phi = cos(dt * w + b) on the VPU, then the
        # (K, d_time) @ (d_time, H*D) bias matmul on the MXU.
        dt = c[:, 1:2]
        theta = dt * t["tw"][...] + t["tb"][...]
        phi = jnp.cos(theta)
        k = k + _mm(phi, t["wtk"][...])
        v = v + _mm(phi, t["wtv"][...])
    if has_edge:
        e = slab(e_scr) * c[:, 2:3]     # zero featureless slots
        k = k + _mm(e, t["wek"][...])
        v = v + _mm(e, t["wev"][...])
    return valid, k, v, phi, theta, dt, e


def _fused_layer_kernel(idx_ref, cols_ref, q_ref, *refs, scale: float,
                        block_s: int, kbuf: int, heads: int, hdim: int,
                        has_time: bool, has_edge: bool):
    """Double-buffered fused gather + bias-fold + attention body.

    ``idx_ref`` (bs, 2K) SMEM DMA indices, ``cols_ref`` (bs, 3, K) VMEM
    slot columns and ``q_ref`` (bs, H*D) VMEM queries are this block's
    seeds; ``refs`` unpacks (in order) the grid-invariant tables and
    weights (``_unpack_tables``), the (bs, H*D) output and the staging
    scratch from ``_staging_scratch``.
    """
    it = iter(refs)
    t = _unpack_tables(it, has_time, has_edge)
    o_ref = next(it)
    k_scr, v_scr = next(it), next(it)       # (2, K, 1, H*D) VMEM
    e_scr = next(it) if has_edge else None  # (2, K, 1, d_edge) VMEM
    sems = list(it)

    masks = _head_masks(heads, hdim, q_ref.shape[-1])
    stage, wait = _make_stager(idx_ref, t, k_scr, v_scr, e_scr, sems,
                               kbuf=kbuf, has_edge=has_edge)

    # Prologue: stage seed 0; the loop then overlaps seed j+1's copies with
    # seed j's compute (classic 2-slot software pipeline).
    stage(0)

    def per_seed(j, carry):
        @pl.when(j + 1 < block_s)
        def _():
            stage(j + 1)

        wait(j)
        valid, k, v, *_ = _seed_kv(j, cols_ref, t, k_scr, v_scr, e_scr,
                                   has_time=has_time, has_edge=has_edge)
        q = q_ref[pl.ds(j, 1), :].astype(jnp.float32) * scale
        o_ref[pl.ds(j, 1), :] = _attend(q, k, v, valid, masks
                                        ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_s, per_seed, 0)


def fused_temporal_layer_kernel(
    q, k_table, v_table, seeds, seed_times, buf, *,
    time_w=None, time_b=None, wt_k=None, wt_v=None,
    edge_feats=None, we_k=None, we_v=None,
    block_s: int = 128, scale: float | None = None,
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

    Returns (S, H, D). The (S, K, H, D) gathered k/v exist only as 2-slot
    (K, H*D) VMEM scratch, never in HBM; per-seed DMAs are double-buffered.
    """
    has_time = wt_k is not None
    has_edge = we_k is not None
    lead, rest, m = _layer_inputs(q, k_table, v_table, seeds, seed_times,
                                  buf, time_w, time_b, wt_k, wt_v,
                                  edge_feats, we_k, we_v, block_s)
    S, H, D, HDp, bs = m["S"], m["H"], m["D"], m["HDp"], m["block_s"]
    scratch, sems = _staging_scratch(m, has_edge)
    operands, in_specs = zip(*(lead + rest))
    out = pl.pallas_call(
        functools.partial(
            _fused_layer_kernel, scale=1.0 / np.sqrt(D) if scale is None
            else scale, block_s=bs, kbuf=m["K"], heads=H, hdim=D,
            has_time=has_time, has_edge=has_edge,
        ),
        grid=(m["ns"],),
        in_specs=list(in_specs),
        out_specs=pl.BlockSpec((bs, HDp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S + m["pad"], HDp), q.dtype),
        scratch_shapes=scratch + sems,
        interpret=interpret,
    )(*operands)
    return out[:S, :H * D].reshape(S, H, D)


def _fused_layer_bwd_kernel(idx_ref, cols_ref, q_ref, g_ref, *refs,
                            scale: float, block_s: int, kbuf: int,
                            heads: int, hdim: int, has_time: bool,
                            has_edge: bool):
    """Flash-style backward body: restage, recompute attention, accumulate.

    Per seed, the neighborhood is re-staged through the same double-buffered
    DMA pipeline as the forward, the biased k/v and attention weights are
    recomputed in VMEM, and the chain rule is applied locally per head:

      dv   = p ⊗ g              ds = p * (dp - Σ_k p·dp)     dp = g · v
      dq   = (ds · k) * scale   dk = ds ⊗ (q * scale)

    dq writes to a blocked output; dk/dv rows are scattered into the
    zero-initialized ANY-space dk_table/dv_table outputs by sequential DMA
    read-modify-write (grid + fori_loop ordering makes duplicate neighbor
    ids safe without atomics); the weight gradients (time/edge projection
    slices and Bochner parameters) live in VMEM-resident accumulator outputs
    initialized at program 0.
    """
    it = iter(refs)
    t = _unpack_tables(it, has_time, has_edge)
    next(it)                             # dk zeros operand (aliased → dk_hbm)
    next(it)                             # dv zeros operand (aliased → dv_hbm)
    dq_ref = next(it)                    # (bs, H*D) VMEM blocked output
    dk_hbm = next(it)                    # (N, 1, H*D) f32 ANY output (aliased)
    dv_hbm = next(it)                    # (N, 1, H*D) f32 ANY output (aliased)
    dtw_ref = dtb_ref = dwtk_ref = dwtv_ref = None
    dwek_ref = dwev_ref = None
    if has_time:
        dtw_ref = next(it)               # (1, d_time) resident accumulator
        dtb_ref = next(it)
        dwtk_ref = next(it)              # (d_time, H*D) resident accumulator
        dwtv_ref = next(it)
    if has_edge:
        dwek_ref = next(it)              # (d_edge, H*D) resident accumulator
        dwev_ref = next(it)
    k_scr, v_scr = next(it), next(it)    # (2, K, 1, H*D) VMEM
    e_scr = next(it) if has_edge else None
    dk_rows = next(it)                   # (K, H*D) f32 — this seed's dk
    dv_rows = next(it)                   # (K, H*D) f32
    rk_row = next(it)                    # (1, H*D) f32 read-modify-write cell
    rv_row = next(it)                    # (1, H*D) f32
    sem_rk = next(it)                    # DMA — dk row read-modify-write
    sem_rv = next(it)
    sems = list(it)                      # staging semaphores

    masks = _head_masks(heads, hdim, q_ref.shape[-1])

    @pl.when(pl.program_id(0) == 0)
    def _():
        if has_time:
            dtw_ref[...] = jnp.zeros_like(dtw_ref)
            dtb_ref[...] = jnp.zeros_like(dtb_ref)
            dwtk_ref[...] = jnp.zeros_like(dwtk_ref)
            dwtv_ref[...] = jnp.zeros_like(dwtv_ref)
        if has_edge:
            dwek_ref[...] = jnp.zeros_like(dwek_ref)
            dwev_ref[...] = jnp.zeros_like(dwev_ref)

    stage, wait = _make_stager(idx_ref, t, k_scr, v_scr, e_scr, sems,
                               kbuf=kbuf, has_edge=has_edge)
    stage(0)

    def per_seed(j, carry):
        @pl.when(j + 1 < block_s)
        def _():
            stage(j + 1)

        wait(j)
        valid, k, v, phi, theta, dt, e = _seed_kv(
            j, cols_ref, t, k_scr, v_scr, e_scr,
            has_time=has_time, has_edge=has_edge)
        qs = q_ref[pl.ds(j, 1), :].astype(jnp.float32) * scale   # (1, H*D)
        g = g_ref[pl.ds(j, 1), :].astype(jnp.float32)            # (1, H*D)

        dq = jnp.zeros(qs.shape, jnp.float32)
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        for m in masks:
            qm, gm = qs * m, g * m
            p = _masked_softmax(jnp.sum(k * qm, axis=1, keepdims=True),
                                valid)                           # (K, 1)
            dp = jnp.sum(v * gm, axis=1, keepdims=True)          # (K, 1)
            ds = p * (dp - jnp.sum(p * dp, axis=0, keepdims=True))
            dq = dq + jnp.sum(ds * k, axis=0, keepdims=True) * m
            dk = dk + ds * qm                                    # ds ⊗ q
            dv = dv + p * gm                                     # p ⊗ g
        dq_ref[pl.ds(j, 1), :] = (dq * scale).astype(dq_ref.dtype)

        # p is exactly 0 on masked slots (exp underflows at -1e30), but the
        # explicit zeroing keeps clamped padding rows provably inert.
        dk = dk * valid                                          # (K, H*D)
        dv = dv * valid

        if has_time:
            dwtk_ref[...] += _mm(phi, dk, ((0,), (0,)))
            dwtv_ref[...] += _mm(phi, dv, ((0,), (0,)))
            dphi = (_mm(dk, t["wtk"][...], ((1,), (1,)))
                    + _mm(dv, t["wtv"][...], ((1,), (1,))))
            dtheta = -jnp.sin(theta) * dphi                      # (K, d_time)
            dtw_ref[...] += jnp.sum(dtheta * dt, axis=0, keepdims=True)
            dtb_ref[...] += jnp.sum(dtheta, axis=0, keepdims=True)
        if has_edge:
            dwek_ref[...] += _mm(e, dk, ((0,), (0,)))  # e already eid-zeroed
            dwev_ref[...] += _mm(e, dv, ((0,), (0,)))

        # Scatter this seed's dk/dv rows into the table gradients: one
        # sequential read-modify-write per slot (no TPU atomics; duplicate
        # ids within a row accumulate correctly because each RMW completes
        # before the next starts).
        dk_rows[...] = dk
        dv_rows[...] = dv

        def rmw(kk, c):
            nid = idx_ref[j, kk]
            in_k = pltpu.make_async_copy(dk_hbm.at[nid], rk_row, sem_rk)
            in_v = pltpu.make_async_copy(dv_hbm.at[nid], rv_row, sem_rv)
            in_k.start()
            in_v.start()
            in_k.wait()
            in_v.wait()
            rk_row[...] = rk_row[...] + dk_rows[pl.ds(kk, 1), :]
            rv_row[...] = rv_row[...] + dv_rows[pl.ds(kk, 1), :]
            out_k = pltpu.make_async_copy(rk_row, dk_hbm.at[nid], sem_rk)
            out_v = pltpu.make_async_copy(rv_row, dv_hbm.at[nid], sem_rv)
            out_k.start()
            out_v.start()
            out_k.wait()
            out_v.wait()
            return c

        jax.lax.fori_loop(0, kbuf, rmw, 0)
        return carry

    jax.lax.fori_loop(0, block_s, per_seed, 0)


def fused_temporal_layer_bwd_kernel(
    g, q, k_table, v_table, seeds, seed_times, buf, *,
    time_w=None, time_b=None, wt_k=None, wt_v=None,
    edge_feats=None, we_k=None, we_v=None,
    block_s: int = 128, scale: float | None = None,
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
    read-modify-write scatter into dk_table/dv_table is race-free.
    """
    has_time = wt_k is not None
    has_edge = we_k is not None
    lead, rest, m = _layer_inputs(q, k_table, v_table, seeds, seed_times,
                                  buf, time_w, time_b, wt_k, wt_v,
                                  edge_feats, we_k, we_v, block_s)
    S, H, D, HDp, bs = m["S"], m["H"], m["D"], m["HDp"], m["block_s"]
    HD, K, N = H * D, m["K"], k_table.shape[0]
    blocked = pl.BlockSpec((bs, HDp), lambda i: (i, 0))
    g2 = _pad2(g.reshape(S, HD), S + m["pad"], HDp)
    # Zero operands aliased to the table-gradient outputs: the kernel
    # accumulates into them by DMA read-modify-write.
    zeros = jnp.zeros((N, 1, HDp), jnp.float32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands, in_specs = zip(*(lead + [(g2, blocked)] + rest
                               + [(zeros, hbm), (zeros, hbm)]))
    alias_base = len(operands) - 2

    names = ["q", "k_table", "v_table"]
    out_shape = [
        jax.ShapeDtypeStruct((S + m["pad"], HDp), jnp.float32),
        jax.ShapeDtypeStruct((N, 1, HDp), jnp.float32),
        jax.ShapeDtypeStruct((N, 1, HDp), jnp.float32),
    ]
    out_specs = [blocked, hbm, hbm]
    resident = lambda shp: pl.BlockSpec(shp, lambda i: (0, 0))  # noqa: E731
    if has_time:
        d_time = time_w.size
        for name, shp in (("time_w", (1, d_time)), ("time_b", (1, d_time)),
                          ("wt_k", (d_time, HDp)), ("wt_v", (d_time, HDp))):
            names.append(name)
            out_shape.append(jax.ShapeDtypeStruct(shp, jnp.float32))
            out_specs.append(resident(shp))
    if has_edge:
        for name in ("we_k", "we_v"):
            names.append(name)
            out_shape.append(jax.ShapeDtypeStruct((m["DEp"], HDp),
                                                  jnp.float32))
            out_specs.append(resident((m["DEp"], HDp)))

    scratch, sems = _staging_scratch(m, has_edge)
    scratch += [
        pltpu.VMEM((K, HDp), jnp.float32),   # dk_rows
        pltpu.VMEM((K, HDp), jnp.float32),   # dv_rows
        pltpu.VMEM((1, HDp), jnp.float32),   # rk_row
        pltpu.VMEM((1, HDp), jnp.float32),   # rv_row
        pltpu.SemaphoreType.DMA,             # sem_rk
        pltpu.SemaphoreType.DMA,             # sem_rv
    ]
    outs = pl.pallas_call(
        functools.partial(
            _fused_layer_bwd_kernel, scale=1.0 / np.sqrt(D) if scale is None
            else scale, block_s=bs, kbuf=K, heads=H, hdim=D,
            has_time=has_time, has_edge=has_edge,
        ),
        grid=(m["ns"],),
        in_specs=list(in_specs),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch + sems,
        input_output_aliases={alias_base: 1, alias_base + 1: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    grads = dict(zip(names, outs))
    grads["q"] = grads["q"][:S, :HD]
    for name in ("k_table", "v_table"):
        grads[name] = grads[name].reshape(N, HDp)[:, :HD]
    for name in ("wt_k", "wt_v"):
        if name in grads:
            grads[name] = grads[name][:, :HD]
    for name in ("we_k", "we_v"):
        if name in grads:
            grads[name] = grads[name][:edge_feats.shape[1], :HD]
    return grads


def fused_recency_attention_kernel(q, k_table, v_table, seeds, buf_ids, *,
                                   block_s: int = 128,
                                   scale: float | None = None,
                                   interpret: bool = False):
    """Fused neighbor-gather + attention over the resident recency buffer.

    q: (S, H, D) seed queries; k_table, v_table: (N, H, D) node-level
    projected keys/values (stay in HBM); seeds: (S,) int32 node ids;
    buf_ids: (Nb, K) int32 circular-buffer neighbor ids (-1 = empty, rows
    indexed by node id — ``DeviceRecencySampler.buffer_ids``).
    Returns (S, H, D).

    Thin wrapper over ``fused_temporal_layer_kernel`` with the time/edge
    bias folds disabled (ids-only buffer): same double-buffered DMA body,
    no (S, K, H, D) HBM intermediate.
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
