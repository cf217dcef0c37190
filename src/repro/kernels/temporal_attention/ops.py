"""Public jit'd wrappers: Pallas kernels on TPU, jnp references elsewhere.

``temporal_attention``       — consumes pre-gathered (S, K, H, D) k/v.
``fused_recency_attention``  — device-sampling path (ids-only buffer):
                               consumes seed ids plus the resident recency
                               buffer and node-level k/v tables; the gather
                               happens inside the kernel (TPU) or via a take
                               in the reference (other backends), never as a
                               hook on the host.
``fused_temporal_layer``     — the full TGAT/TGN layer-1 compute for
                               ``device_sampling=True``: adds the in-kernel
                               time-encoding and edge-feature bias folds and
                               a custom VJP whose backward is itself a
                               Pallas kernel (flash-style recompute), so a
                               jitted, differentiated train step is
                               gather-free end to end.
``fused_temporal_layer_hop2``     — hop-2-aware variant: the (S, K) hop-1
                               frontier (padding ids = -1) queries the same
                               resident buffer at its interaction times.
``fused_temporal_layer_per_seed`` — per-seed-embedding-table variant: each
                               seed attends over its own K *computed* rows
                               (2-layer TGAT's final hop), expressed as a
                               synthetic (S, K, 3) buffer over an (S*K, H,
                               D) table so the same kernel family serves it.
``fused_temporal_layer_sharded``  — shard_map-aware variant for the 2-D
                               mesh: each node shard computes partial
                               attention from its local block of the
                               node-partitioned buffer and one psum over
                               the node axis assembles exact attention
                               (bit-parity with the single-device layer);
                               its custom VJP psums the operand cotangents
                               so sharded gradients stay exact too.

Every wrapper takes ``mode`` ∈ {"auto", "ref", "kernel", "interpret"}:
"auto" picks the Pallas kernel on TPU and the jnp reference elsewhere;
"interpret" forces the kernel body through the Pallas interpreter (the CPU
parity path used by ``tests/kernels/``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.temporal_attention.kernel import (
    fused_recency_attention_kernel,
    fused_temporal_layer_bwd_kernel,
    fused_temporal_layer_kernel,
    temporal_attention_kernel,
)
from repro.kernels.temporal_attention.ref import (
    fused_recency_attention_ref,
    fused_temporal_layer_ref,
    temporal_attention_ref,
)


def _use_kernel(mode: str) -> bool:
    """Resolve a dispatch mode string; raises on unknown modes."""
    if mode not in ("auto", "ref", "kernel", "interpret"):
        raise ValueError(f"unknown kernel dispatch mode {mode!r}")
    return (mode in ("kernel", "interpret")
            or (mode == "auto" and jax.default_backend() == "tpu"))


@partial(jax.jit, static_argnames=("block_s", "mode"))
def temporal_attention(q, k, v, mask, *, block_s: int = 32,
                       mode: str = "auto"):
    """q: (S, H, D); k, v: (S, K, H, D); mask: (S, K) -> (S, H, D)."""
    if _use_kernel(mode):
        return temporal_attention_kernel(q, k, v, mask, block_s=block_s,
                                         interpret=mode == "interpret")
    return temporal_attention_ref(q, k, v, mask)


@partial(jax.jit, static_argnames=("block_s", "mode"))
def fused_recency_attention(q, k_table, v_table, seeds, buf_ids, *,
                            block_s: int | None = None, mode: str = "auto"):
    """q: (S, H, D); k_table, v_table: (N, H, D); seeds: (S,);
    buf_ids: (Nb, K) resident buffer rows -> (S, H, D)."""
    if _use_kernel(mode):
        return fused_recency_attention_kernel(
            q, k_table, v_table, seeds, buf_ids, block_s=block_s,
            interpret=mode == "interpret")
    return fused_recency_attention_ref(q, k_table, v_table, seeds, buf_ids)


def _assemble(flt: dict, aux: dict) -> dict:
    """Merge the differentiable / auxiliary operand dicts back into the
    keyword form shared by the kernel and the reference."""
    kw = dict(aux)
    kw.update(flt)
    return kw


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_layer_call(flt, aux, block_s, interpret):
    return fused_temporal_layer_kernel(
        **_assemble(flt, aux), block_s=block_s, interpret=interpret)


def _fused_layer_fwd(flt, aux, block_s, interpret):
    return _fused_layer_call(flt, aux, block_s, interpret), (flt, aux)


def _fused_layer_bwd(block_s, interpret, res, g):
    # Flash-style backward *kernel*: restage the neighborhoods through the
    # same double-buffered DMA pipeline, recompute the attention weights in
    # VMEM and accumulate every gradient in place — the (S, K, H, D)
    # intermediates the oracle-recompute backward used to materialize never
    # exist in HBM (see fused_temporal_layer_bwd_kernel).
    flt, aux = res
    grads = fused_temporal_layer_bwd_kernel(
        g, **_assemble(flt, aux), block_s=block_s, interpret=interpret)
    out = {name: grads[name].reshape(p.shape).astype(p.dtype)
           for name, p in flt.items()}
    return out, None


_fused_layer_call.defvjp(_fused_layer_fwd, _fused_layer_bwd)


def fused_temporal_layer(q, k_table, v_table, seeds, seed_times, buf, *,
                         time_w=None, time_b=None, wt_k=None, wt_v=None,
                         edge_feats=None, we_k=None, we_v=None,
                         block_s: int | None = None, mode: str = "auto"):
    """Fused TGAT/TGN-style layer attention over the packed recency buffer.

    Computes, for each seed ``s`` with packed buffer row ``buf[seeds[s]]``:

      k[s, j] = k_table[id_j] + phi(t_s - t_j) @ wt_k
                + edge_feats[eid_j] @ we_k        (v analogously)
      out[s]  = softmax((q[s] * scale) . k[s]) @ v[s]   over valid slots

    q: (S, H, D); k_table/v_table: (N, H, D) node-level projected terms
    (dense bias already folded in by the caller); seeds/seed_times: (S,)
    int32 (seeds < 0 — hop-2 frontier padding — produce zero rows and zero
    gradients); buf: (Nb, K, 3). The time group (``time_w``, ``time_b``,
    ``wt_k``, ``wt_v``) and edge group (``edge_feats``, ``we_k``, ``we_v``)
    are each optional but all-or-nothing.

    ``mode`` selects the implementation:
      * ``"auto"``      — Pallas kernel on TPU, jnp reference elsewhere;
      * ``"ref"``       — force the materializing jnp oracle;
      * ``"kernel"``    — force the Pallas kernel (compiled);
      * ``"interpret"`` — force the kernel in interpret mode (CPU parity
                          tests and jaxpr inspection).

    ``block_s`` overrides the seeds the kernels compute per block (tests);
    by default they derive it from the shapes (``kernel._plan``).

    The kernel path is differentiable via a custom VJP whose backward is
    the flash-style Pallas backward kernel — both directions of a jitted
    train step stay gather-free in HBM (``edge_feats`` is treated as
    non-differentiable storage).
    """
    flt, aux = _pack_operands(q, k_table, v_table, seeds, seed_times, buf,
                              time_w, time_b, wt_k, wt_v,
                              edge_feats, we_k, we_v)
    return _dispatch_layer(flt, aux, block_s, mode)


def _pack_operands(q, k_table, v_table, seeds, seed_times, buf, time_w,
                   time_b, wt_k, wt_v, edge_feats, we_k, we_v):
    """Split layer operands into the differentiable / auxiliary dicts the
    custom-VJP calls take (time and edge groups each all-or-nothing)."""
    flt = {"q": q, "k_table": k_table, "v_table": v_table}
    aux = {"seeds": seeds, "seed_times": seed_times, "buf": buf}
    if wt_k is not None:
        flt.update(time_w=time_w, time_b=time_b, wt_k=wt_k, wt_v=wt_v)
    if we_k is not None:
        flt.update(we_k=we_k, we_v=we_v)
        aux.update(edge_feats=edge_feats)
    return flt, aux


def _dispatch_layer(flt, aux, block_s, mode):
    """Mode dispatch shared by the plain and shard-aware layer wrappers."""
    if _use_kernel(mode):
        return _fused_layer_call(flt, aux, block_s, mode == "interpret")
    return fused_temporal_layer_ref(**_assemble(flt, aux))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_layer_sharded_call(flt, aux, axis, block_s, mode):
    return jax.lax.psum(_dispatch_layer(flt, aux, block_s, mode), axis)


def _fused_layer_sharded_fwd(flt, aux, axis, block_s, mode):
    return _fused_layer_sharded_call(flt, aux, axis, block_s, mode), (flt, aux)


def _fused_layer_sharded_bwd(axis, block_s, mode, res, g):
    # The forward is ``psum_axis(local_s)``; downstream compute is
    # node-replicated, so the incoming cotangent ``g`` is identical on
    # every node shard. Recompute the *local* call's VJP (flash-style —
    # residuals are just the operands), apply it to ``g``, then psum the
    # operand cotangents over the node axis: every shard ends up holding
    # the true Σ_s ∂local_s — the exact single-device layer gradient —
    # so no collectives are needed on the rest of the (node-replicated)
    # gradient tree.
    flt, aux = res
    _, vjp = jax.vjp(lambda f: _dispatch_layer(f, aux, block_s, mode), flt)
    (grads,) = vjp(g)
    grads = jax.tree.map(lambda x: jax.lax.psum(x, axis), grads)
    return grads, None


_fused_layer_sharded_call.defvjp(_fused_layer_sharded_fwd,
                                 _fused_layer_sharded_bwd)


def fused_temporal_layer_sharded(q, k_table, v_table, seeds, seed_times,
                                 buf, *, axis: str, rows_per_shard: int,
                                 time_w=None, time_b=None, wt_k=None,
                                 wt_v=None, edge_feats=None, we_k=None,
                                 we_v=None, block_s: int | None = None,
                                 mode: str = "auto"):
    """Shard-aware ``fused_temporal_layer``: partial attention per node
    shard, assembled exactly by one psum over the mesh's node axis.

    Call this *inside* a ``shard_map`` body over a mesh with node axis
    ``axis``. ``buf`` is the shard's local ``(rows_per_shard + 1, K, 3)``
    block of the node-partitioned packed buffer (its sink at local row
    ``rows_per_shard``; see ``DeviceRecencySampler.packed_buffer``), while
    ``seeds`` carry *global* node ids and ``q``/``k_table``/``v_table``/
    weight groups are node-replicated — the buffer's id/eid channels hold
    global ids, so the in-kernel k/v/edge gathers need no remap. Each
    shard remaps the seeds it owns (``[s*per, (s+1)*per)``) to local
    buffer rows and marks the rest ``-1`` — the kernel family's existing
    zero-output / zero-gradient path — computing only its owned seeds'
    attention from rows it holds in local HBM/VMEM; the psum then sums
    exactly one owner's value with exact zeros, so the assembled output is
    bit-identical to the single-device layer at any shard count.

    Differentiation goes through a custom VJP that psums the *layer
    operand* cotangents over ``axis`` (see ``_fused_layer_sharded_bwd``),
    which keeps per-device gradients equal to the true gradients without
    collectives over the rest of the gradient tree. ``mode`` as in
    ``fused_temporal_layer``.
    """
    per = int(rows_per_shard)
    lo = jax.lax.axis_index(axis).astype(jnp.int32) * per
    seeds = seeds.astype(jnp.int32)
    owned = (seeds >= lo) & (seeds < lo + per)
    local = jnp.where(owned, seeds - lo, -1)
    flt, aux = _pack_operands(q, k_table, v_table, local,
                              seed_times.astype(jnp.int32), buf,
                              time_w, time_b, wt_k, wt_v,
                              edge_feats, we_k, we_v)
    return _fused_layer_sharded_call(flt, aux, axis, block_s, mode)


def fused_temporal_layer_hop2(q, k_table, v_table, frontier, frontier_times,
                              buf, **kw):
    """Hop-2-aware variant: embed the (S, K) hop-1 frontier over the buffer.

    ``frontier``/``frontier_times``: (S, K) int32 hop-1 neighbor ids and
    interaction times straight from the sampler hook (padding = -1); each
    frontier node queries the resident buffer *at its own interaction time*
    — the layer-0 compute of 2-layer TGAT. q: (S*K, H, D) frontier queries
    (row-major flattened). Returns (S*K, H, D) with zero rows (and zero
    gradients) for padded frontier slots; no (S, K, ·) float tensor is
    created here. Keyword arguments as in ``fused_temporal_layer``.
    """
    return fused_temporal_layer(
        q, k_table, v_table,
        frontier.reshape(-1).astype(jnp.int32),
        frontier_times.reshape(-1).astype(jnp.int32),
        buf, **kw)


def fused_temporal_layer_per_seed(q, k_rows, v_rows, seed_times, nbr_times,
                                  nbr_mask, *, nbr_eids=None, **kw):
    """Per-seed-embedding-table variant: seed ``s`` attends over *its own*
    K rows of an (S*K, H, D) table — 2-layer TGAT's final hop, where the
    keys/values come from computed hop-1 embeddings rather than a shared
    node table.

    q: (S, H, D); k_rows/v_rows: (S*K, H, D) per-seed projected rows (row
    ``s*K + j`` is seed s's j-th neighbor); seed_times: (S,); nbr_times /
    nbr_mask (and optional nbr_eids, for the edge bias group): (S, K).
    Expressed as a synthetic packed buffer (ids = row indices where valid,
    else -1) over the rows table, so the same fused kernel — and its
    backward — serves the final hop; gradients flow into ``k_rows`` /
    ``v_rows`` via the table gradient. Returns (S, H, D).
    """
    S = q.shape[0]
    K = nbr_mask.shape[1]
    rows = jnp.arange(S * K, dtype=jnp.int32).reshape(S, K)
    ids = jnp.where(nbr_mask, rows, -1)
    eids = (jnp.full((S, K), -1, jnp.int32) if nbr_eids is None
            else jnp.where(nbr_mask, nbr_eids.astype(jnp.int32), -1))
    buf = jnp.stack([ids, nbr_times.astype(jnp.int32), eids], axis=-1)
    return fused_temporal_layer(
        q, k_rows, v_rows, jnp.arange(S, dtype=jnp.int32),
        seed_times.astype(jnp.int32), buf, **kw)
