"""Sorted segment-sum Pallas TPU kernel (discretization psi_r / GCN
aggregation hot spot).

TPU adaptation note (DESIGN.md §2): GPU implementations scatter with atomic
adds; TPUs have no atomics, so the scatter is recast as a *one-hot matmul*
on the MXU: for each edge block, ``out += onehot(seg_ids_block) @ data_block``
where onehot is (num_segments, block_e). The whole (num_segments, D) output
tile stays resident in VMEM across the sequential edge-block walk, so each
output element is written to HBM exactly once.

Grid: (num_edge_blocks,) sequential ("arbitrary") — the output block is
revisited every step (accumulator semantics).

VMEM budget: out (G, D) + onehot (G, block_e) + data (block_e, D); with
G=2048, D=128, block_e=256 that is ~3.3 MiB f32. ops.py tiles larger
segment spaces into G-sized chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segment_sum_kernel(seg_ref, data_ref, o_ref, *, num_segments: int,
                        block_e: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = seg_ref[...]  # (1, block_e) int32; -1 = padding
    data = data_ref[...].astype(jnp.float32)  # (block_e, D)
    # one-hot (G, block_e) on the fly; padding rows match no segment
    seg_grid = jax.lax.broadcasted_iota(jnp.int32, (num_segments, block_e), 0)
    onehot = (seg_grid == seg).astype(jnp.float32)
    # HIGHEST keeps the f32 data exact through the MXU (the one-hot side
    # is exact at any precision).
    o_ref[...] += jax.lax.dot(
        onehot, data, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def segment_sum_kernel(data, seg_ids, num_segments: int, *,
                       block_e: int = 256, interpret: bool = False):
    """data: (E, D); seg_ids: (E,) int32 in [0, num_segments) or -1 padding.

    Returns (num_segments, D). ``num_segments * D`` must fit VMEM; the ops
    wrapper tiles bigger segment spaces. The ids travel as one (1, E) row,
    blocked (1, block_e), so their tiling matches XLA's for any
    ``block_e`` that is a multiple of 128 (a 1-D id block is refused on
    TPU: XLA tiles s32[E] by 1024, Mosaic the block by ``block_e``).
    """
    E, D = data.shape
    pad = (-E) % block_e
    if pad:
        data = jnp.pad(data, ((0, pad), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, pad), constant_values=-1)
    ne = (E + pad) // block_e

    out = pl.pallas_call(
        functools.partial(_segment_sum_kernel, num_segments=num_segments,
                          block_e=block_e),
        grid=(ne,),
        in_specs=[
            pl.BlockSpec((1, block_e), lambda i: (0, i)),
            pl.BlockSpec((block_e, D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(seg_ids.astype(jnp.int32).reshape(1, -1), data)
    return out
