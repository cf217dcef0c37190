"""Mamba2 SSD chunk Pallas TPU kernel.

Implements the state-space-duality chunked algorithm with the inter-chunk
recurrence FUSED into the same kernel: the grid walks chunks sequentially
per (head,) program, carrying the running state (P, N) in VMEM scratch.
This avoids materializing per-chunk states in HBM (the pure-jnp path
round-trips (B, nc, H, P, N)).

Grid: (H, num_chunks) with the chunk axis sequential ("arbitrary").
Blocks (VMEM):
  x:  (1, Q, P)    dt: (1, Q)    B, C: (1, Q, N)    y: (1, Q, P)
  scratch: state (P, N) f32, persists across the chunk walk.

Per chunk, the MXU work is (Q,N)x(N,Q) scores, (Q,Q)x(Q,P) intra-chunk
output, (N,Q)x(Q,P) state update — all 128-aligned when Q=128, N=64/128,
P=64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_scr, *,
                      chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    a = a_ref[0]  # scalar decay rate for this head (negative)
    x = x_ref[0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)  # (Q,)
    B = b_ref[0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0].astype(jnp.float32)  # (Q, N)

    adt = dt * a  # (Q,) log-decay per step
    cum = jnp.cumsum(adt)  # (Q,) inclusive
    # intra-chunk decay matrix L[i, j] = exp(cum_i - cum_j), i >= j
    diff = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    xdt = x * dt[:, None]  # (Q, P)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (Q, Q)
    y_diag = jax.lax.dot(scores * L, xdt)  # (Q, P)

    # contribution of the carried state: y_off = (C * exp(cum)) @ state^T
    state = s_scr[...]  # (P, N)
    y_off = jax.lax.dot_general(C * jnp.exp(cum)[:, None], state,
                                (((1,), (1,)), ((), ())))  # (Q, P)
    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: s' = exp(sum adt) * s + sum_j exp(cum_end - cum_j) B_j (dt x)_j
    decay_end = jnp.exp(cum[-1] - cum)  # (Q,)
    wB = B * decay_end[:, None]  # (Q, N)
    s_new = jax.lax.dot_general(xdt, wB, (((0,), (0,)), ((), ())))  # (P, N)
    s_scr[...] = state * jnp.exp(cum[-1]) + s_new


def ssd_chunk_kernel(x, dt, a, B, C, *, chunk: int = 128,
                     interpret: bool = False):
    """x: (S, H, P); dt: (S, H); a: (H,); B, C: (S, H, N).

    Returns y: (S, H, P). S is padded to a chunk multiple internally
    (padded steps have dt=0 -> exp(0)=1 decay, zero input).
    """
    S, H, P = x.shape
    N = B.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
        B = jnp.pad(B, ((0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    # head-major layout so each (h, chunk) block is contiguous
    xh = jnp.moveaxis(x, 1, 0)  # (H, Sp, P)
    dth = jnp.moveaxis(dt, 1, 0)  # (H, Sp)
    Bh = jnp.moveaxis(B, 1, 0)  # (H, Sp, N)
    Ch = jnp.moveaxis(C, 1, 0)

    out = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk),
        grid=(H, nc),
        in_specs=[
            pl.BlockSpec((1,), lambda h, c: (h,)),
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk), lambda h, c: (h, c)),
            pl.BlockSpec((1, chunk, N), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, c: (h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Sp, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, xh, dth, Bh, Ch)
    return jnp.moveaxis(out, 0, 1)[:S]
