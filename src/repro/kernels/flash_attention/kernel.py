"""Flash attention Pallas TPU kernel.

Grid: (B * H, num_q_blocks, num_kv_blocks); the kv axis is the innermost,
sequential ("arbitrary") dimension so the online-softmax state (running
max / denominator / accumulator) lives in VMEM scratch across kv steps.

BlockSpec tiling (all VMEM):
  q:   (1, block_q, D)   — one q block per (bh, qi)
  k/v: (1, block_k, D)   — streamed over ki; GQA maps the q head to its
                           kv head inside the index map (no kv replication
                           in HBM)
  o:   (1, block_q, D)

Default blocks 128 x 128 keep the MXU fed (D is 64/128 for all assigned
archs) and the VMEM working set at ~(2*block_k*D + 3*block_q*D + block_q *
block_k) * 4B < 0.5 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, num_kv_blocks: int, skv: int, sq: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, D)
    k = k_ref[0].astype(jnp.float32)  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # align causality for Sq != Skv (decode chunks): offset = Skv - Sq
    qpos = qpos + (skv - sq)
    allow = kpos < skv
    if causal:
        allow &= kpos <= qpos
    if window:
        allow &= kpos > qpos - window
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           scale: float | None = None,
                           interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hk, Skv, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Hk, Skv = k.shape[1], k.shape[2]
    G = H // Hk
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nk = (Skv + pad_k) // block_k

    qf = q.reshape(B * H, Sq + pad_q, D)
    kf = k.reshape(B * Hk, Skv + pad_k, D)
    vf = v.reshape(B * Hk, Skv + pad_k, D)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        # GQA: query head bh = b * H + h uses kv head b * Hk + h // G
        b = bh // H
        h = bh % H
        return (b * Hk + h // G, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk, skv=Skv, sq=Sq,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, H, Sq, D)
