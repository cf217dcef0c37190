"""Compile the main path's Pallas kernels for a TPU v5e chip, without one.

Interpret-mode parity (``tests/kernels/``) cannot see what the TPU compiler
refuses: contractions Mosaic cannot lower, DMA slices that cut a memory
tile, id blocks whose tiling disagrees with XLA's. Each test here lowers a
kernel at TGAT's real widths on the wikipedia-shaped stream (H=2, D=50,
K=20, d_time=100, d_edge=172, N=9,000 nodes, E=157,474 events) for a
described ``v5e:2x2`` topology and compiles it with the chip's compiler
(libtpu), then checks that the kernel is in the compiled program.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and every pytest worker imports
this file. The persistent compilation cache is off around the compiles: a
compile for a described chip can be written to it but not read back.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, D, K, D_TIME, D_EDGE = 2, 50, 20, 100, 172
N_NODES, N_EVENTS = 9_000, 157_474
TRAIN_SEEDS, EVAL_SEEDS = 600, 4_400  # batch 200 x (2 + 1 / 20 negatives)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer_operands(sharding, n_seeds, n_table=N_NODES):
    """Shapes of one fused TGAT layer call: queries, node tables, seed ids
    and times, the packed recency buffer, and both bias groups."""
    s = lambda shape, dtype=jnp.float32: _spec(sharding, shape, dtype)  # noqa: E731
    return dict(
        q=s((n_seeds, H, D)), k_table=s((n_table, H, D)),
        v_table=s((n_table, H, D)), seeds=s((n_seeds,), jnp.int32),
        seed_times=s((n_seeds,), jnp.int32),
        buf=s((N_NODES + 1, K, 3), jnp.int32),
        time_w=s((D_TIME,)), time_b=s((D_TIME,)),
        wt_k=s((D_TIME, H * D)), wt_v=s((D_TIME, H * D)),
        edge_feats=s((N_EVENTS + 1, D_EDGE)),
        we_k=s((D_EDGE, H * D)), we_v=s((D_EDGE, H * D)),
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("n_seeds", [TRAIN_SEEDS, EVAL_SEEDS])
def test_fused_layer_forward_compiles(one_chip, n_seeds):
    from repro.kernels.temporal_attention import fused_temporal_layer

    _compile(lambda a: fused_temporal_layer(**a, mode="kernel"),
             _layer_operands(one_chip, n_seeds))


def test_fused_layer_backward_compiles(one_chip):
    """jax.grad through the custom VJP stages the forward and the
    flash-style backward kernel."""
    from repro.kernels.temporal_attention import fused_temporal_layer

    a = _layer_operands(one_chip, TRAIN_SEEDS)
    diff = {k: a.pop(k) for k in ("q", "k_table", "v_table", "wt_k",
                                  "wt_v", "we_k", "we_v", "time_w",
                                  "time_b")}

    def loss(d, rest):
        return jnp.sum(jnp.sin(fused_temporal_layer(**d, **rest,
                                                    mode="kernel")))

    compiled = _compile(jax.grad(loss), diff, a)
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_hop2_frontier_compiles(one_chip):
    """The 2-layer TGAT frontier: S*K = 12,000 hop-1 neighbors query the
    buffer at their own interaction times (padding ids -1)."""
    from repro.kernels.temporal_attention import fused_temporal_layer_hop2

    a = _layer_operands(one_chip, TRAIN_SEEDS * K)
    frontier = _spec(one_chip, (TRAIN_SEEDS, K), jnp.int32)
    a.pop("seeds")
    a.pop("seed_times")
    _compile(lambda a, f, ft: fused_temporal_layer_hop2(
        frontier=f, frontier_times=ft, **a, mode="kernel"),
        a, frontier, frontier)


@pytest.mark.parametrize("width", [1, 128])
def test_segment_sum_compiles(one_chip, width):
    """GCN aggregation over N=9,000 nodes (tiled into 2,048-segment
    kernels): degree counts (width 1) and 128-dim messages."""
    from repro.kernels.segment_reduce import segment_sum

    data = _spec(one_chip, (4096, width))
    ids = _spec(one_chip, (4096,), jnp.int32)
    _compile(lambda d, s: segment_sum(d, s, N_NODES, mode="kernel"),
             data, ids)
